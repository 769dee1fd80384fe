"""A module of the benchmark found by its name: ``<base>/<name>.py``, loaded
from its path, so that a copied benchmark root can add one as a file
without writing into the package. Metric readers, model families and
references are found this way."""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path


@functools.cache
def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def module(base: Path, name: str, what: str):
    """The module in ``base/<name>.py``; an error that names the file when
    there is none. ``what`` says what kind of module it is."""
    path = Path(base).resolve() / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {what} {name!r}: {path} does not exist")
    return _load(path)
