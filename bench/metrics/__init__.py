"""One reader per metric, found by the metric's name.

``bench/metrics/<name>.py`` defines ``read(run) -> float | None``; a name
with a suffix after a dot (``decode_step_ms.rate``) that has no file of its
own is read by the file of the part before the dot, so a quantity split by
the end-to-end metric it moves keeps one reader. ``run`` is the
``harness.Run`` of the finished window. A reader that finds nothing to read
returns None and the metric is left out of the line.
"""
import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def reader(name: str, base: Path = HERE):
    for stem in (name, name.split(".")[0]):
        path = base / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(f"bench_metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under {base}")
