"""One reader per metric, found by the metric's name.

``bench/metrics/<name>.py`` defines ``read(run) -> float | None``; a name
with a suffix after a dot (``decode_step_ms.rate``) that has no file of its
own is read by the file of the part before the dot, so a quantity split by
the end-to-end metric it moves keeps one reader. ``run`` is the
``harness.Run`` of the finished window. A reader that finds nothing to read
returns None and the metric is left out of the line.
"""
from pathlib import Path

from bench import found

HERE = Path(__file__).resolve().parent


def reader(name: str, base: Path = HERE):
    stem = name if (base / f"{name}.py").exists() else name.split(".")[0]
    return found.module(base, stem, "metric reader").read
