import os

# the benchmark's own tests run on the CPU, at small sizes, with no
# persistent compilation cache
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import json  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
LATER = json.loads((ROOT / "bench" / "tests" / "data" / "later_cells.json").read_text())


def all_entries() -> dict:
    """BENCHMARK.json with the entries of the cells kept for a later PR
    (``data/later_cells.json``): their files are under ``bench`` and have to
    keep working."""
    bj = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, entries in LATER.items():
        bj[key] = bj[key] + entries
    return bj


@pytest.fixture(scope="session")
def bench_root(tmp_path_factory) -> Path:
    """A benchmark root over this ``bench`` that lists every cell."""
    root = tmp_path_factory.mktemp("root")
    (root / "BENCHMARK.json").write_text(json.dumps(all_entries()))
    (root / "bench").symlink_to(ROOT / "bench")
    return root
