"""Self-tests of the chip benchmark, on the CPU. Run by hand:

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m pytest chipbench/tests -q
"""

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import spec  # noqa: E402

#: each cell cut in rows to a size the CPU runs in seconds; each keeps
#: its width, since the check's float32 rounding scale depends on it.
#: Every other key, the limits among them, is the cell's own
TINY = {
    "retrieval_1m": {"rows": 5000, "k": 10, "block_rows": 1024,
                     "check_sample": 64, "server": {"max_batch": 8}},
    "lmhead_ds67b": {"rows": 2048, "rank": 8192, "k": 5, "block_rows": 512,
                     "check_sample": 32, "server": {"max_batch": 16}},
}
TINY_TRAFFIC = {"poisson": {"rate_per_s": 300},
                "decode128": {"batch": 16, "pool_steps": 4}}


@pytest.fixture()
def tiny(tmp_path):
    """A copy of the benchmark's files with every cell cut to a tiny
    size; returns ``(base, bench)``."""
    base = tmp_path / "chipbench"
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(spec.BENCH_DIR / d, base / d)
    for kind, table in (("configs", TINY), ("traffic", TINY_TRAFFIC)):
        for name, change in table.items():
            path = base / kind / f"{name}.json"
            data = json.loads(path.read_text())
            data.update(change)
            path.write_text(json.dumps(data))
    return base, spec.load_benchmark()
