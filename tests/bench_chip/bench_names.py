"""Names and fixtures shared by the chip benchmark's CPU tests (imported by
the test modules; a ``conftest.py`` here would shadow the suite's own).

The fixtures give a tiny grid in place of each configuration's network, and
a network cache of the session's own."""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
ROOT = BENCH.parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

# each configuration cut to a grid the CPU runs in seconds; the widths (k,
# arc lengths, batch shapes) stay as configured
TINY = {
    "config.network.grid": 14,
    "config.vertices": 196,
    "config.mu": 0.12,
    "config.fleet_size": 30,
}
CELLS = ["grid362-poi-k20.zipf-read", "grid362-poi-k20.rebuild"]
# the read cell turned into a dispatch tick: a fleet moving at YCSB B's
# update share beside 8 batches of 1024 queries, one flush a tick; the
# generic driver's move and flush path, which no cell of BENCHMARK.json
# drives yet
FLEET = {
    "config.objects": "fleet",
    "config.k": 10,
    "traffic.tick.update_share": 0.05,
    "traffic.tick.query_batches": 8,
    "traffic.tick.batch": 1024,
    "traffic.tick.flush": True,
}


@pytest.fixture(scope="session", name="cache_dir")
def cache_dir_fixture(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_chip_cache")


@pytest.fixture(name="run_tiny")
def run_tiny_fixture(cache_dir):
    """One run of a cell on the CPU at the tiny grid; returns the result."""
    import harness

    def go(cell: str, seed: int = 2**31 + 17, seconds: float = 0.3, **kw):
        return harness.run(cell, seed, seconds, False, t_start=time.perf_counter(),
                           cache_dir=cache_dir, require_tpu=False,
                           overrides=dict(TINY, **kw.pop("overrides", {})),
                           log=lambda msg: None, **kw)

    return go
