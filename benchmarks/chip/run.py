"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Every cell, configuration, traffic mix and metric is found by name from
``BENCHMARK.json`` at the root of the checkout (see ``harness.py``). The
last line of standard output is the result object; the numbers compared
with the plain reference are the last lines of standard error. Without a
TPU, or with fewer chips than the cell asks for, it exits nonzero and prints
no result: it never falls back to the CPU. JAX's compile cache and the
network cache live in ``benchmarks/chip/.cache`` inside the checkout.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
# the compile cache is the checkout's own, whatever the machine sets
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".cache" / "jax")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

if __name__ == "__main__":
    import harness

    sys.exit(harness.main(t_start=T_START))
