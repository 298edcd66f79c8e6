"""Readings that set the limit of ``correct``: the program's and the control's.

    python3 benchmarks/chip/control.py --workload <cell> --seconds 5 --seeds 1,2,3

For each seed, in one process (set-up is long): the cell's set-up and a short
window at its own load, then the window's sampled answers against the plain
reference (the program's reading: wrong answers, which sound runs hold at
0), and the control put in the program's place on the same sample: the
reference computed in bfloat16, one step below the float32 the
configurations state (the control's reading, which has to come out wrong).
The benchmark's own runs never run this. Without a TPU it refuses, like
``run.py``.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".cache" / "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def readings(cell, seed: int, seconds: float, cache_dir: Path) -> dict:
    import harness

    drv = harness.Driver(cell, seed, cache_dir=cache_dir, traced=False)
    drv.warm_up()
    rec = drv.window(seconds)
    g, checked, k = drv.g, drv.checked, drv.k
    del drv
    sample = harness.sample_answers(checked, seed, int(harness.cell_check(cell, "answers")))
    wrong, notes = harness.compare_answers(g, checked, sample)
    ctrl = harness.control_answers(g, checked, sample, k)
    ctrl_wrong, ctrl_notes = harness.compare_answers(g, checked, ctrl)
    return {"cell": cell.name, "seed": seed, "ticks": rec.ticks, "compared": len(sample),
            "wrong_answers": wrong, "control_wrong_answers": ctrl_wrong,
            "notes": notes, "control_notes": ctrl_notes}


def main(argv=None) -> int:
    import argparse

    import jax

    import harness
    from repro.analysis import sanitize

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    sanitize.enable_compile_cache()
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    for seed in args.seeds.split(","):
        t0 = time.perf_counter()
        r = readings(cell, int(seed), args.seconds, HERE / ".cache")
        r["wall_s"] = time.perf_counter() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
