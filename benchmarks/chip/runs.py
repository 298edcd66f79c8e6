"""Run cells one process after another and summarise them (no JAX here).

    python3 benchmarks/chip/runs.py --out <dir> \\
        --run grid362-poi-k20.zipf-read:101:30:0 --run grid362-poi-k20.zipf-read:102:30:0 ...

Each ``--run`` is ``cell:seed:seconds:trace``, optionally ``:label`` (a set
name; runs of one cell and label form a set). Every run is its own process
of ``run.py``, as the benchmark is run, so this parent never touches the
chip. The output and errors of each run go under ``--out``; the summary
line per run, and per cell, set and metric the median and the spread (the
distance between the first and third quartiles of
``statistics.quantiles(values, n=4)`` over the median), go to standard
output and to ``summary.json``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from collections import defaultdict
from pathlib import Path

HERE = Path(__file__).resolve().parent


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--run", action="append", required=True)
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    runs = []
    for i, spec in enumerate(args.run):
        cell, seed, seconds, trace, *label = spec.split(":")
        cmd = [sys.executable, str(HERE / "run.py"), "--workload", cell, "--seed", seed,
               "--seconds", seconds, "--trace", trace]
        t0 = time.perf_counter()
        try:
            p = subprocess.run(cmd, capture_output=True, text=True, timeout=args.timeout)
            rc, so, se = p.returncode, p.stdout, p.stderr
        except subprocess.TimeoutExpired as e:
            rc, so, se = 124, e.stdout or "", e.stderr or ""
            so = so.decode() if isinstance(so, bytes) else so
            se = se.decode() if isinstance(se, bytes) else se
        wall = time.perf_counter() - t0
        tag = f"{i:02d}-{cell}-{seed}-t{trace}"
        (out / f"{tag}.out").write_text(so)
        (out / f"{tag}.err").write_text(se)
        try:
            result = json.loads(so.strip().splitlines()[-1])
        except (IndexError, json.JSONDecodeError):
            result = None
        info = [ln for ln in so.splitlines()[:-1]
                if ln.startswith(("setup_compiles", "window_compiles", "reference_s", "wrong",
                                  "flush_s"))]
        row = {"cell": cell, "seed": int(seed), "trace": int(trace),
               "label": label[0] if label else "", "rc": rc, "wall_s": wall,
               "result": result, "info": info}
        runs.append(row)
        print(json.dumps(row), flush=True)
        if result is None:
            print(se[-3000:], flush=True)
    groups = defaultdict(lambda: defaultdict(list))
    for r in runs:
        if r["result"] and not r["trace"]:
            for name, m in r["result"]["metrics"].items():
                groups[(r["cell"], r["label"])][name].append(m["value"])
    summary = []
    for (cell, label), metrics in groups.items():
        for name, vals in metrics.items():
            row = {"cell": cell, "label": label, "metric": name, "n": len(vals),
                   "median": statistics.median(vals),
                   "spread": spread(vals) if len(vals) >= 2 else None, "values": vals}
            summary.append(row)
            print("SPREAD", json.dumps(row), flush=True)
    (out / "summary.json").write_text(json.dumps({"runs": runs, "spreads": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
