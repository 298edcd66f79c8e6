"""Every cell runs end to end on the CPU at a tiny grid, and the command
refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench_names import CELLS, FLEET, ROOT, cache_dir_fixture, run_tiny_fixture  # noqa: F401
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("cell,overrides", [(c, {}) for c in CELLS] + [(CELLS[0], FLEET)],
                         ids=CELLS + ["fleet"])
def test_cell_runs_end_to_end(cell, overrides, run_tiny, capsys):
    import harness

    result = run_tiny(cell, overrides=overrides)
    harness.emit(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == CONTRACT_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert err.strip().splitlines()[-1] == "wrong_answers: 0 (limit 0)"
    bench = harness.load_benchmark()
    want = {m["name"] for m in bench["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_fleet_moves_are_ycsb_b_update_share():
    """A fleet tick's moves are 5% of its operations (YCSB workload B): 431
    beside 8 x 1024 queries; each moves one vehicle one street to a vertex
    no other vehicle holds."""
    import harness
    import network

    cell = harness.resolve(harness.load_benchmark(), CELLS[0])
    for key, value in FLEET.items():
        harness.override(cell, key, value)
    g = network.road_network(dict(cell.config["network"], grid=60))
    traffic = harness.Traffic(cell.traffic, g, cell.config["k"], 2**31 + 5, 4)
    assert traffic.moves_per_tick(2621) == 431
    pos = harness.draw_objects(dict(cell.config, fleet_size=800), g.n,
                               np.random.default_rng(1)).tolist()
    before, occupied = list(pos), set(pos)
    moves = traffic.moves(pos, occupied)
    assert len(moves) == 431 and len({u for u, _ in moves}) == 431
    assert len(set(pos)) == len(pos) and occupied == set(pos)
    for u, v in moves:
        assert v in g.neighbors(u)[0]
    assert sum(a != b for a, b in zip(before, pos)) == 431


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0], "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_refuses_in_a_checkout_of_only_the_benchmark(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns(".cache"))
    p = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
