"""Launcher-level tests: knn_build writes an artifact that serve.py serves,
and serve.py's ranges=auto drift detector on forced host devices."""
import subprocess
import sys

from conftest import REPO


def test_knn_build_then_serve_artifact(tmp_path):
    """knn_build --out writes a QueryEngine artifact that serve.py loads and
    serves under mixed query+update traffic."""
    import json
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/src"
    art = str(tmp_path / "index.npz")
    build = subprocess.run(
        [sys.executable, "-m", "repro.launch.knn_build",
         "--grid", "10", "--k", "4", "--mu", "0.2", "--out", art],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert build.returncode == 0, build.stderr
    stats = json.loads(build.stdout)
    assert stats["index_bytes"] == stats["n"] * stats["k"] * 8
    assert os.path.exists(art)

    serve = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--smoke", "--grid", "10", "--k", "4",
         "--mu", "0.2", "--ops", "600", "--query-batch", "128",
         "--update-frac", "0.05", "--artifact", art],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert serve.returncode == 0, serve.stderr
    out = json.loads(serve.stdout)
    assert out["queries"] > 0 and out["queries_per_s"] > 0
    assert out["updates"] > 0
    assert out["engine"]["staged_queue_depth"] == 0  # all flushed
    assert out["engine"]["flushes"] > 0


def test_serve_auto_ranges_drift_resplit():
    """ranges=auto is a continuous drift detector: the initial skew triggers
    a first re-split, then --hot-flip-round moves the zipf city to another
    shard's range and the detector must fire a *second* repartition after
    the cooldown — plus the collective halo serves every multi-shard flush
    without falling back."""
    import json
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/src"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    flip = 8
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--smoke", "--grid", "10", "--k", "4",
         "--batch", "128", "--ops", "2500", "--seed", "3",
         "--partition", "shards=4,ranges=auto",
         "--hot-shard", "0", "--hot-frac", "0.9",
         "--hot-flip-round", str(flip)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout)
    resplits = out["repartition_rounds"]
    assert len(resplits) >= 2, resplits
    assert resplits[0] < flip  # warmup skew caught before the flip
    assert any(r >= flip for r in resplits)  # the moved city caught after
    assert out["repartitioned_at_round"] == resplits[0]
    assert out["errors"] == 0
    assert out["engine"]["halo"] == "collective"
    assert out["engine"]["halo_rounds_collective"] > 0
    assert out["engine"]["halo_fallbacks"] == 0
