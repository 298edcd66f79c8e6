"""``BENCHMARK.json`` keeps to the benchmark's contract, and every cell,
configuration, traffic mix and metric is found by name, so a new one is new
files plus entries."""
import json
import re
import shutil
import time

import pytest

from bench_names import BENCH, ROOT, TINY, cache_dir_fixture  # noqa: F401
import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_top_level_keys_and_paths(bench):
    assert list(bench) == ["command", "paths", "run_seconds", "configs", "workloads",
                           "end_to_end", "per_layer"]
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and (ROOT / p).is_dir()


def test_names_units_and_bounds(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in bench["workloads"]] + [
        c["name"] for c in bench["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_every_cell_reports_what_the_contract_asks(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        cell = harness.resolve(bench, w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e and m["moves"] in reported


def test_config_files_state_their_source_cut_and_whys(bench):
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["why"] == c["why"]
        assert cfg["reduced"] == c["reduced"] and all(k in cfg for k in cfg["reduced"])
        assert cfg["guarantees"] and cfg["assumed"] and cfg["source"]
        cells = {w["name"]: w["why"] for w in bench["workloads"] if w["config"] == c["name"]}
        assert cfg["cells"] == cells
        assert cfg["vertices"] == cfg["network"]["grid"] ** 2


def test_everything_is_found_by_name(bench):
    for w in bench["workloads"]:
        cell = harness.resolve(bench, w["name"])
        assert cell.traffic["name"] == w["traffic"]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.load_metric(m["name"]).read)


def test_every_mix_key_is_read_by_the_driver(bench):
    """A mix holds only parameters the one generator and driver read: no key
    that looks like a switch and selects nothing."""
    read = {"rebuild", "update_share", "query_batches", "batch", "origins", "k", "flush"}
    for w in bench["workloads"]:
        mix = harness.resolve(bench, w["name"]).traffic
        assert set(mix) == {"name", "why", "tick", "warmup", "check"}
        assert set(mix["tick"]) <= read
        assert set(mix["warmup"]) == {"min_ticks", "quiet_ticks", "max_ticks"}


def test_a_new_cell_is_new_files_plus_entries(tmp_path, cache_dir):
    """A copy of the benchmark gains a configuration, a mix, an end-to-end
    metric and a per-layer metric as new files plus entries only, and its
    new cell runs."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    dst = tmp_path / BENCH.relative_to(ROOT)
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    cfg = json.loads((dst / "configs" / "grid362-poi-k20.json").read_text())
    cfg.update(name="grid362-poi-k8", k=8)
    (dst / "configs" / "grid362-poi-k8.json").write_text(json.dumps(cfg))
    mix = json.loads((dst / "traffic" / "zipf-read.json").read_text())
    mix["name"] = "small-batch"
    mix["tick"]["batch"] = 64
    (dst / "traffic" / "small-batch.json").write_text(json.dumps(mix))
    (dst / "metrics" / "query_p50_ms.py").write_text(
        "from harness import percentile\n\n\ndef read(rec):\n"
        "    return 1e3 * percentile(rec.batch_lat_s, rec.batch_size, 50)\n")
    (dst / "metrics" / "batches.py").write_text(
        "def read(rec):\n    return len(rec.batch_size) or None\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append(dict(b["configs"][0], name="grid362-poi-k8",
                             file="benchmarks/chip/configs/grid362-poi-k8.json"))
    b["workloads"].append({"name": "grid362-poi-k8.small-batch", "config": "grid362-poi-k8",
                           "traffic": "small-batch", "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "query_p50_ms", "unit": "ms", "better": "lower",
                            "bound": 0.05, "source": "host_clock",
                            "workloads": ["grid362-poi-k8.small-batch"]})
    for m in b["end_to_end"]:
        if m["name"] in ("query_qps", "query_p95_ms"):
            m["workloads"].append("grid362-poi-k8.small-batch")
    b["per_layer"].append({"name": "batches", "unit": "batches", "better": "higher",
                           "source": "host_clock", "layer": "query gather",
                           "moves": "query_qps"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = harness.resolve(b, "grid362-poi-k8.small-batch", tmp_path)
    assert "batches" in [m["name"] for m in cell.per_layer]
    assert harness.load_metric("batches", tmp_path).read is not None
    result = harness.run("grid362-poi-k8.small-batch", 5, 0.2, False, t_start=time.perf_counter(),
                         root=tmp_path, cache_dir=cache_dir, require_tpu=False,
                         overrides=TINY, log=lambda msg: None)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "query_qps", "query_p95_ms", "query_p50_ms"}
