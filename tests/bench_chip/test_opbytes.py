"""Each op/byte function against a hand count on small shapes, and the
readers' arithmetic on a hand-made record."""
import numpy as np
import pytest

import bench_names  # noqa: F401  (puts benchmarks/chip on the path)
import devtrace
import harness
import network

ROOFLINE_KIND = "TPU v5 lite"


def test_serve_gather_bytes_by_hand():
    b, k = 2, 3
    rows_read = b * k * (4 + 4)     # k int32 ids + k float32 dists per row
    answer_written = b * k * (4 + 4)
    ids_and_ks = b * 4 + b * 4
    assert harness.load_metric("serve_gather_roofline").bytes_needed(b, k) \
        == rows_read + answer_written + ids_and_ks == 112


def test_sweep_bytes_by_hand():
    entries, n, k = 5, 4, 2
    per_entry = k * 8 + 4 + 4       # neighbor row, the entry's id and length
    per_vertex = 4 + k * 8 + k * 8  # its id, its extras row, the row written
    f = harness.load_metric("sweep_program_roofline").sweep_bytes
    assert f(entries, n, k) == entries * per_entry + n * per_vertex == 264


def test_sweep_entries_count_the_valid_schedule_cells(tmp_path):
    spec = {"grid": 6, "delete_frac": 0.18, "diag_frac": 0.08, "weight_low": 100,
            "weight_high": 1000, "graph_seed": 3}
    _, bn = network.load_network(spec, tmp_path)
    by_hand = [sum(1 for row in tab for x in row if x >= 0) for tab in (bn.lo_ids, bn.hi_ids)]
    # every undirected BN-Graph edge is one lower and one higher entry
    assert by_hand[0] == by_hand[1]
    assert (int((bn.lo_ids >= 0).sum()), int((bn.hi_ids >= 0).sum())) == tuple(by_hand)


def _record(**kw):
    cell = harness.Cell("c", 1, {}, {"tick": {"batch": 4}}, [], [])
    return harness.Record(cell=cell, k=2, n=4, device_kind=ROOFLINE_KIND, **kw)


def test_roofline_readers_on_a_hand_made_trace():
    peak = harness.peak(ROOFLINE_KIND, "hbm_bytes_per_s")
    summ = devtrace.Summary(window_s=1.0, busy_s=0.25,
                            programs_s={"jit_serve_gather": 1e-6, "jit__sweep_program": 2e-6},
                            program_calls={"jit_serve_gather": 3, "jit__sweep_program": 2},
                            device_ops=[], idle_gaps=[])
    rec = _record(trace=summ, sweep_entries=(5, 5))
    g = harness.load_metric("serve_gather_roofline").read(rec)
    assert g == pytest.approx(100 * 3 * (2 * 4 * 2 * 8 + 2 * 4 * 4) / (1e-6 * peak))
    s = harness.load_metric("sweep_program_roofline").read(rec)
    assert s == pytest.approx(100 * 1 * 2 * 264 / (2e-6 * peak))
    assert harness.load_metric("idle_pct.read").read(rec) == pytest.approx(75.0)
    assert harness.load_metric("serve_gather_roofline").read(_record()) is None


def test_unknown_device_has_no_peak():
    with pytest.raises(harness.BenchError):
        harness.peak("cpu", "hbm_bytes_per_s")


def test_end_to_end_readers_by_hand():
    rec = _record(window_s=2.0, batch_lat_s=[0.003, 0.001, 0.002], batch_size=[4, 4, 4],
                  dispatch_s=[0.001, 0.002, 0.003], builds_s=[0.5, 0.5], setup_s=7.0)
    read = lambda name: harness.load_metric(name).read(rec)  # noqa: E731
    assert read("query_qps") == 6.0
    assert read("query_p95_ms") == pytest.approx(3.0)
    assert read("build_s") == 1.0
    assert read("setup_s") == 7.0
    assert read("query_dispatch_ms") == pytest.approx(2.0)


def test_nearest_rank_percentile():
    assert harness.percentile([1, 2, 3, 4], [1, 1, 1, 1], 95) == 4
    assert harness.percentile([1, 2, 3, 4], [97, 1, 1, 1], 95) == 1
    assert harness.percentile(np.arange(1, 101), np.ones(100), 95) == 95
