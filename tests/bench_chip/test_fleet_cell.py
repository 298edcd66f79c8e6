"""The dispatch cell ``grid362-dispatch-k10.fleet-tick``: it runs end to end
on the CPU at the tiny grid and is correct, a broken flush makes it
incorrect, and its eight per-layer readers read a hand-made record or trace
excerpt, and nothing from an untraced run."""
import json
import types

import pytest

from bench_names import ROOT, cache_dir_fixture, run_tiny_fixture  # noqa: F401
import harness
import spanattrs
from spanattrs import AttrSpan
from test_faults import _flush_unchanged

CELL = "grid362-dispatch-k10.fleet-tick"
KIND = "TPU v5 lite"
READERS = ["flush_rounds", "flush_readbacks", "flush_wait_ms", "frontier_ms", "repair_ms",
           "purge_merge_ms", "idle_pct.fleet", "frontier_round_roofline"]


def test_the_cell_runs_end_to_end_and_is_correct(run_tiny):
    result = run_tiny(CELL)
    assert result["correct"] is True and result["checks"]["wrong_answers"]["value"] == 0
    bench = harness.load_benchmark()
    assert set(result["metrics"]) == {m["name"] for m in bench["end_to_end"]
                                      if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) == {"setup_s", "query_qps", "query_p95_ms"}
    assert result["attempted"] > 0 and result["failed"] == 0


def test_the_cell_shares_the_poi_network_and_reads_its_own_metrics():
    bench = harness.load_benchmark()
    cell = harness.resolve(bench, CELL)
    poi = json.loads((ROOT / "benchmarks/chip/configs/grid362-poi-k20.json").read_text())
    assert cell.config["network"] == poi["network"] and cell.config["k"] == 10
    assert cell.config["objects"] == "fleet" and cell.config["fleet_size"] == 2621
    assert cell.chips == 1 and [m["name"] for m in cell.per_layer] == READERS
    assert all(m["moves"] == "query_qps" for m in cell.per_layer)


def _move_dropped(monkeypatch):
    """Each flush leaves one staged delete and one staged insert out of its
    tables and acknowledges them all the same."""
    from repro.core.engine import EngineCore

    orig = EngineCore._apply_delta

    def dropped(self, deletes, inserts, new_epoch):
        return orig(self, deletes[1:], inserts[1:], new_epoch)

    monkeypatch.setattr(EngineCore, "_apply_delta", dropped)


@pytest.mark.parametrize("fault", [_flush_unchanged, _move_dropped],
                         ids=["flush_unchanged", "move_dropped"])
def test_a_broken_flush_makes_the_cell_incorrect(fault, run_tiny, monkeypatch):
    fault(monkeypatch)
    result = run_tiny(CELL)
    assert result["correct"] is False
    assert result["checks"]["wrong_answers"]["value"] > 0


def _record(traced=True, **trace):
    cell = harness.Cell(CELL, 1, {}, {"tick": {"batch": 1024}}, [], [])
    rec = harness.Record(cell=cell, k=10, n=100, device_kind=KIND)
    rec.flushes = [{"frontier_rounds": 12, "repair_rounds": 8},
                   {"frontier_rounds": 10, "repair_rounds": 6}]
    if traced:
        rec.trace = types.SimpleNamespace(window_s=10.0, idle_share=0.6, **trace)
    return rec


SPANS = {"knn:flush": [7.0, 2], "knn:flush.readback": [4.0, 160],
         "knn:flush.frontier": [6.0, 2], "knn:flush.repair": [1.0, 2]}
PARTS = [
    AttrSpan("knn:flush", 0.0, 100.0, {"epoch": 5, "inserts": 431}),
    AttrSpan("knn:flush.frontier.round", 1.0, 20.0, {"round": 1, "rows": 431}),
    AttrSpan("knn:flush.frontier.part", 2.0, 5.0, {"rows": 1000, "t": 8}),
    AttrSpan("knn:flush.frontier.part", 8.0, 5.0, {"rows": 30, "t": 128}),
    AttrSpan("knn:flush", 200.0, 100.0, {"epoch": 6, "inserts": 7}),
    AttrSpan("knn:flush.frontier.part", 210.0, 5.0, {"rows": 64, "t": 32}),
    AttrSpan("knn:flush.repair.part", 250.0, 5.0, {"rows": 99, "t": 8}),
]


@pytest.fixture(name="read")
def read_fixture():
    return lambda name, rec: harness.load_metric(name).read(rec)


def test_flush_readers_by_hand(read):
    rec = _record(spans=SPANS, programs_s={"jit_rows_purge_merge": 0.012},
                  program_calls={"jit_rows_purge_merge": 2})
    assert read("flush_rounds", rec) == 18.0
    assert read("flush_readbacks", rec) == 80.0
    assert read("flush_wait_ms", rec) == pytest.approx(2000.0)
    assert read("frontier_ms", rec) == pytest.approx(3000.0)
    assert read("repair_ms", rec) == pytest.approx(500.0)
    assert read("purge_merge_ms", rec) == pytest.approx(6.0)
    assert read("idle_pct.fleet", rec) == pytest.approx(60.0)


def test_frontier_round_bytes_by_hand():
    r, t, b = 3, 8, 5
    ids_weights_bounds = r * t * (4 + 4 + 4)
    neighbor_rows = r * t * b * 4
    own_rows_read_and_written = 2 * r * b * 4
    assert harness.load_metric("frontier_round_roofline").bytes_needed(r, t, b) \
        == ids_weights_bounds + neighbor_rows + own_rows_read_and_written == 888


def test_frontier_round_roofline_by_hand(read):
    f = harness.load_metric("frontier_round_roofline").bytes_needed
    rec = _record(attr_spans=PARTS, programs_s={"jit__frontier_round": 0.5},
                  program_calls={"jit__frontier_round": 3})
    need = f(1000, 8, 431) + f(30, 128, 431) + f(64, 32, 7)
    peak = harness.peak(KIND, "hbm_bytes_per_s")
    assert read("frontier_round_roofline", rec) == pytest.approx(100 * need / (0.5 * peak))
    # a program without part spans (the parent's) reads nothing, and no error
    assert read("frontier_round_roofline", _record(attr_spans=PARTS[:2] + PARTS[4:5],
                programs_s={"jit__frontier_round": 0.5})) is None


@pytest.mark.parametrize("name", READERS)
def test_every_reader_reads_nothing_untraced(name, read):
    assert read(name, _record(traced=False)) is None


def test_span_attributes_from_the_name_or_the_stats():
    assert spanattrs.parse("knn:flush#epoch=3,inserts=431#", {}) == (
        "knn:flush", {"epoch": 3, "inserts": 431})
    assert spanattrs.parse("knn:flush.frontier.part", {"rows": 12, "t": "8"}) == (
        "knn:flush.frontier.part", {"rows": 12, "t": 8})
    assert spanattrs.enclosing(PARTS, PARTS[3], "knn:flush") is PARTS[0]
    assert spanattrs.enclosing(PARTS, PARTS[5], "knn:flush") is PARTS[4]


def test_window_attr_spans_reads_the_trace_file_of_the_run(tmp_path, monkeypatch):
    import jax

    import spantrace
    from repro.core.spans import span

    jax.profiler.start_trace(str(tmp_path))
    with span("flush", inserts=9):
        pass
    with jax.profiler.TraceAnnotation("bench:window"):
        with span("flush", epoch=1, inserts=4):
            with span("flush.frontier.part", rows=3, t=8):
                pass
    jax.profiler.stop_trace()
    monkeypatch.setattr(spantrace, "TRACE_DIR", tmp_path)
    spans = spanattrs.events(next(tmp_path.rglob("*.xplane.pb")))
    w0, w1 = spantrace.window_of(spans)
    rec = _record(traced=False)
    rec.trace = types.SimpleNamespace(window_s=(w1 - w0) * 1e-9)
    got = spanattrs.window_attr_spans(rec)
    assert [(s.name, s.attrs) for s in got] == [
        ("knn:flush", {"epoch": 1, "inserts": 4}),
        ("knn:flush.frontier.part", {"rows": 3, "t": 8})]
    rec.trace = types.SimpleNamespace(window_s=(w1 - w0) * 1e-9 + 1e-3)
    assert spanattrs.window_attr_spans(rec) is None
