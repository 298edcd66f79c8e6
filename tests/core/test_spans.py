"""Host spans and transfer counters inside the engine and the build.

Every blocking readback of a flush goes through ``EngineCore._readback``
(pinned against ``sanitize.count_transfers``, which sees every
``np.asarray`` of a device array), the span totals are what ``stats()``
reports as the phase times, and the program names the benchmark's trace
readers key on stay as they are.
"""
import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_devices_subprocess
from repro import knn
from repro.analysis import sanitize
from repro.core import construct_jax, engine as engine_mod
from repro.core.reference import knn_index_cons_plus
from repro.core.sharded import ShardedQueryEngine
from repro.core.spans import span
from repro.graph.generators import pick_objects, road_network
from repro.kernels import ops


def _setup(grid=12, mu=0.15, k=6, seed=0):
    g = road_network(grid, grid, seed=seed)
    objects = pick_objects(g.n, mu, seed=seed)
    bn = knn.build_bngraph(g)
    idx = knn_index_cons_plus(bn, objects, k)
    return g, objects, bn, idx


def _stage_churn(eng, g, rng, n_del=6, n_ins=10):
    """Deletes, inserts and moves: every phase of the flush runs."""
    objs = np.asarray(eng.objects)
    free = np.setdiff1d(np.arange(g.n), objs)
    picks = rng.choice(objs, n_del + 2, replace=False)
    dests = rng.choice(free, n_ins + 2, replace=False)
    for u in picks[:n_del]:
        eng.stage_delete(int(u))
    for v in dests[:n_ins]:
        eng.stage_insert(int(v))
    for u, v in zip(picks[n_del:], dests[n_ins:]):
        eng.stage_move(int(u), int(v))


@pytest.fixture(name="sanitize_off")
def sanitize_off_fixture(monkeypatch):
    # the sanitizer leg's post-flush table scan reads the tables back
    # outside the flush path; the counts here are the flush's own
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)


@pytest.mark.parametrize("kind", ["scalar", "sharded-1"])
def test_every_flush_readback_is_counted(kind, sanitize_off):
    g, objects, bn, idx = _setup()
    if kind == "scalar":
        eng = knn.QueryEngine.from_index(idx, objects, bn=bn)
    else:
        eng = ShardedQueryEngine.from_index(idx, objects, bn=bn, shards=1)
    rng = np.random.default_rng(3)
    for _ in range(2):
        _stage_churn(eng, g, rng)
        before = eng.stats()
        with sanitize.count_transfers() as t:
            res = eng.flush_updates()
        assert res["rows_purged"] and res["rows_merged"] and res["repair_rounds"]
        st = eng.epoch_stats(eng.epoch)
        assert st["readbacks"] == t.d2h > 0
        assert st["uploads"] == t.h2d > 0
        assert st["readback_bytes"] > 0 and st["upload_bytes"] > 0
        after = eng.stats()
        for key in ("readbacks", "readback_bytes", "uploads", "upload_bytes"):
            assert after["flush_" + key] - before["flush_" + key] == st[key]
        # one knn:flush.readback span per readback
        spans = after["spans"]
        assert spans["knn:flush.readback"]["n"] == after["flush_readbacks"]


def test_queries_count_no_flush_transfers(sanitize_off):
    g, objects, bn, idx = _setup()
    eng = ShardedQueryEngine.from_index(idx, objects, bn=bn, shards=1)
    eng.query_batch(np.arange(16, dtype=np.int32), 3)
    st = eng.stats()
    assert st["flush_uploads"] == st["flush_readbacks"] == 0
    assert st["spans"]["knn:query"]["n"] == 1


def test_span_totals_are_the_phase_times(sanitize_off):
    g, objects, bn, idx = _setup()
    eng = knn.QueryEngine.from_index(idx, objects, bn=bn)
    rng = np.random.default_rng(4)
    walls = []
    for _ in range(3):
        _stage_churn(eng, g, rng)
        eng.flush_updates()
        walls.append(eng.epoch_stats(eng.epoch)["t_wall_s"])
    st = eng.stats()
    spans = st["spans"]
    assert "t_purge_merge_s" not in st
    assert st["t_frontier_s"] == spans["knn:flush.frontier"]["s"] > 0
    assert st["t_repair_s"] == spans["knn:flush.repair"]["s"] > 0
    assert spans["knn:flush"]["n"] == 3
    assert spans["knn:flush.frontier.round"]["n"] >= 3
    assert spans["knn:flush.repair.round"]["n"] >= 3
    # the epochs' wall times are the knn:flush spans (the last two retained)
    assert spans["knn:flush"]["s"] == pytest.approx(sum(walls))
    for inner in ("knn:flush.frontier", "knn:flush.repair", "knn:flush.readback"):
        assert spans[inner]["s"] <= spans["knn:flush"]["s"]


def test_a_failed_flush_still_counts_its_spans(sanitize_off):
    g, objects, bn, idx = _setup()
    eng = knn.QueryEngine.from_index(idx, objects, bn=bn)
    _stage_churn(eng, g, np.random.default_rng(5))

    def kill(_, phase):
        if phase == "pre-swap":
            raise RuntimeError("killed")

    eng.checkpoint_hook = kill
    with pytest.raises(RuntimeError):
        eng.flush_updates()
    st = eng.stats()
    assert st["flushes_failed"] == 1 and st["epoch"] == 0
    assert st["spans"]["knn:flush"]["n"] == 1 and st["flush_readbacks"] > 0
    eng.checkpoint_hook = None
    eng.flush_updates()
    assert eng.stats()["spans"]["knn:flush"]["n"] == 2


def test_span_adds_to_totals_and_nests():
    totals = {}
    with span("a", totals, x=1) as outer:
        with span("a.b", totals):
            pass
        with span("a.b", totals):
            pass
    assert outer.name == "knn:a" and outer.s > 0
    assert totals["knn:a"]["n"] == 1 and totals["knn:a.b"]["n"] == 2
    assert totals["knn:a.b"]["s"] <= totals["knn:a"]["s"]
    with span("free"):  # no totals: the annotation alone
        pass
    assert "knn:free" not in totals


def _module_name(jitted, *args, **kw) -> str:
    text = jitted.lower(*args, **kw).compile().as_text()
    return text.split("HloModule ", 1)[1].split(",", 1)[0].split(" ", 1)[0]


def test_program_names_the_trace_readers_key_on():
    """The device trace names a program after its jitted function; the
    benchmark's readers find ``jit_serve_gather`` (serve_gather_roofline),
    ``jit__sweep_program`` (sweep_program_roofline), ``jit_rows_purge_merge``
    (purge_merge_ms) and the flush rounds by these names."""
    n1, k, b, r, t = 33, 4, 8, 16, 8
    ids = jnp.zeros((n1, k), jnp.int32)
    d = jnp.zeros((n1, k), jnp.float32)
    q = jnp.zeros((b,), jnp.int32)
    rows = jnp.zeros((r,), jnp.int32)
    nbr = jnp.zeros((n1, t), jnp.int32)
    w = jnp.zeros((n1, t), jnp.float32)
    dist = jnp.zeros((n1, b), jnp.float32)
    assert _module_name(ops.answer_program(ops.serve_gather), ids, d, q, q) == "jit_serve_gather"
    assert _module_name(ops.rows_purge_merge, ids, d, rows, q, jnp.zeros((r, 4), jnp.int32),
                        jnp.zeros((r, 4), jnp.float32), k) == "jit_rows_purge_merge"
    assert _module_name(engine_mod._frontier_round, nbr, w, rows, dist, d, q,
                        False) == "jit__frontier_round"
    assert _module_name(engine_mod._repair_round, nbr, w, rows, ids, d) == "jit__repair_round"
    g, objects, bn, _ = _setup(grid=6, k=k)
    plan = construct_jax.prepare_sweep(bn, "up")
    ex_ids, ex_d = construct_jax.object_extras(bn.n, objects, k)
    bucket_data = tuple((bk.verts, bk.nbr, bk.w) for bk in plan.buckets)
    assert _module_name(
        construct_jax._sweep_program_jit, bucket_data, plan.chunk_bucket, plan.chunk_off,
        ex_ids, ex_d, n=plan.n, k=k, chunks=tuple(bk.chunk for bk in plan.buckets),
        use_pallas=False, interpret=None,
    ) == "jit__sweep_program"


_SHARDED = textwrap.dedent("""
    import json
    import numpy as np
    from repro import knn
    from repro.analysis import sanitize
    from repro.core.reference import knn_index_cons_plus
    from repro.core.sharded import ShardedQueryEngine
    from repro.graph.generators import pick_objects, road_network

    g = road_network(12, 12, seed=0)
    objects = pick_objects(g.n, 0.15, seed=0)
    bn = knn.build_bngraph(g)
    idx = knn_index_cons_plus(bn, objects, 6)
    out = []
    for shards, halo, cap in ((2, "collective", None), (4, "collective", None),
                              (4, "collective", 1), (2, "host", None)):
        eng = ShardedQueryEngine.from_index(idx, objects, bn=bn, shards=shards)
        eng.halo = halo
        eng.halo_capacity = cap
        rng = np.random.default_rng(shards)
        for _ in range(2):
            objs = np.asarray(eng.objects)
            free = np.setdiff1d(np.arange(g.n), objs)
            picks = rng.choice(objs, 8, replace=False)
            dests = rng.choice(free, 12, replace=False)
            for u in picks[:6]:
                eng.stage_delete(int(u))
            for v in dests[:10]:
                eng.stage_insert(int(v))
            for u, v in zip(picks[6:], dests[10:]):
                eng.stage_move(int(u), int(v))
            with sanitize.count_transfers() as t:
                eng.flush_updates()
            st = eng.epoch_stats(eng.epoch)
            out.append([shards, halo, cap, st["readbacks"], t.d2h,
                        eng.stats()["halo_fallbacks"]])
    print(json.dumps(out))
""")


def test_every_sharded_flush_readback_is_counted():
    """Both halos, the fused collective round, its overflow fallback and
    the deferred mask thunks: every readback goes through the helper."""
    out = run_devices_subprocess(_SHARDED, n_devices=4)
    rows = json.loads(out.strip().splitlines()[-1])
    assert len(rows) == 8
    for shards, halo, cap, counted, d2h, fallbacks in rows:
        assert counted == d2h > 0, (shards, halo, cap)
        assert (fallbacks > 0) == (cap is not None)


def test_spans_land_in_a_profiler_trace(tmp_path, sanitize_off):
    """A trace of one build, one query batch and one flush holds the
    ``knn:`` spans nested by call, with their attributes."""
    from jax.profiler import ProfileData

    g, objects, bn, idx = _setup()
    eng = knn.QueryEngine.from_index(idx, objects, bn=bn)
    _stage_churn(eng, g, np.random.default_rng(6))
    jax.profiler.start_trace(str(tmp_path))
    try:
        construct_jax.build_knn_tables_jax(bn, objects, 6, use_pallas=False)
        eng.query_batch(np.arange(8, dtype=np.int32), 3)
        eng.flush_updates()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    evs = [(ev.name.split("#", 1)[0], ev.start_ns, ev.start_ns + ev.duration_ns,
            dict(ev.stats))
           for plane in ProfileData.from_file(str(path)).planes
           for line in plane.lines for ev in line.events if ev.name.startswith("knn:")]
    by = {}
    for name, s, e, attrs in evs:
        by.setdefault(name, []).append((s, e, attrs))
    (fs, fe, fattrs), = by["knn:flush"]
    assert fattrs["epoch"] == 1 and fattrs["staged"] > 0
    for inner in ("knn:flush.frontier.round", "knn:flush.repair.round", "knn:flush.readback"):
        assert by[inner] and all(fs <= s and e <= fe for s, e, _ in by[inner])
    rounds = [a["round"] for _, _, a in by["knn:flush.repair.round"]]
    assert rounds == list(range(1, len(rounds) + 1))
    (qs, qe, qattrs), = by["knn:query"]
    assert qattrs == {"batch": 0, "epoch": 0, "b": 8}
    assert qe <= fs
    (bs, be, _), = by["knn:build"]
    sweeps = by["knn:build.sweep"]
    assert [a["direction"] for _, _, a in sweeps] == ["up", "down"]
    assert [a["cells"] for _, _, a in sweeps] == [
        construct_jax.prepare_sweep(bn, d).cells for d in ("up", "down")]
    assert all(bs <= s and e <= be for s, e, _ in sweeps + by["knn:build.extras"])


def test_the_query_readback_follows_the_query_span(tmp_path, sanitize_off):
    """A batch's one readback is ``knn:query.readback``: the sibling after
    ``knn:query`` (whose seconds hold no readback), with the packed
    answer's 8 * B * k bytes."""
    from jax.profiler import ProfileData

    g, objects, bn, idx = _setup()
    eng = knn.QueryEngine.from_index(idx, objects, bn=bn)
    us = np.arange(16, dtype=np.int32)
    eng.query_batch(us, 3)  # compiled outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.query_batch(us, 3)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    by = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("knn:query"):
                    by.setdefault(ev.name.split("#", 1)[0], []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
    (qs, qe, _), = by["knn:query"]
    (rs, re, rattrs), = by["knn:query.readback"]
    assert qe <= rs < re
    assert rattrs["bytes"] == 8 * len(us) * eng.k
    assert all(qs <= s and e <= qe for s, e, _ in by["knn:query.gather"])


def test_the_first_flush_warms_and_each_round_spans_its_parts(tmp_path, sanitize_off):
    """``knn:flush.warm`` runs in the first flush only, with the number of
    programs it compiled; every program dispatch of a round is a
    ``.part`` span inside it, with its unpadded rows and width bucket."""
    from jax.profiler import ProfileData

    g, objects, bn, idx = _setup(grid=11)
    eng = knn.QueryEngine.from_index(idx, objects, bn=bn)
    rng = np.random.default_rng(7)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            _stage_churn(eng, g, rng)
            eng.flush_updates()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    by = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("knn:"):
                    by.setdefault(ev.name.split("#", 1)[0], []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
    first, second = sorted(by["knn:flush"])
    (ws, we, wattrs), = by["knn:flush.warm"]
    assert first[0] <= ws and we <= first[1]
    assert wattrs["programs"] == len(eng._flush_signatures()) == eng.stats()["flush_programs"]
    tiers = eng._t_tiers()
    for kind in ("frontier", "repair"):
        rounds = by[f"knn:flush.{kind}.round"]
        parts = by[f"knn:flush.{kind}.part"]
        assert parts and {a["t"] for _, _, a in parts} <= set(tiers)
        assert all(a["rows"] >= 1 for _, _, a in parts)
        for s, e, a in rounds:
            inner = [p for p in parts if s <= p[0] and p[1] <= e]
            assert inner
            if kind == "repair":  # a repair round's parts split its rows
                assert sum(p[2]["rows"] for p in inner) == a["rows"]
        assert all(any(s <= p[0] and p[1] <= e for s, e, _ in rounds) for p in parts)
