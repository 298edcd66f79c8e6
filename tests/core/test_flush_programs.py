"""The flush's program set is closed: an engine enumerates every program
signature a flush can dispatch, its first flush compiles them all, and no
later flush compiles anything, whatever the size of its delta.

A moving fleet on a 40 x 40 road network, ticks of 1 up to every vehicle
moving, each published epoch held to a rebuild from the positions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import knn
from repro.analysis import sanitize
from repro.core import engine as engine_mod
from repro.core.reference import knn_index_cons_plus
from repro.graph.generators import road_network

K = 6


@pytest.fixture(scope="module")
def city():
    g = road_network(40, 40, seed=1)
    return g, knn.build_bngraph(g)


@pytest.fixture(name="sanitize_off")
def sanitize_off_fixture(monkeypatch):
    # the sanitizer leg's post-flush table scan compiles a readback of its
    # own; the counts here are the flush's
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)


def _engine(city, objects):
    g, bn = city
    objects = np.sort(np.asarray(objects, np.int32))
    return knn.QueryEngine.from_index(knn_index_cons_plus(bn, objects, K), objects, bn=bn)


def _matches_rebuild(bn, eng, objects) -> bool:
    fresh = knn_index_cons_plus(bn, np.array(sorted(objects), np.int32), K)
    got = eng.to_index()
    return knn.indices_equivalent(fresh, got) and np.array_equal(fresh.dists, got.dists)


def test_no_flush_compiles_after_the_first(city, sanitize_off):
    g, bn = city
    rng = np.random.default_rng(15)
    fleet = rng.choice(g.n, g.n // 20, replace=False)
    eng = _engine(city, fleet)
    pos = set(fleet.tolist())
    # 1 .. every vehicle, then the rest in a seeded order
    counts = [1, len(pos)] + rng.integers(1, len(pos) + 1, size=28).tolist()
    n_sigs = None
    for tick, m in enumerate(counts):
        here = sorted(pos)
        free = np.setdiff1d(np.arange(g.n), here)
        for u, v in zip(rng.choice(here, m, replace=False), rng.choice(free, m, replace=False)):
            eng.stage_move(int(u), int(v))
            pos.discard(int(u))
            pos.add(int(v))
        with sanitize.count_compiles() as c:
            eng.flush_updates()
        if tick == 0:
            n_sigs = len(eng._flush_signatures())
            assert eng.stats()["spans"]["knn:flush.warm"]["n"] == 1
        else:
            assert c.count == 0, f"tick {tick}: a flush of {m} moves compiled {c.count}"
        assert eng.stats()["flush_programs"] == n_sigs
        assert eng.epoch == tick + 1
        assert _matches_rebuild(bn, eng, pos), f"epoch {eng.epoch}"
    assert eng.stats()["spans"]["knn:flush.warm"]["n"] == 1


def test_a_delta_past_the_cap_is_applied_in_parts_and_published_once(city, sanitize_off):
    g, bn = city
    rng = np.random.default_rng(16)
    objects = rng.choice(g.n, 700, replace=False)
    eng = _engine(city, objects)
    cap = eng._delta_cap()
    assert cap == 512
    obj = set(objects.tolist())
    # a first, small flush warms the engine
    u, v = int(objects[0]), int(np.setdiff1d(np.arange(g.n), objects)[0])
    eng.stage_move(u, v)
    obj = (obj - {u}) | {v}
    eng.flush_updates()
    n_sigs = eng.stats()["flush_programs"]
    dels = rng.choice(sorted(obj), cap + 8, replace=False)
    ins = rng.choice(np.setdiff1d(np.arange(g.n), sorted(obj)), cap + 8, replace=False)
    for x in dels:
        eng.stage_delete(int(x))
    for x in ins:
        eng.stage_insert(int(x))
    with sanitize.count_compiles() as c:
        res = eng.flush_updates()
    assert c.count == 0
    assert eng.epoch == 2 and eng.retained_epochs() == [1, 2]
    assert res["inserts"] == res["deletes"] == cap + 8
    assert eng.stats()["flush_programs"] == n_sigs
    assert _matches_rebuild(bn, eng, (obj - set(dels.tolist())) | set(ins.tolist()))


def test_a_query_only_engine_compiles_no_flush_program():
    g = road_network(9, 11, seed=2)
    bn = knn.build_bngraph(g)
    objects = np.arange(0, g.n, 7, dtype=np.int32)
    eng = knn.QueryEngine.from_index(knn_index_cons_plus(bn, objects, 3), objects, bn=bn)
    for _ in range(3):
        eng.query_batch(np.arange(32, dtype=np.int32), 2)
    assert eng.stats()["flush_programs"] == 0
    assert "knn:flush.warm" not in eng.stats()["spans"]


def test_the_delta_cap_follows_the_device_memory(city, monkeypatch):
    """B's (n+1, B) float32 state stays within its share of the device's
    memory; the small source width is an eighth of the cap."""
    g, bn = city
    eng = _engine(city, np.arange(0, g.n, 20))
    column = engine_mod._STATE_SHARE * 4 * (g.n + 1)
    for columns, cap in ((100, 64), (1000, 512), (1 << 20, 512), (3, 8)):
        monkeypatch.setattr(engine_mod, "_device_bytes", lambda c=columns: c * column)
        assert eng._delta_cap() == cap
        assert eng._src_widths() == sorted({max(cap // 8, 8), cap})
    eng.use_pallas = True
    assert eng._delta_cap() == 128 and eng._src_widths() == [128]


def test_signatures_follow_the_tier_helpers(city):
    """Each signature's shapes are widths the flush's own pad helpers give,
    and the rounds' rows stay within their width bucket's vertices."""
    g, bn = city
    eng = _engine(city, np.arange(0, g.n, 20))
    sigs = eng._flush_signatures()
    assert len(sigs) == len(set(sigs))
    rows = {eng._rows_width(r) for r in range(1, g.n + 1)}
    cands = {eng._cand_width(p) for p in range(1, eng._delta_cap() + 1)}
    deg = eng._nbr_deg[: g.n]
    prev = 0
    for t in eng._t_tiers():
        pop = int(((deg > prev) & (deg <= t)).sum())
        got = {s[2] for s in sigs if s[0] == "frontier_round" and s[1] == t}
        assert got == {eng._rows_width(r) for r in range(1, pop + 1)}
        prev = t
    srcs = {len(eng._frontier_pad_src(np.zeros(b, np.int32))) for b in range(1, 513)}
    assert srcs == set(eng._src_widths()) == {64, 512}
    assert {s[1:] for s in sigs if s[0] == "frontier_affected"} == {
        (r, b) for r in rows for b in srcs}
    assert {s[3] for s in sigs if s[0] == "frontier_round"} == srcs
    assert {(s[1], s[2]) for s in sigs if s[0] == "rows_purge_merge"} == {
        (r, p) for r in rows for p in cands}
    assert {s[1] for s in sigs if s[0] == "frontier_init"} == srcs
    assert {s[1] for s in sigs if s[0] == "rows_containing"} == {512}
    assert len(eng._padded_deletes([1, 2])) == 512


def test_the_repair_round_merges_in_row_blocks_past_the_gather_limit(monkeypatch):
    """However few indices one gather may take, a repair round gives the
    same tables and changed mask, bit for bit, as its one-gather merge."""
    rng = np.random.default_rng(5)
    n1, k, t, r = 41, 4, 13, 30
    ids = rng.integers(-1, 30, size=(n1, k)).astype(np.int32)
    d = np.where(ids >= 0, rng.integers(1, 50, size=(n1, k)), np.inf).astype(np.float32)
    ids[-1], d[-1] = -1, np.inf
    nbr = rng.integers(0, n1 - 1, size=(n1, t)).astype(np.int32)
    nbr[rng.random((n1, t)) < 0.3] = -1
    nbr[-1] = -1
    w = np.where(nbr >= 0, rng.integers(1, 9, size=(n1, t)), np.inf).astype(np.float32)
    rows = np.sort(rng.choice(n1 - 1, r - 3, replace=False)).astype(np.int32)
    rows = np.concatenate([rows, [n1 - 1] * 3]).astype(np.int32)
    args = [jnp.asarray(x) for x in (nbr, w, rows, ids, d)]

    want = [np.asarray(x) for x in engine_mod._repair_round.__wrapped__(*args)]
    assert want[2].any()
    for block in (8, 16, 24):   # 4, 2 and 2 blocks, the last overlapping
        monkeypatch.setattr(engine_mod, "_GATHER_INDICES", block * t)
        got = engine_mod._repair_round.__wrapped__(*args)
        assert all(np.array_equal(np.asarray(a), b) for a, b in zip(got, want))


def test_the_sweep_merge_gathers_at_once_past_the_repair_limit():
    """The construction sweeps' XLA merge is not blocked, whatever its
    chunk's neighbor count (64 x 1024 here, twice the repair round's
    limit): its program is the one-gather merge."""
    from repro.kernels import ops
    from repro.kernels.topk_merge import kround_merge

    chunk, t, k, n1 = 64, 1024, 10, 5000
    assert chunk * t > engine_mod._GATHER_INDICES
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    shapes = (i32(chunk, t), i32(chunk), f32(chunk, t), i32(n1, k), f32(n1, k),
              i32(n1, k), f32(n1, k))

    def one_gather(nbr, verts, w, ex_ids, ex_d, vk_ids, vk_d):
        valid = nbr >= 0
        nbr_c = jnp.where(valid, nbr, n1 - 1)
        g_ids = jnp.where(valid[..., None], vk_ids[nbr_c], -1).reshape(chunk, t * k)
        g_d = (w[..., None] + vk_d[nbr_c]).reshape(chunk, t * k)
        e_ids = ex_ids[verts]
        return kround_merge(
            [(g_ids, jnp.where(g_ids < 0, jnp.inf, g_d)),
             (e_ids, jnp.where(e_ids < 0, jnp.inf, ex_d[verts].astype(jnp.float32)))], k)

    def sweep(*a):
        return ops.sweep_merge_rows(*a, k, use_pallas=False)

    got = str(jax.make_jaxpr(sweep)(*shapes))
    assert "while" not in got
    assert got == str(jax.make_jaxpr(one_gather)(*shapes))


@pytest.mark.parametrize("width", [5, 20, 80, 512, 714])
def test_every_part_of_any_round_is_an_enumerated_signature(city, width):
    """Whatever rows a round holds, each part ``_bucket_parts`` makes of them
    is dispatched at a width and a row count ``_flush_signatures`` lists,
    whatever the packed BNS width (degrees past 512 take the widest)."""
    g, bn = city
    eng = _engine(city, np.arange(0, g.n, 20))
    eng._nbr_tables()
    eng._nbr_ids = np.zeros((g.n + 1, width), np.int32)
    rng = np.random.default_rng(width)
    eng._nbr_deg = rng.integers(1, width + 1, size=g.n + 1)
    sigs = set(eng._flush_signatures())
    assert {t for _, t, _ in (s for s in sigs if s[0] == "repair_round")} == {
        t for t, _ in eng._t_widths()}
    for size in (1, 2, 5, 40, 300, g.n):
        for _ in range(20):
            rows = np.sort(rng.choice(g.n, size, replace=False)).astype(np.int32)
            for part in eng._bucket_parts(rows):
                for i in (1, part.size):
                    sig = (eng._t_bucket(part[:i]), eng._rows_width(i))
                    assert ("repair_round", *sig) in sigs
                    assert all(("frontier_round", *sig, b) in sigs
                               for b in eng._src_widths())
