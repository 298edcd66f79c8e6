"""ShardedQueryEngine: vertex-sharded multi-device serving vs the scalar engine.

The sharded engine's contract is *exact* equivalence, not just tie-tolerant
``indices_equivalent``: per-shard routing returns bit-identical query results,
and every flush lands on bit-identical tables (the per-row candidate multisets
and the merge are the same math, only partitioned). These tests run at every
shard count the visible device pool allows — under plain tier-1 CI that is a
single shard; the multi-device CI job forces 8 host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) so shard counts
{1, 2, 4, 8} all execute, and that job fails if this module is skipped.
"""
import os

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import knn
from repro.core.reference import knn_index_cons_plus
from repro.core.sharded import ShardedQueryEngine, make_mesh, shard_tables
from repro.graph.generators import pick_objects, random_connected_graph, road_network

DEVICES = len(jax.devices())
SHARD_COUNTS = [s for s in (1, 2, 4, 8) if s <= DEVICES]


def _setup(grid=12, mu=0.15, k=6, seed=0, shards=1):
    g = road_network(grid, grid, seed=seed)
    objects = pick_objects(g.n, mu, seed=seed)
    bn = knn.build_bngraph(g)
    idx = knn_index_cons_plus(bn, objects, k)
    plain = knn.QueryEngine.from_index(idx, objects, bn=bn)
    sharded = ShardedQueryEngine.from_index(idx, objects, bn=bn, shards=shards)
    return g, objects, bn, plain, sharded


def _tables_equal(a, b) -> bool:
    ia, ib = a.to_index(), b.to_index()
    return np.array_equal(ia.ids, ib.ids) and np.array_equal(ia.dists, ib.dists)


@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_query_routing_bit_identical(shards):
    """Random batches spanning shard boundaries: same ids AND same dists."""
    g, objects, bn, plain, sharded = _setup(shards=shards)
    rng = np.random.default_rng(1)
    r = sharded.shard_rows
    # boundary-heavy traffic: first/last rows of every shard + uniform fill
    # + out-of-range ids, which must get the scalar gather's jnp semantics
    # (negatives wrap once from the table end, so -1 reads the dummy row ->
    # pad sentinel and -3 reads row n-2; ids >= n clamp to the dummy row)
    edges = np.concatenate(
        [np.arange(0, g.n, r), np.arange(r - 1, g.n, r), rng.integers(0, g.n, 128),
         [-3, -1, g.n, g.n + 7]]
    ).astype(np.int32)
    for us in (edges, rng.integers(0, g.n, size=257).astype(np.int32)):
        pi, pd = plain.query_batch(us)
        si, sd = sharded.query_batch(us)
        assert np.array_equal(np.asarray(pi), np.asarray(si))
        assert np.array_equal(np.asarray(pd), np.asarray(sd))
        ks = rng.integers(1, plain.k + 1, size=len(us)).astype(np.int32)
        pi, pd = plain.query_batch(us, ks)
        si, sd = sharded.query_batch(us, ks)
        assert np.array_equal(np.asarray(pi), np.asarray(si))
        assert np.array_equal(np.asarray(pd), np.asarray(sd))


@settings(max_examples=10, deadline=None)
@given(st.tuples(
    st.integers(min_value=8, max_value=36),
    st.integers(min_value=0, max_value=30),
    st.integers(min_value=0, max_value=10_000),
    st.integers(min_value=1, max_value=4),
))
def test_query_routing_property(p):
    """Property: on arbitrary topologies, routed sharded queries are
    bit-identical to the plain gather for random batches."""
    n, extra, seed, k = p
    rng = np.random.default_rng(seed)
    g = random_connected_graph(n, extra_edges=extra, seed=seed)
    objects = pick_objects(n, 0.5, seed=seed)
    if len(objects) <= k:
        objects = np.arange(min(n, k + 2), dtype=np.int32)
    bn = knn.build_bngraph(g)
    idx = knn_index_cons_plus(bn, objects, k)
    shards = SHARD_COUNTS[min(int(rng.integers(0, len(SHARD_COUNTS))),
                              len(SHARD_COUNTS) - 1)]
    if shards > n:
        shards = 1
    plain = knn.QueryEngine.from_index(idx, objects, bn=bn)
    sharded = ShardedQueryEngine.from_index(idx, objects, bn=bn, shards=shards)
    us = rng.integers(0, n, size=64).astype(np.int32)
    pi, pd = plain.query_batch(us)
    si, sd = sharded.query_batch(us)
    assert np.array_equal(np.asarray(pi), np.asarray(si))
    assert np.array_equal(np.asarray(pd), np.asarray(sd))


@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_flush_exact_equivalence(shards):
    """Mixed staged updates (inserts/deletes/moves) flushed at several
    points: the sharded tables equal the scalar tables exactly after EVERY
    flush, and the final state matches a fresh rebuild."""
    g, objects, bn, plain, sharded = _setup(mu=0.2, shards=shards)
    k = plain.k
    rng = np.random.default_rng(7)
    mset = set(objects.tolist())
    for step in range(36):
        u = int(rng.integers(0, g.n))
        outside = sorted(set(range(g.n)) - mset)
        r = rng.random()
        if r < 0.3 and outside and len(mset) > k + 1:
            src = int(rng.choice(sorted(mset)))
            dst = int(rng.choice(outside))
            plain.stage_move(src, dst)
            sharded.stage_move(src, dst)
            mset.discard(src)
            mset.add(dst)
        elif u in mset and len(mset) > k + 1:
            plain.stage_delete(u)
            sharded.stage_delete(u)
            mset.discard(u)
        elif u not in mset:
            plain.stage_insert(u)
            sharded.stage_insert(u)
            mset.add(u)
        if step % 8 == 7:
            sp, ss = plain.flush_updates(), sharded.flush_updates()
            assert sp == ss
            assert _tables_equal(plain, sharded)
    plain.flush_updates()
    sharded.flush_updates()
    assert _tables_equal(plain, sharded)
    fresh = knn_index_cons_plus(bn, np.array(sorted(mset)), k)
    assert knn.indices_equivalent(fresh, sharded.to_index())
    assert set(sharded.objects.tolist()) == mset


@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_sharded_device_frontier_matches_host_oracle(shards):
    """The shard-local checkIns frontier (boundary-crossing sources pinned
    to the first/last vertices of shard ranges) returns exactly the host
    oracle's affected rows, candidate ids and distances — and bit-identical
    output to the scalar engine's device frontier. Integer edge weights make
    every comparison exact, not tolerance-based."""
    g, objects, bn, plain, sharded = _setup(mu=0.2, shards=shards)
    rng = np.random.default_rng(11)
    outside = np.setdiff1d(np.arange(g.n), objects)
    r = sharded.shard_rows
    boundary = np.concatenate([np.arange(0, g.n, r), np.arange(r - 1, g.n, r)])
    srcs = [int(v) for v in boundary if v in set(outside.tolist())][:4]
    fill = [int(v) for v in rng.permutation(outside) if v not in srcs]
    srcs = sorted(srcs + fill[: max(0, 6 - len(srcs))])

    rows_p, ci_p, cd_p, rounds_p = plain._insert_frontier(srcs)
    rows_s, ci_s, cd_s, rounds_s = sharded._insert_frontier(srcs)
    assert rounds_p == rounds_s
    np.testing.assert_array_equal(rows_p, rows_s)
    np.testing.assert_array_equal(ci_p, ci_s)
    np.testing.assert_array_equal(cd_p, cd_s)

    from repro.core.updates import insert_affected_set

    kth = np.asarray(plain.tables[1][: g.n, -1], np.float64)
    per_row = {}
    for u in srcs:
        for v, d in insert_affected_set(bn, lambda x: float(kth[x]), u).items():
            per_row.setdefault(v, []).append((u, d))
    assert rows_s.tolist() == sorted(per_row)
    for i, v in enumerate(rows_s.tolist()):
        got = [(int(c), float(d)) for c, d in zip(ci_s[i], cd_s[i]) if c >= 0]
        assert got == per_row[v]


def test_reshard_on_load_roundtrip(tmp_path):
    """Save at 2 shards, load at 4 and at 1: all equivalent to the unsharded
    build, and the resharded engines keep serving and updating."""
    g = road_network(11, 13, seed=3)  # n not divisible by any shard count
    objects = pick_objects(g.n, 0.2, seed=3)
    bn = knn.build_bngraph(g)
    k = 5
    unsharded = knn.QueryEngine.build(bn, objects, k)
    writer = ShardedQueryEngine.build(bn, objects, k, shards=min(2, DEVICES))
    assert _tables_equal(unsharded, writer)
    path = os.path.join(tmp_path, "sharded.npz")
    writer.save(path)
    for shards in (min(4, DEVICES), 1):
        loaded = knn.load_engine(path, bn=bn, shards=shards)
        assert isinstance(loaded, ShardedQueryEngine)
        assert loaded.num_shards == shards
        assert knn.indices_equivalent(unsharded.to_index(), loaded.to_index())
        assert _tables_equal(unsharded, loaded)
        assert np.array_equal(loaded.objects, writer.objects)
        # the resharded engine still updates correctly
        outside = int(np.setdiff1d(np.arange(g.n), loaded.objects)[0])
        loaded.stage_insert(outside)
        loaded.flush_updates()
        fresh = knn_index_cons_plus(
            bn, np.array(sorted(set(loaded.objects.tolist()))), k
        )
        assert knn.indices_equivalent(fresh, loaded.to_index())
    # a scalar engine reads the same artifact (shard meta is provenance only)
    scalar = knn.load_engine(path, bn=bn)
    assert isinstance(scalar, knn.QueryEngine)
    assert _tables_equal(unsharded, scalar)


def test_sharded_fleet_workload():
    """The moving-fleet loop drives the sharded engine unchanged and lands on
    the same tables as the scalar engine on an identical movement trace."""
    from repro.workloads import drive_fleet_ticks

    g = road_network(10, 10, seed=4)
    bn = knn.build_bngraph(g)
    k = 4
    sim = knn.FleetSim(g, fleet_size=24, seed=4)
    init = sim.positions.copy()
    trace = [sim.tick() for _ in range(5)]
    plain = knn.QueryEngine.build(bn, init, k)
    sharded = ShardedQueryEngine.build(bn, init, k, shards=SHARD_COUNTS[-1])
    r_p = drive_fleet_ticks(plain, trace, batch=32, rng=np.random.default_rng(0))
    r_s = drive_fleet_ticks(sharded, trace, batch=32, rng=np.random.default_rng(0))
    assert r_p["moves"] == r_s["moves"] and r_p["ticks"] == r_s["ticks"]
    assert _tables_equal(plain, sharded)
    fresh = knn_index_cons_plus(bn, sim.positions, k)
    assert knn.indices_equivalent(fresh, sharded.to_index())


def test_build_sharded_engine_facade():
    g = road_network(8, 8, seed=5)
    objects = pick_objects(g.n, 0.2, seed=5)
    engine = knn.build_sharded_engine(g, objects, 4, shards=SHARD_COUNTS[-1])
    assert isinstance(engine, ShardedQueryEngine)
    fresh = knn_index_cons_plus(knn.build_bngraph(g), objects, 4)
    assert knn.indices_equivalent(fresh, engine.to_index())


def test_stats_report_shard_meta_and_padding():
    g, objects, bn, plain, sharded = _setup(shards=SHARD_COUNTS[-1])
    s = sharded.stats()
    assert s["num_shards"] == SHARD_COUNTS[-1]
    r = sharded.shard_rows
    padded = s["num_shards"] * (r + 1)
    assert s["padded_rows"] == padded
    assert s["row_padding_overhead"] == round((padded - g.n) / g.n, 4)


def test_save_refuses_pending_queue(tmp_path):
    g, objects, bn, plain, sharded = _setup(shards=1)
    sharded.stage_insert(int(np.setdiff1d(np.arange(g.n), objects)[0]))
    with pytest.raises(RuntimeError):
        sharded.save(os.path.join(tmp_path, "sharded.npz"))


def test_query_k_too_large_raises():
    _, _, _, _, sharded = _setup(shards=1)
    with pytest.raises(ValueError):
        sharded.query_batch(np.array([0, 1]), sharded.k + 1)


def test_make_mesh_validates_device_count():
    with pytest.raises(ValueError):
        make_mesh(DEVICES + 1)


def test_shard_tables_layout():
    """The sharded layout puts vertex v at row (v//R)*(R+1) + v%R with pad
    sentinels on dummy and overhang rows."""
    import jax.numpy as jnp

    n, k = 10, 3
    ids = jnp.arange((n + 1) * k, dtype=jnp.int32).reshape(n + 1, k)
    ids = ids.at[n].set(-1)
    d = ids.astype(jnp.float32)
    d = d.at[n].set(jnp.inf)
    mesh = make_mesh(min(4, DEVICES))
    s = mesh.devices.size
    r = -(-n // s)
    gi, gd = shard_tables(ids, d, n, mesh)
    assert gi.shape == (s * (r + 1), k)
    host = np.asarray(gi)
    for v in range(n):
        g_row = (v // r) * (r + 1) + v % r
        assert np.array_equal(host[g_row], np.asarray(ids[v]))
    covered = {(v // r) * (r + 1) + v % r for v in range(n)}
    for row in set(range(s * (r + 1))) - covered:
        assert (host[row] == -1).all()


_CONSOLIDATE = """
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import knn
from repro.graph.generators import pick_objects, road_network

g = road_network(12, 12, seed=0)
objects = pick_objects(g.n, 0.15, seed=0)
bn = knn.build_bngraph(g)
plain = knn.QueryEngine.build(bn, objects, 6)
sharded = knn.build_sharded_engine(bn, objects, 6, shards=4)

def no_dlpack(*a, **kw):
    raise AssertionError("np.from_dlpack takes CPU buffers only")
np.from_dlpack = no_dlpack

x = np.arange(4 * 8 * 6, dtype=np.float32).reshape(4, 8, 6)
tile = jax.device_put(x, NamedSharding(sharded.mesh, P("shard", None, None)))
assert len({sh.device for sh in tile.addressable_shards}) == 4
for _ in range(3):  # both pooled staging buffers, then the first again
    assert np.array_equal(sharded._consolidate(tile), x)

us = np.random.default_rng(1).integers(0, g.n, size=4096).astype(np.int32)
for a, b in zip(plain.query_batch(us), sharded.query_batch(us)):
    assert np.array_equal(np.asarray(a), np.asarray(b))
assert sharded.stats()["balanced_batches"] == 1
print("CONSOLIDATE_OK")
"""


def test_consolidate_reads_shards_without_dlpack(devices_subprocess):
    """The balanced gather's host consolidation reads each shard without
    ``np.from_dlpack`` (CPU buffers only, so it fails on a TPU) and gives the
    same tile and the same answers as the scalar engine."""
    assert "CONSOLIDATE_OK" in devices_subprocess(_CONSOLIDATE, n_devices=4)
