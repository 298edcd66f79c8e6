"""Algorithm 2 / Algorithm 3 / JAX fused-sweep construction vs Dijkstra oracle."""
import re

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import construct_jax
from repro.core.bngraph import build_bngraph
from repro.core.construct_jax import (
    build_knn_index_jax,
    object_extras,
    prepare_sweep,
    run_sweep,
)
from repro.core.index import KNNIndex, indices_equivalent
from repro.core.reference import dijkstra_cons, knn_index_cons, knn_index_cons_plus
from repro.graph.csr import from_edges
from repro.graph.generators import pick_objects, random_connected_graph, road_network

params = st.tuples(
    st.integers(min_value=5, max_value=45),
    st.integers(min_value=0, max_value=60),
    st.integers(min_value=0, max_value=10_000),
    st.floats(min_value=0.2, max_value=1.0),
    st.integers(min_value=1, max_value=8),
)


@settings(max_examples=20, deadline=None)
@given(params)
def test_alg2_alg3_match_oracle(p):
    n, extra, seed, mu, k = p
    g = random_connected_graph(n, extra_edges=extra, seed=seed)
    objects = pick_objects(n, mu, seed=seed)
    bn = build_bngraph(g)
    oracle = dijkstra_cons(g, objects, k)
    assert indices_equivalent(oracle, knn_index_cons(bn, objects, k))
    assert indices_equivalent(oracle, knn_index_cons_plus(bn, objects, k))


@settings(max_examples=8, deadline=None)
@given(params)
def test_jax_construction_matches_reference(p):
    n, extra, seed, mu, k = p
    g = random_connected_graph(n, extra_edges=extra, seed=seed)
    objects = pick_objects(n, mu, seed=seed)
    bn = build_bngraph(g)
    ref = knn_index_cons_plus(bn, objects, k)
    jx = build_knn_index_jax(bn, objects, k, use_pallas=False)
    assert indices_equivalent(ref, jx)


def test_jax_construction_pallas_road():
    g = road_network(10, 10, seed=5)
    objects = pick_objects(g.n, 0.2, seed=5)
    bn = build_bngraph(g)
    ref = knn_index_cons_plus(bn, objects, 6)
    jx = build_knn_index_jax(bn, objects, 6, use_pallas=True)
    assert indices_equivalent(ref, jx)


def test_sweep_plan_layout_and_occupancy():
    g = road_network(12, 12, seed=1)
    bn = build_bngraph(g)
    for direction in ("up", "down"):
        plan = prepare_sweep(bn, direction)
        assert 0 < plan.occupancy <= 1
        assert 0 < plan.occupancy_levelwise <= 1
        assert sum(plan.level_sizes) == g.n
        # every chunk names a valid in-bucket row range
        cb = np.asarray(plan.chunk_bucket)
        co = np.asarray(plan.chunk_off)
        assert plan.num_chunks == cb.shape[0] == co.shape[0]
        for b, off in zip(cb.tolist(), co.tolist()):
            bucket = plan.buckets[b]
            assert off + bucket.chunk <= bucket.verts.shape[0]
        # padded rows carry the dummy vertex id n, real rows each vertex once
        all_verts = np.concatenate([np.asarray(b.verts) for b in plan.buckets])
        real = all_verts[all_verts < g.n]
        assert sorted(real.tolist()) == list(range(g.n))


def test_run_sweep_zero_host_transfers():
    """The schedule is uploaded once; the sweep itself must not touch host."""
    g = road_network(9, 9, seed=2)
    objects = pick_objects(g.n, 0.3, seed=2)
    bn = build_bngraph(g)
    k = 5
    plan_up = prepare_sweep(bn, "up")
    plan_down = prepare_sweep(bn, "down")
    ex_ids, ex_d = object_extras(g.n, objects, k)
    with jax.transfer_guard("disallow"):
        vkl_ids, vkl_d = run_sweep(plan_up, ex_ids, ex_d, k, use_pallas=False)
        vk_ids, vk_d = run_sweep(plan_down, vkl_ids, vkl_d, k, use_pallas=False)
        jax.block_until_ready((vk_ids, vk_d))
    ref = knn_index_cons_plus(bn, objects, k)
    ids = np.asarray(vk_ids[: g.n])
    dists = np.where(ids >= 0, np.asarray(vk_d[: g.n], np.float64), np.inf)
    assert indices_equivalent(ref, KNNIndex(ids=ids, dists=dists, k=k))


def _cul_de_sac_city():
    """A 12 x 12 road network with dead-end streets: 8 intersections get 20
    each, 8 get 8 and 8 get 2, so the bottom-up level those intersections
    share mixes three neighbor-width tiers."""
    g = road_network(12, 12, seed=1)
    u, v, w = g.edge_list()
    edges = list(zip(u.tolist(), v.tolist(), w.tolist()))
    n = g.n
    hubs = [x * 12 + y for x in range(0, 12, 2) for y in range(0, 12, 2)][:24]
    for i, h in enumerate(hubs):
        for _ in range((20, 8, 2)[i // 8]):
            edges.append((h, n, float(1 + n % 9)))
            n += 1
    return from_edges(n, edges)


def _chunks(plan):
    """(bucket, first row in it, the row ids) of each chunk, in sweep order."""
    for b, off in zip(np.asarray(plan.chunk_bucket).tolist(),
                      np.asarray(plan.chunk_off).tolist()):
        bucket = plan.buckets[b]
        yield bucket, off, np.asarray(bucket.verts)[off : off + bucket.chunk]


def _level_tiers(bn, plan):
    """Distinct chunk widths T of each level."""
    level_of = bn.sweep_tables(plan.direction)[0]
    tiers = {}
    for bucket, _, verts in _chunks(plan):
        tiers.setdefault(int(level_of[verts[0]]), set()).add(bucket.t_pad)
    return tiers


@pytest.mark.parametrize("direction", ["up", "down"])
def test_sweep_plan_sizes_each_chunk_to_its_rows(direction):
    """Each chunk holds rows of one level, in level order, at a width T that
    fits every row's neighbors; the plan pads fewer cells than giving every
    chunk its level's widest row would."""
    bn = build_bngraph(_cul_de_sac_city())
    level_of, ids_tab, w_tab = bn.sweep_tables(direction)
    deg = (ids_tab >= 0).sum(axis=1)
    plan = prepare_sweep(bn, direction)
    levels = []
    for bucket, off, verts in _chunks(plan):
        t = bucket.t_pad
        real = verts[verts < bn.n]
        assert real.size and (verts[real.size:] == bn.n).all()
        assert len(set(level_of[real].tolist())) == 1
        levels.append(int(level_of[real[0]]))
        nbr = np.asarray(bucket.nbr)[off * t : (off + bucket.chunk) * t].reshape(-1, t)
        w = np.asarray(bucket.w)[off * t : (off + bucket.chunk) * t].reshape(-1, t)
        for row, v in enumerate(real.tolist()):
            assert deg[v] <= t
            np.testing.assert_array_equal(nbr[row, : deg[v]], ids_tab[v, : deg[v]])
            np.testing.assert_array_equal(w[row, : deg[v]], w_tab[v, : deg[v]])
            assert (nbr[row, deg[v]:] == -1).all() and np.isinf(w[row, deg[v]:]).all()
    assert levels == sorted(levels)
    assert plan.cells == sum(b.nbr.shape[0] for b in plan.buckets)
    cap = construct_jax._next_pow2(int(deg.max()), lo=4)
    levelwise = 0
    for vs in bn.level_members(direction):
        chunk = (construct_jax.CHUNK_LARGE if len(vs) >= construct_jax._LARGE_LEVEL
                 else construct_jax.CHUNK_SMALL)
        levelwise += (-(-len(vs) // chunk) * chunk
                      * construct_jax._t_bucket(int(deg[vs].max()), cap))
    assert plan.cells < levelwise


@pytest.mark.parametrize("use_pallas", [False, True])
def test_sweep_padded_rows_write_nothing(use_pallas):
    """Rows that pad a chunk, or pad a branch's rows to the widest CHUNK,
    leave the dummy row n at (-1, +inf) and every real row as the reference
    has it, on plans whose levels do not fill their chunks in either tier,
    one of them a level whose chunks take three widths."""
    k = 6
    for g in (road_network(12, 12, seed=1), _cul_de_sac_city()):
        objects = pick_objects(g.n, 0.2, seed=1)
        bn = build_bngraph(g)
        plans = prepare_sweep(bn, "up"), prepare_sweep(bn, "down")
        up = plans[0]
        assert {c for _, c in up.bucket_signature()} == {
            construct_jax.CHUNK_SMALL, construct_jax.CHUNK_LARGE}
        assert any(s >= construct_jax._LARGE_LEVEL and s % construct_jax.CHUNK_LARGE
                   for s in up.level_sizes)
        assert any(s < construct_jax._LARGE_LEVEL and s % construct_jax.CHUNK_SMALL
                   for s in up.level_sizes)
        tables = object_extras(g.n, objects, k)
        for plan in plans:
            tables = run_sweep(plan, *tables, k, use_pallas=use_pallas)
            ids, d = (np.asarray(x) for x in tables)
            assert (ids[g.n] == -1).all() and np.isinf(d[g.n]).all()
        dists = np.where(ids[: g.n] >= 0, d[: g.n].astype(np.float64), np.inf)
        ref = knn_index_cons_plus(bn, objects, k)
        assert indices_equivalent(ref, KNNIndex(ids=ids[: g.n], dists=dists, k=k))
    assert max(len(t) for t in _level_tiers(bn, up).values()) >= 3


@pytest.mark.parametrize("direction", ["up", "down"])
def test_sweep_program_copies_no_table(direction):
    """The sweep loop writes each chunk's rows into the V_k carry in place:
    the compiled program holds no copy of an (n+1, k) table."""
    g = road_network(40, 40, seed=1)
    bn = build_bngraph(g)
    plan = prepare_sweep(bn, direction)
    assert len({c for _, c in plan.bucket_signature()}) == 2  # both CHUNK tiers
    k = 20
    table = jax.ShapeDtypeStruct((g.n + 1, k), np.int32)
    hlo = construct_jax._sweep_program_jit.lower(
        tuple((b.verts, b.nbr, b.w) for b in plan.buckets),
        plan.chunk_bucket, plan.chunk_off,
        table, table.update(dtype=np.float32),
        n=g.n, k=k, chunks=tuple(b.chunk for b in plan.buckets),
        use_pallas=False, interpret=None,
    ).compile().as_text()
    copy = re.compile(rf"\[{g.n + 1},{k}\]\S* copy(-start)?\(")
    assert "scatter" in hlo
    assert not [line for line in hlo.splitlines() if copy.search(line)]


def test_sweep_compilations_bounded_by_buckets():
    """A full build compiles at most one program per sweep direction."""
    g = road_network(11, 13, seed=7)
    objects = pick_objects(g.n, 0.2, seed=7)
    bn = build_bngraph(g)
    before = construct_jax.sweep_compile_count()
    if before < 0:
        import pytest

        pytest.skip("jit cache introspection unavailable in this jax version")
    build_knn_index_jax(bn, objects, 4, use_pallas=False)
    first = construct_jax.sweep_compile_count() - before
    n_buckets = len(prepare_sweep(bn, "up").buckets) + len(
        prepare_sweep(bn, "down").buckets
    )
    assert first <= min(2, n_buckets)
    # a rebuild on the same graph shape reuses every program
    build_knn_index_jax(bn, objects, 4, use_pallas=False)
    assert construct_jax.sweep_compile_count() - before == first
