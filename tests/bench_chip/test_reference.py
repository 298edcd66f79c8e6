"""The plain reference against a brute-force all-pairs table, the exact
comparison against altered answers, and the control: the reference in
bfloat16 in the program's place fails the comparison."""
import numpy as np
import pytest

from bench_names import CELLS, FLEET, TINY, cache_dir_fixture  # noqa: F401
import harness
import network
import reference

SPEC = {"grid": 7, "delete_frac": 0.18, "diag_frac": 0.08, "weight_low": 100,
        "weight_high": 1000, "graph_seed": 5}


@pytest.fixture(scope="module")
def small():
    g = network.road_network(SPEC)
    d = np.full((g.n, g.n), np.inf)
    np.fill_diagonal(d, 0.0)
    for u in range(g.n):
        s, e = g.indptr[u], g.indptr[u + 1]
        d[u, g.indices[s:e]] = g.weights[s:e]
    for m in range(g.n):  # Floyd-Warshall
        d = np.minimum(d, d[:, m:m + 1] + d[m:m + 1, :])
    is_obj = np.zeros(g.n, bool)
    is_obj[np.random.default_rng(1).choice(g.n, 9, replace=False)] = True
    return g, d, is_obj


def test_knn_matches_all_pairs(small):
    g, d, is_obj = small
    objs = np.flatnonzero(is_obj)
    for u in range(g.n):
        for k in (1, 4, 9, 12):
            ids, dist = reference.answer(g, is_obj, k, u, 12)
            want = np.sort(d[u, objs])[:k]
            assert np.array_equal(dist[: len(want)], want)
            assert np.array_equal(d[u, ids[: len(want)]], want)
            assert (ids[len(want):] == -1).all()


def test_compare_accepts_the_reference_and_flags_altered_answers(small):
    g, _, is_obj = small
    u, k = 3, 5
    ids, d = reference.answer(g, is_obj, k, u, 8)
    assert reference.compare(g, is_obj, k, u, ids, d) is None
    bad_d = d.copy()
    bad_d[2] += 1
    assert reference.compare(g, is_obj, k, u, ids, bad_d) is not None
    rep = ids.copy()
    rep[1] = rep[0]
    assert reference.compare(g, is_obj, k, u, rep, d) is not None
    missing_ids, missing_d = ids.copy(), d.copy()
    missing_ids[k - 1], missing_d[k - 1] = -1, np.inf
    assert reference.compare(g, is_obj, k, u, missing_ids, missing_d) is not None
    extra_ids = ids.copy()
    extra_ids[k] = int(np.flatnonzero(~np.isin(np.arange(g.n), ids))[0])
    assert reference.compare(g, is_obj, k, u, extra_ids, d) is not None
    not_obj = ids.copy()
    not_obj[0] = int(np.flatnonzero(~is_obj)[0])
    assert reference.compare(g, is_obj, k, u, not_obj, d) is not None


def test_round_bf16():
    assert reference.round_bf16(256.0) == 256.0
    assert reference.round_bf16(257.0) == 256.0
    assert reference.round_bf16(259.0) == 260.0
    assert reference.round_bf16(1234.0) == 1232.0


@pytest.mark.parametrize("cell,overrides", [(c, {}) for c in CELLS] + [(CELLS[0], FLEET)],
                         ids=CELLS + ["fleet"])
def test_control_fails_where_the_program_passes(cell, overrides, cache_dir):
    """The control at a size a test run holds: the program's window answers
    compare clean, the bfloat16 reference on the same sample does not."""
    import control

    c = harness.resolve(harness.load_benchmark(), cell)
    for key, value in dict(TINY, **overrides).items():
        harness.override(c, key, value)
    r = control.readings(c, 2**31 + 99, 0.3, cache_dir)
    assert r["compared"] > 0
    assert r["wrong_answers"] == 0
    assert r["control_wrong_answers"] > 0
