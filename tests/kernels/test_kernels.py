"""Per-kernel shape/dtype sweeps: pallas (interpret) vs pure-jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rng(seed=0):
    return np.random.default_rng(seed)


@pytest.mark.parametrize("b", [1, 7, 128, 300])
@pytest.mark.parametrize("c,k", [(16, 3), (130, 10), (257, 20)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize("use_pallas", [True, False])
def test_topk_merge_sweep(b, c, k, dtype, use_pallas):
    rng = _rng(b * 1000 + c)
    ids = rng.integers(0, max(4, c // 3), size=(b, c)).astype(np.int32)
    ids[rng.random((b, c)) < 0.15] = -1
    d = np.round(rng.uniform(0, 64, size=(b, c)), 1).astype(dtype)
    got_i, got_d = ops.topk_merge(jnp.asarray(ids), jnp.asarray(d), k, use_pallas=use_pallas)
    want_i, want_d = ref.topk_merge_ref(jnp.asarray(ids), jnp.asarray(d), k)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(
        np.nan_to_num(np.asarray(got_d, np.float32), posinf=1e30),
        np.nan_to_num(np.asarray(want_d, np.float32), posinf=1e30),
        rtol=1e-3,
    )


def test_topk_merge_all_invalid_row():
    ids = jnp.full((4, 20), -1, jnp.int32)
    d = jnp.zeros((4, 20), jnp.float32)
    got_i, got_d = ops.topk_merge(ids, d, 5)
    assert (np.asarray(got_i) == -1).all()
    assert np.isinf(np.asarray(got_d)).all()


@pytest.mark.parametrize("m,k,n", [(32, 32, 32), (70, 90, 130), (128, 256, 128)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_minplus_sweep(m, k, n, dtype):
    rng = _rng(m + k + n)
    a = rng.uniform(0, 50, size=(m, k)).astype(dtype)
    b = rng.uniform(0, 50, size=(k, n)).astype(dtype)
    got = ops.minplus_matmul(jnp.asarray(a), jnp.asarray(b), block_m=32, block_n=64, block_k=32)
    want = ref.minplus_matmul_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_minplus_with_inf_padding():
    a = np.full((8, 8), np.inf, np.float32)
    a[0, 0] = 1.0
    b = np.full((8, 8), np.inf, np.float32)
    b[0, 0] = 2.0
    got = np.asarray(ops.minplus_matmul(jnp.asarray(a), jnp.asarray(b), block_m=8, block_n=8, block_k=8))
    assert got[0, 0] == 3.0 and np.isinf(got[1, 1])


def _frontier_case(seed, n, r, t, b, n_src):
    """Random frontier_relax instance with every pad convention exercised."""
    rng = _rng(seed)
    nbr = rng.integers(0, n, size=(r, t)).astype(np.int32)
    nbr[rng.random((r, t)) < 0.3] = -1          # padded neighbor slots
    rows = rng.choice(n, size=r, replace=False).astype(np.int32)
    rows[-1] = n                                 # padded receiver row
    w = np.where(nbr >= 0, rng.uniform(1, 9, size=nbr.shape), np.inf).astype(np.float32)
    dist = rng.uniform(0, 30, size=(n + 1, b)).astype(np.float32)
    dist[rng.random((n + 1, b)) < 0.4] = np.inf  # unreached entries
    dist[n] = np.inf                             # dummy row
    dist[:, n_src:] = np.inf                     # padded source columns
    kth = rng.uniform(0, 35, size=n + 1).astype(np.float32)
    kth[n] = np.inf
    src = np.full(b, -1, np.int32)
    src[:n_src] = rng.choice(n, size=n_src, replace=False)
    for i in range(n_src):                       # sources sit at distance 0
        dist[src[i], i] = 0.0
    return nbr, rows, w, dist, kth, src


@pytest.mark.parametrize("seed,n,r,t,b,n_src", [
    (0, 40, 9, 6, 8, 5),
    (1, 140, 9, 6, 128, 100),  # lane-aligned column count (TPU layout)
    (2, 150, 40, 17, 16, 11),  # receivers neighboring each other
    (3, 25, 6, 1, 8, 3),       # single neighbor column
])
def test_frontier_relax_pallas_vs_ref(seed, n, r, t, b, n_src):
    """The fused kernel must be bit-identical to the pure-Jacobi oracle even
    when receiver rows read each other: neighbor reads go through the
    non-aliased operand, so in-place receiver writes stay invisible."""
    args = [jnp.asarray(a) for a in _frontier_case(seed, n, r, t, b, n_src)]
    want = np.asarray(ref.frontier_relax_ref(*args))
    got_xla = np.asarray(ops.frontier_relax(*args, use_pallas=False))
    got_pl = np.asarray(ops.frontier_relax(*args, use_pallas=True))
    np.testing.assert_array_equal(got_xla, want)
    np.testing.assert_array_equal(got_pl, want)


def test_frontier_relax_gate_blocks_propagation():
    """A neighbor at dist >= kth must not propagate (checkIns), unless it is
    the column's source vertex — which always propagates."""
    n = 4
    nbr = np.array([[1]], np.int32)   # receiver 0 reads neighbor 1
    rows = np.array([0], np.int32)
    w = np.array([[2.0]], np.float32)
    dist = np.full((n + 1, 8), np.inf, np.float32)
    dist[1, 0] = 5.0                  # col 0: src elsewhere, 1 at 5.0
    dist[1, 1] = 0.0                  # col 1: 1 IS the source (dist 0)
    kth = np.full(n + 1, np.inf, np.float32)
    kth[1] = 4.0                      # gate closed: 5.0 >= 4.0, 0.0 < 4.0
    src = np.full(8, -1, np.int32)
    src[0] = 3
    src[1] = 1
    for use_pallas in (False, True):
        out = np.asarray(ops.frontier_relax(
            *[jnp.asarray(a) for a in (nbr, rows, w, dist, kth, src)],
            use_pallas=use_pallas,
        ))
        assert np.isinf(out[0, 0])        # blocked by the checkIns gate
        assert out[0, 1] == 2.0           # source column propagates at w
        np.testing.assert_array_equal(out[2:], dist[2:])  # untouched rows


def test_frontier_relax_all_pad_row_stays_inf():
    n = 6
    nbr = np.full((2, 3), -1, np.int32)
    rows = np.array([2, n], np.int32)
    w = np.full((2, 3), np.inf, np.float32)
    dist = np.full((n + 1, 8), np.inf, np.float32)
    kth = np.full(n + 1, np.inf, np.float32)
    src = np.full(8, -1, np.int32)
    for use_pallas in (False, True):
        out = np.asarray(ops.frontier_relax(
            *[jnp.asarray(a) for a in (nbr, rows, w, dist, kth, src)],
            use_pallas=use_pallas,
        ))
        assert np.isinf(out).all()
