"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def topk_merge_ref(cand_ids: jax.Array, cand_d: jax.Array, k: int):
    """k smallest-distance distinct ids per row; ties broken by smaller id."""
    if cand_ids.shape[1] < k:  # fewer candidate slots than outputs: pad
        pad = ((0, 0), (0, k - cand_ids.shape[1]))
        cand_ids = jnp.pad(cand_ids, pad, constant_values=-1)
        cand_d = jnp.pad(cand_d, pad, constant_values=jnp.inf)
    d = jnp.where(cand_ids < 0, jnp.inf, cand_d.astype(jnp.float32))

    def row(ids_r, d_r):
        order = jnp.lexsort((d_r, ids_r))  # by id, then dist
        sid, sd = ids_r[order], d_r[order]
        first = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
        sd = jnp.where(first, sd, jnp.inf)  # dedup: keep min dist per id
        order2 = jnp.lexsort((sid, sd))  # by dist, then id
        top_ids = sid[order2[:k]]
        top_d = sd[order2[:k]]
        return jnp.where(jnp.isfinite(top_d), top_ids, -1), top_d

    out_ids, out_d = jax.vmap(row)(cand_ids, d)
    return out_ids, out_d.astype(cand_d.dtype)


def sweep_merge_ref(
    nbr: jax.Array,     # (CHUNK, T) int32, -1 = padded slot
    verts: jax.Array,   # (CHUNK,)  int32, n = dummy row
    w: jax.Array,       # (CHUNK, T) float32
    ex_ids: jax.Array,  # (n+1, E) int32
    ex_d: jax.Array,    # (n+1, E) float32
    vk_ids: jax.Array,  # (n+1, k) int32
    vk_d: jax.Array,    # (n+1, k) float32
    k: int,
):
    """Unfused oracle for the sweep_merge kernel: explicit candidate tensor.

    gather neighbor k-lists -> shift by edge weight -> append extras ->
    topk_merge_ref -> scatter rows back into copies of the V_k tables.
    """
    chunk, t = nbr.shape
    n1 = vk_ids.shape[0]
    valid = nbr >= 0
    nbr_c = jnp.where(valid, nbr, n1 - 1)
    g_ids = jnp.where(valid[..., None], vk_ids[nbr_c], -1)
    g_d = w[..., None] + vk_d[nbr_c]
    cand_ids = jnp.concatenate([g_ids.reshape(chunk, t * k), ex_ids[verts]], axis=1)
    cand_d = jnp.concatenate([g_d.reshape(chunk, t * k), ex_d[verts]], axis=1)
    m_ids, m_d = topk_merge_ref(cand_ids, cand_d.astype(jnp.float32), k)
    return vk_ids.at[verts].set(m_ids), vk_d.at[verts].set(m_d)


def frontier_relax_ref(
    nbr: jax.Array,   # (R, T) int32 BNS neighbor ids per receiver row, -1 pad
    rows: jax.Array,  # (R,)  int32 receiver vertex ids, n = dummy pad
    w: jax.Array,     # (R, T) float  BNS edge weights, +inf on pads
    dist: jax.Array,  # (n+1, B) tentative multi-source distance columns
    kth: jax.Array,   # (n+1,) per-vertex k-th-distance pruning bound
    src: jax.Array,   # (B,)  int32 source vertex per column, -1 pad
):
    """Unfused oracle for one batched pruned-relaxation (checkIns) round.

    For every receiver row v and source column i:
        new[v, i] = min(dist[v, i],
                        min over u in BNS(v) with gate(u, i) of
                            w(v, u) + dist[u, i])
    where ``gate(u, i) = dist[u, i] < kth[u]  or  u == src[i]`` — Algorithm
    4's checkIns test: a neighbor u propagates distance mass only while the
    inserted object would enter u's top-k (or u is the source itself). Pure
    Jacobi: every read sees the pre-round ``dist``. Materialises the full
    (R, T, B) candidate tensor; the production forms in kernels/ops.py and
    kernels/frontier_relax.py compute the same values without it.
    """
    n1 = dist.shape[0]
    valid = nbr >= 0
    nc = jnp.where(valid, nbr, n1 - 1)
    nd = dist[nc]                                            # (R, T, B)
    gate = (nd < kth[nc][..., None]) | (nc[..., None] == src[None, None, :])
    cand = jnp.where(valid[..., None] & gate, w[..., None] + nd, jnp.inf)
    acc = jnp.minimum(dist[rows], jnp.min(cand, axis=1))
    return dist.at[rows].set(acc)


def minplus_matmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    out = jnp.min(af[:, :, None] + bf[None, :, :], axis=1)
    return out.astype(a.dtype)
