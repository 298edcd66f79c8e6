"""Pallas TPU kernel: deduplicating top-k merge of candidate (id, dist) sets.

This is the numeric hot spot of the paper's construction (Lemmas 5.12/5.21):
for every vertex in a level, merge the C = tau*k candidate pairs gathered from
its bridge neighbors' lists and emit the k closest *distinct* objects.

TPU adaptation: a GPU implementation would bitonic-sort the candidates; the
TPU VPU has no efficient in-register sort, so we run k rounds of a vectorised
min-reduction over a VMEM-resident candidate tile, masking out every candidate
that shares the selected id (which performs the dedup for free). O(k*C) VPU
work, branch-free, one HBM read of the candidates and one HBM write of the
result per tile.

Grid: one dimension over vertex blocks. Block shapes: candidates (B_BLK, C) in
VMEM, outputs (B_BLK, k). C is padded to a multiple of 128 (lane width) by the
ops.py wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INT_MAX = jnp.iinfo(jnp.int32).max


def kround_merge(groups, k: int):
    """k rounds of dedup min-selection, row by row, over candidate groups.

    ``groups`` is a sequence of ``(ids, d)`` pairs, each (b, c_g) with its
    own width; row r's candidate set is the union of row r of every group,
    so callers never concatenate along lanes. ``d`` must already be +inf
    wherever ``ids < 0``. Semantics match ref.topk_merge_ref: the k
    smallest-distance distinct ids per row, distance ties broken by the
    smaller id, exhausted slots -> (-1, inf). Branch-free, and slot i is
    written through an iota mask, so the same code runs in XLA and inside
    the Pallas TPU kernels (which cannot lower ``dynamic_update_slice``).
    """
    b = groups[0][0].shape[0]
    ids = tuple(g[0] for g in groups)
    col = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)

    def rowmin(xs):
        return functools.reduce(
            jnp.minimum, [jnp.min(x, axis=1, keepdims=True) for x in xs]
        )

    def pick(i, out_ids, out_d, ds):
        dmin = rowmin(ds)
        # tie-break: smallest id among distance ties
        idmin = rowmin(jnp.where(d == dmin, g, _INT_MAX) for g, d in zip(ids, ds))
        ok = dmin < jnp.inf
        here = col == i
        out_ids = jnp.where(here, jnp.where(ok, idmin, -1), out_ids)
        out_d = jnp.where(here, jnp.where(ok, dmin, jnp.inf), out_d)
        # drop every candidate carrying the selected id -> dedup for free
        ds = tuple(jnp.where(g == idmin, jnp.inf, d) for g, d in zip(ids, ds))
        return out_ids, out_d, ds

    # slot 0 is peeled so the loop carry derives from the candidates: inside
    # shard_map it then varies over the mesh axis like the body's result
    first = pick(0, -1, jnp.inf, tuple(g[1] for g in groups))
    out_ids, out_d, _ = jax.lax.fori_loop(1, k, lambda i, c: pick(i, *c), first)
    return out_ids, out_d


def _topk_merge_kernel(ids_ref, d_ref, oid_ref, od_ref, *, k: int):
    ids = ids_ref[...]
    d = jnp.where(ids < 0, jnp.inf, d_ref[...].astype(jnp.float32))
    out_ids, out_d = kround_merge([(ids, d)], k)
    oid_ref[...] = out_ids
    od_ref[...] = out_d.astype(od_ref.dtype)


def topk_merge_pallas(
    cand_ids: jax.Array,  # (B, C) int32, -1 = invalid
    cand_d: jax.Array,    # (B, C) float
    k: int,
    *,
    block_b: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """k nearest distinct-(id) candidates per row; rows padded to block_b."""
    b, c = cand_ids.shape
    assert b % block_b == 0, f"B={b} must be padded to a multiple of {block_b}"
    grid = (b // block_b,)
    kernel = functools.partial(_topk_merge_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, c), lambda i: (i, 0)),
            pl.BlockSpec((block_b, c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.int32),
            jax.ShapeDtypeStruct((b, k), cand_d.dtype),
        ],
        interpret=interpret,
    )(cand_ids, cand_d)
