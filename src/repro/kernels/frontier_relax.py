"""Fused Pallas TPU kernel for one batched checkIns frontier round.

The batched insert frontier (Algorithm 4's checkIns search, run for a whole
staged batch of inserted objects at once) keeps a multi-source tentative
distance matrix ``dist`` of shape (n+1, B) on device — row v holds, per
source column i, the best known pruned distance from inserted object
``src[i]`` to vertex v. One round relaxes every *receiver* row v (a BNS
neighbor of last round's changed vertices) against its bridge neighbors:

    new[v, i] = min(dist[v, i],
                    min over u in BNS(v), gate(u, i) of  w(v, u) + dist[u, i])
    gate(u, i) = dist[u, i] < kth[u]  or  u == src[i]        (checkIns)

The XLA form (kernels/ops.py) runs a fori_loop over the neighbor columns to
avoid the (R, T, B) candidate tensor; this kernel fuses the round the same
way sweep_merge fuses a construction step. The neighbor table ``nbr``, the
receiver rows and, per neighbor slot, the edge weight and the neighbor's
pruning bound ``kth[u]`` (gathered in XLA by the wrapper) are
scalar-prefetched, flattened; the grid is (R, T), and each grid step DMAs
the (8, B) tile of ``dist`` holding one neighbor's distance row (a TPU block
spans 8 rows), picks the row out in VMEM, and min-folds it, gated and
shifted, into the receiver's row of the (8, B) output tile. Step (i, 0)
seeds that row with the receiver's own pre-round row.

Jacobi discipline: the kernel emits the (R, B) tile of new receiver rows and
the wrapper scatters it into ``dist`` in XLA, so every neighbor read sees
the pre-round values even when receivers neighbor each other — bit-identical
to the pure-Jacobi reference for any receiver set, which the exactness
contract of the engine (scalar vs sharded table equality) relies on.

Padded receiver rows use vertex id n (the dummy row: all-pad neighbors, +inf
distances). Padded neighbor slots use -1 (their weight is ignored); padded
source columns use src = -1 (matching no vertex) with all-+inf distance
columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sweep_merge import (
    ROW_BLOCK, clamp_row, pad_rows, row_slices, tile_map, tile_row,
)


def _frontier_relax_kernel(
    nbr_s, rows_s, w_s, kth_s,           # scalar prefetch: (R*T,), (R,), (R*T,) x2
    src_ref, nd_ref, own_ref,
    out_ref,
    *, t: int, n1: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    u = nbr_s[i * t + j]
    at = pl.ds(i % ROW_BLOCK, 1)

    @pl.when(j == 0)
    def _seed_with_own_row():
        out_ref[at, :] = tile_row(own_ref, rows_s[i])

    nd = tile_row(nd_ref, clamp_row(u, n1))              # (1, B) neighbor row
    gate = (nd < kth_s[i * t + j]) | (src_ref[...] == u)
    ok = (u >= 0) & gate
    cand = w_s[i * t + j] + nd
    out_ref[at, :] = jnp.minimum(out_ref[at, :], jnp.where(ok, cand, jnp.inf))


def _frontier_relax_call(nbr, rows, w, kth_g, dist, src, *, interpret):
    r, t = nbr.shape
    n1, b = dist.shape

    def tile(ids: int, pos):
        """The (ROW_BLOCK, B) dist tile holding row ``prefetch[ids][pos(i, j)]``."""
        return pl.BlockSpec(
            (ROW_BLOCK, b), lambda i, j, *s: tile_map(s[ids], pos(i, j), n1)
        )

    return pl.pallas_call(
        functools.partial(_frontier_relax_kernel, t=t, n1=n1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(r, t),
            in_specs=[
                pl.BlockSpec((1, b), lambda i, j, *_: (0, 0)),   # src (bcast)
                tile(0, lambda i, j: i * t + j),                 # neighbor rows
                tile(1, lambda i, j: i),                         # own rows
            ],
            out_specs=pl.BlockSpec((ROW_BLOCK, b), lambda i, j, *_: (i // ROW_BLOCK, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((r, b), jnp.float32),
        interpret=interpret,
    )(nbr.reshape(-1), rows, w.reshape(-1), kth_g.reshape(-1), src.reshape(1, b), dist, dist)


def frontier_relax_pallas(
    nbr: jax.Array,   # (R, T) int32 neighbor ids, -1 = padded slot
    rows: jax.Array,  # (R,)  int32 receiver rows, n = padded row (dummy)
    w: jax.Array,     # (R, T) float32 edge weights, ignored on pads
    dist: jax.Array,  # (n+1, B) float32 tentative distances
    kth: jax.Array,   # (n+1,) float32 pruning bounds
    src: jax.Array,   # (B,) int32 source vertex per column, -1 pad
    *,
    interpret: bool = False,
    sorted_rows: bool = False,
) -> jax.Array:
    """One fused frontier round; returns the updated (n+1, B) dist matrix.
    ``sorted_rows`` as ``ops.frontier_relax``."""
    r, t = nbr.shape
    n1 = dist.shape[0]
    padded = -(-r // ROW_BLOCK) * ROW_BLOCK
    nbr_p = pad_rows(nbr, padded, -1)
    rows_p = pad_rows(rows, padded, n1 - 1)
    w_p = pad_rows(w, padded, jnp.inf)
    kth_g = kth[clamp_row(nbr_p, n1)]
    new = jnp.concatenate([
        _frontier_relax_call(
            nbr_p[s:s + m], rows_p[s:s + m], w_p[s:s + m], kth_g[s:s + m],
            dist, src, interpret=interpret,
        )
        for s, m in row_slices(padded, 3 * t + 1)
    ])
    return dist.at[rows].set(new[:r], indices_are_sorted=sorted_rows)
