"""Fused Pallas TPU kernel for one construction sweep step (chunk of a level).

The level-synchronous construction (Algorithm 3, see core/construct_jax.py)
repeats, for every vertex of a level,

    gather the k-lists of its bridge neighbors from the live V_k tables
    -> shift every candidate by the connecting edge weight
    -> merge with the vertex's extra candidates (Lemmas 5.12/5.21)
    -> keep the k closest *distinct* objects
    -> scatter the merged row back into the V_k tables.

The unfused form (the XLA path in kernels/ops.py) materialises the
(CHUNK, T*k) gathered candidates in HBM before the merge. This kernel never
does: the Pallas pipeline DMAs, per grid step, only the table tile that holds
the one neighbor row it needs.

Mechanics: the neighbor ids, target rows and edge weights are
scalar-prefetched (flattened, so SMEM holds R*T words rather than R lane-padded
rows); the grid is (CHUNK, T) and the gathers are BlockSpec index maps reading
them. A TPU block must span 8 rows or the whole table, so each step fetches
the (8, k) tile holding its row and picks the row out in VMEM. Step (i, j)
folds neighbor j's shifted k-list into row i's running top-k with
``kround_merge`` (step (i, 0) seeds it with row i's extras). Top-k with dedup
is associative — an id dropped from a running top-k is beaten by k distinct
ids that stay — so the streamed merge equals the one-shot merge of the whole
candidate row, bit for bit. At the last column the row lands in its
(8, k) output tile, which the pipeline writes back once all 8 rows are done.

The kernel emits the merged (CHUNK, k) tile; its caller scatters it into the
tables in XLA (``ops.sweep_merge``, or once per chunk in the sweep loop). The
kernel therefore only ever reads the pre-step tables and has no aliased
operand to get wrong.

Padded rows use vertex id n (the dummy row) and padded neighbor slots use -1
(their weight is ignored), exactly as in the XLA path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_merge import kround_merge

ROW_BLOCK = 8              # rows per gathered/emitted tile: the TPU sublane tile
_SMEM_WORDS = 1 << 17      # scalar-prefetch budget per call (half of v5e's SMEM)


def row_slices(rows: int, scalars_per_row: int) -> list[tuple[int, int]]:
    """Split ``rows`` (a ROW_BLOCK multiple) into per-call (start, size)
    slices whose scalar-prefetch operands fit the SMEM budget."""
    cap = max(ROW_BLOCK, _SMEM_WORDS // scalars_per_row // ROW_BLOCK * ROW_BLOCK)
    return [(s, min(cap, rows - s)) for s in range(0, rows, cap)]


def pad_rows(x: jax.Array, rows: int, value) -> jax.Array:
    """Pad axis 0 of ``x`` to ``rows``."""
    widths = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=value)


def clamp_row(x, n1: int):
    """Table row for id ``x``: padded ids (< 0) read the dummy row n1 - 1."""
    return jnp.where(x >= 0, x, n1 - 1)


def tile_map(ids_ref, pos, n1: int):
    """Block index of the (ROW_BLOCK, ·) tile holding row ``ids_ref[pos]``."""
    return (clamp_row(ids_ref[pos], n1) // ROW_BLOCK, 0)


def tile_row(tile_ref, row):
    """Row ``row`` (already clamped) out of the (ROW_BLOCK, ·) tile that
    ``tile_map`` fetched for it, as a (1, ·) value."""
    return tile_ref[pl.ds(row % ROW_BLOCK, 1), :]


def _sweep_merge_kernel(
    nbr_s, verts_s, w_s,             # scalar prefetch: (R*T,), (R,), (R*T,)
    exi_ref, exd_ref, vki_ref, vkd_ref,
    oi_ref, od_ref,
    acc_i, acc_d,                    # VMEM (1, k) running top-k of row i
    *, k: int, t: int, n1: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    u = nbr_s[i * t + j]

    @pl.when(j == 0)
    def _seed_with_extras():
        v = verts_s[i]
        e_ids = tile_row(exi_ref, v)
        e_d = jnp.where(e_ids >= 0, tile_row(exd_ref, v), jnp.inf)
        acc_i[...], acc_d[...] = kround_merge([(e_ids, e_d)], k)

    g_ids = tile_row(vki_ref, clamp_row(u, n1))
    ok = (u >= 0) & (g_ids >= 0)
    g_d = w_s[i * t + j] + tile_row(vkd_ref, clamp_row(u, n1))
    acc_i[...], acc_d[...] = kround_merge(
        [(acc_i[...], acc_d[...]),
         (jnp.where(ok, g_ids, -1), jnp.where(ok, g_d, jnp.inf))],
        k,
    )

    @pl.when(j == t - 1)
    def _emit():
        oi_ref[pl.ds(i % ROW_BLOCK, 1), :] = acc_i[...]
        od_ref[pl.ds(i % ROW_BLOCK, 1), :] = acc_d[...]


def _sweep_merge_call(nbr, verts, w, ex_ids, ex_d, vk_ids, vk_d, *, k, interpret):
    rows, t = nbr.shape
    e = ex_ids.shape[1]
    n1 = vk_ids.shape[0]
    # table tiles holding neighbor j's row and row i's extras
    gather = pl.BlockSpec(
        (ROW_BLOCK, k), lambda i, j, nbr_s, *_: tile_map(nbr_s, i * t + j, n1))
    extras = pl.BlockSpec(
        (ROW_BLOCK, e), lambda i, j, nbr_s, verts_s, w_s: tile_map(verts_s, i, n1))
    out = pl.BlockSpec((ROW_BLOCK, k), lambda i, j, *_: (i // ROW_BLOCK, 0))
    return pl.pallas_call(
        functools.partial(_sweep_merge_kernel, k=k, t=t, n1=n1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, t),
            in_specs=[extras, extras, gather, gather],
            out_specs=[out, out],
            scratch_shapes=[
                pltpu.VMEM((1, k), jnp.int32),
                pltpu.VMEM((1, k), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, k), jnp.int32),
            jax.ShapeDtypeStruct((rows, k), jnp.float32),
        ],
        interpret=interpret,
    )(nbr.reshape(-1), verts, w.reshape(-1), ex_ids, ex_d, vk_ids, vk_d)


def sweep_merge_pallas(
    nbr: jax.Array,       # (CHUNK, T) int32, -1 = padded slot
    verts: jax.Array,     # (CHUNK,)  int32, n = padded row (dummy)
    w: jax.Array,         # (CHUNK, T) float32, ignored on padded slots
    ex_ids: jax.Array,    # (n+1, E) int32 per-vertex extra candidates
    ex_d: jax.Array,      # (n+1, E) float32
    vk_ids: jax.Array,    # (n+1, k) int32 live table
    vk_d: jax.Array,      # (n+1, k) float32 live table
    *,
    k: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One fused construction step; returns the merged (CHUNK, k) rows."""
    chunk, t = nbr.shape
    rows = -(-chunk // ROW_BLOCK) * ROW_BLOCK
    nbr_p = pad_rows(nbr, rows, -1)
    verts_p = pad_rows(verts, rows, vk_ids.shape[0] - 1)
    w_p = pad_rows(w, rows, jnp.inf)
    parts = [
        _sweep_merge_call(
            nbr_p[s:s + m], verts_p[s:s + m], w_p[s:s + m],
            ex_ids, ex_d, vk_ids, vk_d, k=k, interpret=interpret,
        )
        for s, m in row_slices(rows, 2 * t + 1)
    ]
    m_ids = jnp.concatenate([p[0] for p in parts])[:chunk]
    m_d = jnp.concatenate([p[1] for p in parts])[:chunk]
    return m_ids, m_d
