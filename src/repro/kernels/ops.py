"""Public wrappers around the Pallas kernels and their XLA forms.

``use_pallas`` picks the Pallas kernel or the plain XLA form of the same
math. On the Pallas path a wrapper pads inputs to the kernel's tiling
constraints and, unless ``interpret`` is given, runs the kernel compiled on a
TPU and in interpret mode on any other backend (CPU runs validate the kernel
bodies; tests/kernels/test_tpu_compile.py checks that they compile for the
chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.frontier_relax import frontier_relax_pallas
from repro.kernels.minplus import minplus_matmul_pallas
from repro.kernels.sweep_merge import sweep_merge_pallas
from repro.kernels.topk_merge import kround_merge, topk_merge_pallas


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("k", "block_b", "use_pallas", "interpret"))
def topk_merge(
    cand_ids: jax.Array,
    cand_d: jax.Array,
    k: int,
    *,
    block_b: int = 128,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k distinct-(id) merge. cand_ids: (B, C) int32 (-1 invalid).

    The XLA form is the kernel's own ``kround_merge`` loop, not the
    sort-based ``ref.topk_merge_ref`` oracle: its two vmapped lexsorts take
    seconds to compile for a TPU (this loop well under one), once per
    candidate shape, and a flush compiles one shape per round width.
    """
    if not use_pallas:
        d = jnp.where(cand_ids < 0, jnp.inf, cand_d.astype(jnp.float32))
        m_ids, m_d = kround_merge([(cand_ids, d)], k)
        return m_ids, m_d.astype(cand_d.dtype)
    b = cand_ids.shape[0]
    ids = _pad_to(_pad_to(cand_ids, 1, 128, -1), 0, block_b, -1)
    d = _pad_to(_pad_to(cand_d, 1, 128, jnp.inf), 0, block_b, jnp.inf)
    itp = (not _on_tpu()) if interpret is None else interpret
    oid, od = topk_merge_pallas(ids, d, k, block_b=block_b, interpret=itp)
    return oid[:b], od[:b]


def sweep_merge_rows(
    nbr: jax.Array,
    verts: jax.Array,
    w: jax.Array,
    ex_ids: jax.Array,
    ex_d: jax.Array,
    vk_ids: jax.Array,
    vk_d: jax.Array,
    k: int,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused construction step without its write: gather + shift + dedup top-k.

    Returns the merged (CHUNK, k) rows for ``verts``, built from the k-lists
    of the neighbors in ``nbr`` (shifted by ``w``) merged with per-vertex
    extras, and only reads the live (n+1, k) V_k tables. Unlike the other
    wrappers this is a *trace-level* function, meant to be called inside an
    already-jitted loop (core/construct_jax.py), so it does no padding or jit
    of its own: the caller guarantees the layout invariants (padded slots
    -1/+inf, dummy row n).

    The XLA form materialises the (CHUNK, T*k) gathered candidates and runs
    the same k-round merge; the Pallas path never materialises them (see
    sweep_merge.py).
    """
    if not use_pallas:
        chunk, t = nbr.shape
        n1 = vk_ids.shape[0]
        valid = nbr >= 0
        nbr_c = jnp.where(valid, nbr, n1 - 1)
        g_ids = jnp.where(valid[..., None], vk_ids[nbr_c], -1)
        g_ids = g_ids.reshape(chunk, t * k)
        g_d = (w[..., None] + vk_d[nbr_c]).reshape(chunk, t * k)
        e_ids = ex_ids[verts]
        return kround_merge(
            [(g_ids, jnp.where(g_ids < 0, jnp.inf, g_d)),
             (e_ids, jnp.where(e_ids < 0, jnp.inf, ex_d[verts].astype(jnp.float32)))],
            k,
        )
    itp = (not _on_tpu()) if interpret is None else interpret
    return sweep_merge_pallas(
        nbr, verts, w, ex_ids, ex_d, vk_ids, vk_d, k=k, interpret=itp
    )


def sweep_merge(
    nbr: jax.Array,
    verts: jax.Array,
    w: jax.Array,
    ex_ids: jax.Array,
    ex_d: jax.Array,
    vk_ids: jax.Array,
    vk_d: jax.Array,
    k: int,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``sweep_merge_rows`` plus its write: returns the updated (vk_ids, vk_d).

    A trace-level function like ``sweep_merge_rows``. The scatter yields new
    tables, so every read of the step sees the pre-step tables (Jacobi).
    """
    m_ids, m_d = sweep_merge_rows(
        nbr, verts, w, ex_ids, ex_d, vk_ids, vk_d, k,
        use_pallas=use_pallas, interpret=interpret,
    )
    return vk_ids.at[verts].set(m_ids), vk_d.at[verts].set(m_d)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k", "use_pallas", "interpret"))
def minplus_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Tropical (min,+) matmul C = A (+,min) B."""
    if not use_pallas:
        return ref.minplus_matmul_ref(a, b)
    m, kdim = a.shape
    _, n = b.shape
    ap = _pad_to(_pad_to(a, 0, block_m, jnp.inf), 1, block_k, jnp.inf)
    bp = _pad_to(_pad_to(b, 0, block_k, jnp.inf), 1, block_n, jnp.inf)
    itp = (not _on_tpu()) if interpret is None else interpret
    out = minplus_matmul_pallas(
        ap, bp, block_m=block_m, block_n=block_n, block_k=block_k, interpret=itp
    )
    return out[:m, :n]


def serve_gather(
    vk_ids: jax.Array,   # (n+1, k) int32 live index table (dummy row last)
    vk_d: jax.Array,     # (n+1, k) float32
    queries: jax.Array,  # (B,) int32 query vertices
    ks: jax.Array,       # (B,) int32 per-query result count, <= k
) -> tuple[jax.Array, jax.Array]:
    """Batched kNN query: one row gather + per-query k mask (Theorem 4.3).

    Traced inside ``answer_program(serve_gather)``, the program that packs
    its (B, k) answer for the readback.
    """
    return mask_answer(vk_ids[queries], vk_d[queries], ks)


def mask_answer(ids: jax.Array, d: jax.Array, ks: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Columns at positions >= ks[b] of a (B, k) answer to the pad sentinel
    (-1, +inf), so one (B, k) launch serves heterogeneous-k traffic."""
    b, k = ids.shape
    mask = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1) < ks[:, None]
    return jnp.where(mask, ids, -1), jnp.where(mask & (ids >= 0), d, jnp.inf)


def pack_answer(ids: jax.Array, d: jax.Array) -> jax.Array:
    """A (B, k) answer as one lane-dense (B*2k,) int32 buffer: row b holds
    its k ids, then the bits of its k float32 distances. Read back in one
    transfer and split by ``unpack_answer``."""
    bits = jax.lax.bitcast_convert_type(d, jnp.int32)
    return jnp.concatenate([ids, bits], axis=1).reshape(-1)


def unpack_answer(buf: np.ndarray, k: int, width: int) -> tuple[np.ndarray, np.ndarray]:
    """The host side of ``pack_answer``: (B, width) int32 ids and float32
    distances, views of ``buf`` bit for bit (pads and all)."""
    rows = buf.reshape(-1, 2 * k)
    return rows[:, :width], rows[:, k : k + width].view(np.float32)


@functools.cache
def answer_program(gather):  # replint: disable=REP003(one jit per gather function, memoized by functools.cache)
    """The one jitted program of a query batch: ``gather`` (``serve_gather``'s
    signature) and ``pack_answer`` of its answer. It carries the gather's
    name, so the device trace shows ``jit_serve_gather``; callers pass
    ``ops.serve_gather`` as they find it, so a replaced gather gets a
    program of its own."""

    @functools.wraps(gather)
    def program(vk_ids, vk_d, queries, ks):
        return pack_answer(*gather(vk_ids, vk_d, queries, ks))

    return jax.jit(program)


@jax.jit
def rows_containing(vk_ids: jax.Array, obj_ids: jax.Array) -> jax.Array:
    """(n,) bool: which index rows hold any of ``obj_ids`` (dummy row excluded).

    The vectorized replacement for the host checkDel membership scan: the
    rows a batched delete must repair are exactly the rows naming a deleted
    object, and this finds them in one device pass over the table.
    """
    return (vk_ids[:-1, :, None] == obj_ids[None, None, :]).any(axis=(1, 2))


def frontier_relax(
    nbr: jax.Array,   # (R, T) int32 BNS neighbor ids per receiver, -1 pad
    rows: jax.Array,  # (R,)  int32 receiver rows, n (dummy) = padding
    w: jax.Array,     # (R, T) float32 BNS edge weights, +inf on pads
    dist: jax.Array,  # (n+1, B) float32 multi-source tentative distances
    kth: jax.Array,   # (n+1,) float32 k-th-distance pruning bounds
    src: jax.Array,   # (B,) int32 source vertex per column, -1 pad
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
    sorted_rows: bool = False,
) -> jax.Array:
    """One batched pruned-relaxation round of the checkIns frontier.

    Relaxes every receiver row's BNS edges for a whole batch of insert
    sources at once: column i of ``dist`` is the tentative distance field of
    source ``src[i]``, and a neighbor u only propagates into column i while
    ``dist[u, i] < kth[u]`` (Algorithm 4's checkIns test — the insertion
    still improves u's top-k) or u is the source itself. Returns the updated
    ``dist``; the caller derives the changed-row mask that narrows the next
    round's frontier (the same discipline the delete-repair rounds use).

    Like ``sweep_merge`` this is a trace-level function meant to be called
    inside an already-jitted round program; the caller guarantees the layout
    invariants (pad conventions above, dummy row n all +inf). The XLA form
    runs a fori_loop over neighbor columns so only (R, B) intermediates ever
    materialise; the Pallas kernel fuses the gather/gate/min per neighbor
    row (see kernels/frontier_relax.py). Both are pure Jacobi: every
    neighbor read sees the pre-round ``dist``.

    ``sorted_rows`` promises ``rows`` ascending (pads, the largest id, last),
    which the write back passes on to the scatter: the TPU's compiler then
    compiles it in a time that does not grow with R.
    """
    if not use_pallas:
        n1 = dist.shape[0]

        def body(t, acc):
            nv = jax.lax.dynamic_index_in_dim(nbr, t, axis=1, keepdims=False)
            wv = jax.lax.dynamic_index_in_dim(w, t, axis=1, keepdims=False)
            valid = nv >= 0
            nc = jnp.where(valid, nv, n1 - 1)
            nd = dist[nc]                                        # (R, B)
            gate = (nd < kth[nc][:, None]) | (nc[:, None] == src[None, :])
            cand = wv[:, None] + nd
            ok = valid[:, None] & gate
            return jnp.minimum(acc, jnp.where(ok, cand, jnp.inf))

        acc = jax.lax.fori_loop(0, nbr.shape[1], body, dist[rows])
        return dist.at[rows].set(acc, indices_are_sorted=sorted_rows)
    itp = (not _on_tpu()) if interpret is None else interpret
    return frontier_relax_pallas(nbr, rows, w, dist, kth, src, interpret=itp,
                                 sorted_rows=sorted_rows)


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret", "sorted_rows"))
def rows_purge_merge(
    vk_ids: jax.Array,    # (n+1, k) int32 live table
    vk_d: jax.Array,      # (n+1, k) float32
    rows: jax.Array,      # (R,) int32 target rows, n (dummy) = padding
    del_ids: jax.Array,   # (D,) int32 deleted object ids, n = padding
    cand_ids: jax.Array,  # (R, P) int32 new candidates per row, -1 = padding
    cand_d: jax.Array,    # (R, P) float32
    k: int,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
    sorted_rows: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused batched move repair: purge + candidate merge in ONE pass.

    The device form of a coalesced *move* flush (Algorithms 4+5 combined):
    each row is gathered once, its entries naming a deleted object become pad
    sentinels, the surviving entries and the new insert candidates run through
    one dedup top-k merge, and the row scatters back — instead of a purge
    gather/merge/scatter followed by a separate insert gather/merge/scatter
    over largely the same rows. ``rows`` is the union of the delete-hit rows
    and the insert (checkIns) frontier; rows outside one of the two sets just
    carry all-pad columns for the other. ``sorted_rows`` as in
    ``frontier_relax``.
    """
    own_ids = vk_ids[rows]
    own_d = vk_d[rows]
    hit = (own_ids[:, :, None] == del_ids[None, None, :]).any(axis=-1)
    pid = jnp.where(hit, -1, own_ids)
    pd = jnp.where(hit, jnp.inf, own_d)
    cat_ids = jnp.concatenate([pid, cand_ids], axis=1)
    cat_d = jnp.concatenate([pd, cand_d.astype(vk_d.dtype)], axis=1)
    cat_d = jnp.where(cat_ids < 0, jnp.inf, cat_d)
    m_ids, m_d = topk_merge(cat_ids, cat_d, k, use_pallas=use_pallas, interpret=interpret)
    return (vk_ids.at[rows].set(m_ids, indices_are_sorted=sorted_rows),
            vk_d.at[rows].set(m_d, indices_are_sorted=sorted_rows))


# ----------------------------------------------------------------------
# Shard-local variants (for use inside ``shard_map`` blocks).
#
# The sharded engine (core/sharded.py) stores the (n+1, k) tables row-sharded
# across a 1-D mesh: shard ``s`` owns the contiguous vertex range
# [s*R, (s+1)*R) as a local (R+1, k) block whose last row is that shard's own
# dummy gather row. These variants are trace-level functions called from
# inside a ``shard_map`` body: they take the shard's *global* row ids plus the
# shard's ``row_offset`` (= s*R) and localize on device, so the host routes
# work by owner without rewriting indices per shard. Padded slots use global
# row id -1 (-> the local dummy row).
# ----------------------------------------------------------------------


def shard_local_rows(block_rows: int, rows: jax.Array, row_offset) -> jax.Array:
    """Global row ids -> local block rows; -1 (padding) -> the local dummy."""
    return jnp.where(rows < 0, block_rows - 1, rows - row_offset)


def shard_gather_rows(
    vk_ids: jax.Array,   # (R+1, k) int32 shard-local table block (dummy row last)
    vk_d: jax.Array,     # (R+1, k) float32
    rows: jax.Array,     # (B,) int32 GLOBAL row ids owned by this shard, -1 pad
    row_offset,          # scalar int32: first global row owned by this shard
) -> tuple[jax.Array, jax.Array]:
    """Shard-local ``serve_gather``: one row gather out of this shard's block.

    Padded query slots (-1) read the shard's dummy row and come back as the
    pad sentinel (-1, +inf); the caller drops them when reassembling the
    per-shard result tiles into the original batch order.
    """
    loc = shard_local_rows(vk_ids.shape[0], rows, row_offset)
    return vk_ids[loc], vk_d[loc]


def shard_rows_containing(
    vk_ids: jax.Array,   # (R+1, k) int32 shard-local table block
    obj_ids: jax.Array,  # (D,) int32 deleted object ids (global, replicated)
) -> jax.Array:
    """(R,) bool: which of this shard's rows hold any of ``obj_ids``.

    The per-shard half of ``rows_containing``: each shard scans only its own
    block and the host concatenates the per-shard hit masks back into global
    vertex ids (rows past n in the last shard are all-pad, so never hit).
    """
    return (vk_ids[:-1, :, None] == obj_ids[None, None, :]).any(axis=(1, 2))


def shard_rows_purge_merge(
    vk_ids: jax.Array,    # (R+1, k) int32 shard-local table block
    vk_d: jax.Array,      # (R+1, k) float32
    rows: jax.Array,      # (B,) int32 GLOBAL row ids owned by this shard, -1 pad
    row_offset,           # scalar int32: first global row owned by this shard
    del_ids: jax.Array,   # (D,) int32 deleted object ids (global, replicated)
    cand_ids: jax.Array,  # (B, P) int32 new candidates per row, -1 = padding
    cand_d: jax.Array,    # (B, P) float32
    k: int,
    *,
    use_pallas: bool = False,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shard-local ``rows_purge_merge`` + per-row changed mask.

    Identical math to the global op (gather own rows, drop deleted entries,
    merge candidates, recompact, scatter back into the block) over this
    shard's slice of the row batch; additionally returns the (B,) changed
    mask the repair rounds use to narrow the next round's frontier, so one
    op serves both the flush's purge+merge pass and each Jacobi repair round.
    Object ids in the table are global vertex ids, so the purge membership
    test needs no localization — only the row indices do.
    """
    loc = shard_local_rows(vk_ids.shape[0], rows, row_offset)
    own_ids = vk_ids[loc]
    own_d = vk_d[loc]
    hit = (own_ids[:, :, None] == del_ids[None, None, :]).any(axis=-1)
    pid = jnp.where(hit, -1, own_ids)
    pd = jnp.where(hit, jnp.inf, own_d)
    cat_ids = jnp.concatenate([pid, cand_ids], axis=1)
    cat_d = jnp.concatenate([pd, cand_d.astype(vk_d.dtype)], axis=1)
    cat_d = jnp.where(cat_ids < 0, jnp.inf, cat_d)
    m_ids, m_d = topk_merge(cat_ids, cat_d, k, use_pallas=use_pallas, interpret=interpret)
    changed = jnp.any((m_ids != own_ids) | (m_d != own_d), axis=1)
    return vk_ids.at[loc].set(m_ids), vk_d.at[loc].set(m_d), changed


# ----------------------------------------------------------------------
# Collective-halo building blocks. The sharded engine's all_gather halo
# programs (sharded._device_fns: "expand" / "rhalo" / "fhalo") are thin
# shard_map shells around these trace-level pieces, so the candidate
# construction stays bit-identical to the host-routed halo (same neighbor-
# major column order, same pad-sentinel semantics) and unit-testable
# outside a mesh.
# ----------------------------------------------------------------------


def halo_candidates(
    recv_ids: jax.Array,  # (M, k) int32 received neighbor rows
    recv_d: jax.Array,    # (M, k) float32
    slot: jax.Array,      # (B, t) int32 recv-buffer row per neighbor (M = miss)
    w: jax.Array,         # (B, t) float32 edge weights (pad value irrelevant)
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Received halo rows -> per-receiver (B, t*k) repair candidates.

    The same shift-and-flatten the host-routed repair performs on numpy
    (``_repair_part``): candidate order is neighbor-major / table-column-
    minor, pad entries (id < 0, including every miss slot — ``slot == M``
    reads clamp to the last row and the miss mask forces id -1) carry +inf
    distances. float32 add on device == float32 add on host, so the
    merged tables stay bit-identical across halo modes.
    """
    b, t = slot.shape
    m = recv_ids.shape[0]
    safe = jnp.minimum(slot, m - 1)
    g_ids = jnp.where((slot < m)[..., None], recv_ids[safe], -1)  # (B, t, k)
    g_d = w[..., None] + recv_d[safe]
    cand_ids = g_ids.reshape(b, t * k)
    cand_d = jnp.where(cand_ids < 0, jnp.inf, g_d.reshape(b, t * k))
    return cand_ids, cand_d.astype(jnp.float32)


def halo_fold_min(
    recv: jax.Array,  # (M, B) float32 received gated send rows
    slot: jax.Array,  # (R, t) int32 recv-buffer row per neighbor (M = miss)
    w: jax.Array,     # (R, t) float32 edge weights
) -> jax.Array:
    """Received frontier send rows -> per-receiver (R, B) min-folded cand.

    One neighbor column at a time — (R, B) intermediates, never the
    (R, t, B) tensor — mirroring both ``ops.frontier_relax``'s fori_loop
    form and the host-routed fold in ``_frontier_part``. Miss slots
    (``slot == M``) clamp their gather to the last row and are masked to
    +inf, so no sentinel row is ever materialized; min is fold-order-
    insensitive, so the distance trajectories stay bit-identical. The fold
    starts from column 0 rather than an all-+inf constant, so inside
    ``shard_map`` the loop carry varies over the shard axis like the body's
    result (``slot``/``w`` are per-shard operands).
    """
    m = recv.shape[0]

    def column(j):
        sl = slot[:, j]
        row = w[:, j, None] + recv[jnp.minimum(sl, m - 1)]
        return jnp.where((sl < m)[:, None], row, jnp.inf)

    def body(j, cand):
        return jnp.minimum(cand, column(j))

    return jax.lax.fori_loop(1, slot.shape[1], body, column(0))
