"""Device-resident level-synchronous construction of the KNN-Index (Alg. 3).

The paper's bidirectional construction processes vertices one at a time in
rank order. The only true dependency is through BNS^< (bottom-up sweep) or
BNS^> (top-down sweep), so vertices sharing a DAG level are independent and
are processed as one vectorised device step:

    gather neighbor rows -> shift by edge weight -> dedup top-k merge -> scatter

This module runs the whole sweep as a *fused, device-resident schedule*:

* ``prepare_sweep`` packs every level's ``verts``/``nbr``/``w`` into a small
  number of flat, contiguous device arrays — one set per (T, CHUNK) shape
  bucket — plus two tiny index arrays naming, for each fixed-size row chunk,
  which bucket it lives in and at which row offset. ``nbr``/``w`` are kept
  1-D (rows flattened): a 1-D array has one device layout, so the TPU
  compiler has no whole-bucket relayout to sink into the loop's branches.
  The entire schedule is uploaded **once** per sweep (explicit
  ``jax.device_put``); nothing else crosses the host/device boundary until
  the final result readback.
  Ragged-aware bucketing caps padding waste: each level is sorted by
  degree, widest first, and cut into chunks (two chunk tiers, by level
  size); each chunk's neighbor width T is the power-of-4 bucket (capped at
  the global max) of its own widest row, not of its level's. Reordering a
  level changes no result, since its vertices are mutually independent. The
  plan reports its padded neighbor ``cells`` and ``occupancy`` next to
  ``occupancy_levelwise`` for the seed's per-level power-of-two padding.

* ``run_sweep`` executes one direction as a **single jitted program**: a
  ``lax.fori_loop`` over chunks whose body ``lax.switch``es into one branch
  per shape bucket. Each branch dynamic-slices its chunk out of the flat
  schedule and applies ``ops.sweep_merge_rows`` — on the Pallas path a
  single fused kernel per chunk that gathers neighbor k-lists straight out
  of the live HBM V_k tables into VMEM, shifts and merges (k rounds of dedup
  min-selection), never materialising the (S, T*k + E) candidate tensor; on
  the XLA path the same math with an explicit candidate tensor. The
  branches only read the tables and return the merged rows; the loop body
  scatters them into the V_k carry once per chunk, outside the switch, so
  the write is in place (a write inside a branch copies both whole tables,
  since a conditional's operand cannot alias its result). Distinct
  compilations per build are bounded by the number of shape-bucket
  signatures (one program per sweep), not by the number of levels.

* ``build_knn_index_jax`` chains the two sweeps entirely on device: the
  bottom-up result tables (V_k^<, including the dummy padding row) are handed
  to the top-down sweep as its per-vertex extra candidates (the paper's
  computation sharing, §5.3) with no host sync in between.

Value-equivalence with the sequential reference is exact (tested): a level
only ever reads rows written by strictly earlier levels — the same partial
order the paper's total rank refines.
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bngraph import BNGraph
from repro.core.index import KNNIndex
from repro.core.spans import span
from repro.kernels import ops

_INF = np.float32(np.inf)

# Row-chunk tiers: big levels stream in wide chunks, the long tail of tiny
# levels (often size 1) pads only to the sublane width.
CHUNK_SMALL = 8
CHUNK_LARGE = 64
_LARGE_LEVEL = 48  # levels at least this big use CHUNK_LARGE


def _t_bucket(t_true: int, cap: int) -> int:
    """Power-of-4 neighbor-width bucket (lo 4), capped at the global width."""
    p = 4
    while p < t_true:
        p *= 4
    return min(p, cap)


@dataclasses.dataclass(frozen=True)
class SweepBucket:
    """Flat device-resident schedule arrays for one (T, CHUNK) shape bucket."""

    t_pad: int
    chunk: int
    verts: jax.Array  # (R,) int32, padded rows hold n (the dummy row id)
    nbr: jax.Array    # (R * t_pad,) int32, rows flattened, padded slots hold -1
    w: jax.Array      # (R * t_pad,) float32, rows flattened, padded slots hold +inf


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """One direction of the construction, uploaded once and replayed on device."""

    n: int
    direction: str
    buckets: tuple[SweepBucket, ...]
    chunk_bucket: jax.Array   # (Nc,) int32: bucket index of each chunk
    chunk_off: jax.Array      # (Nc,) int32: first row of each chunk in its bucket
    num_chunks: int
    level_sizes: tuple[int, ...]
    cells: int                  # padded neighbor cells of the flat schedule
    occupancy: float            # true neighbor cells / flat padded cells
    occupancy_levelwise: float  # same metric under per-level pow2 padding (seed)

    @property
    def num_levels(self) -> int:
        return len(self.level_sizes)

    def bucket_signature(self) -> tuple[tuple[int, int], ...]:
        """The (T, CHUNK) shapes that bound distinct compilations."""
        return tuple((b.t_pad, b.chunk) for b in self.buckets)


def _next_pow2(x: int, lo: int = 8) -> int:
    return max(lo, 1 << (max(1, x) - 1).bit_length())


def prepare_sweep(bn: BNGraph, direction: str) -> SweepPlan:
    """Extract one direction's schedule and upload it to the device, once.

    Each level is sorted by degree, widest first (a stable sort), and cut
    into chunks; each chunk takes the width bucket of its own first, widest
    row, so a level's few wide vertices do not pad all its narrow ones. A
    level may be reordered and split across buckets because its vertices
    are mutually independent (``BNGraph.level_members``): no row's inputs
    change, and chunks still run in level order.
    """
    level_of, ids_tab, w_tab = bn.sweep_tables(direction)
    n = bn.n
    deg = (ids_tab >= 0).sum(axis=1)
    cap = _next_pow2(int(deg.max()), lo=4) if n else 4

    levels = bn.level_members(direction)
    acc: dict[tuple[int, int], dict] = {}
    chunk_bucket: list[int] = []
    chunk_off: list[int] = []
    key_index: dict[tuple[int, int], int] = {}
    true_cells = 0
    flat_cells = 0
    levelwise_cells = 0
    for vs in levels:
        t_true = int(deg[vs].max())
        chunk = CHUNK_LARGE if len(vs) >= _LARGE_LEVEL else CHUNK_SMALL
        # widest first, so each chunk's first row sets its width; chunks of
        # one width are adjacent and go to their bucket as one run
        vs = vs[np.argsort(-deg[vs], kind="stable")]
        widths = [_t_bucket(int(d), cap) for d in deg[vs[::chunk]]]
        lo = 0
        for t_pad, run in itertools.groupby(widths):
            hi = lo + chunk * len(list(run))
            part, lo = vs[lo:hi], hi
            rows = -(-len(part) // chunk) * chunk
            key = (t_pad, chunk)
            b = acc.setdefault(key, {"verts": [], "nbr": [], "w": [], "rows": 0})
            verts = np.full(rows, n, np.int32)
            verts[: len(part)] = part
            nbr = np.full((rows, t_pad), -1, np.int32)
            w = np.full((rows, t_pad), _INF, np.float32)
            t_copy = min(t_pad, ids_tab.shape[1])
            nbr[: len(part), :t_copy] = ids_tab[part][:, :t_copy]
            w[: len(part), :t_copy] = w_tab[part][:, :t_copy].astype(np.float32)
            w[nbr < 0] = _INF
            start = b["rows"]
            b["verts"].append(verts)
            b["nbr"].append(nbr)
            b["w"].append(w)
            b["rows"] += rows
            bid = key_index.setdefault(key, len(key_index))
            for c in range(rows // chunk):
                chunk_bucket.append(bid)
                chunk_off.append(start + c * chunk)
            flat_cells += rows * t_pad
        true_cells += int(deg[vs].sum())
        levelwise_cells += _next_pow2(len(vs)) * (_next_pow2(t_true, lo=1) if t_true else 1)

    buckets = []
    for key, _ in sorted(key_index.items(), key=lambda kv: kv[1]):
        b = acc[key]
        buckets.append(
            SweepBucket(
                t_pad=key[0],
                chunk=key[1],
                verts=jax.device_put(np.concatenate(b["verts"])),
                nbr=jax.device_put(np.concatenate(b["nbr"]).reshape(-1)),
                w=jax.device_put(np.concatenate(b["w"]).reshape(-1)),
            )
        )
    return SweepPlan(
        n=n,
        direction=direction,
        buckets=tuple(buckets),
        chunk_bucket=jax.device_put(np.asarray(chunk_bucket, np.int32)),
        chunk_off=jax.device_put(np.asarray(chunk_off, np.int32)),
        num_chunks=len(chunk_bucket),
        level_sizes=tuple(len(vs) for vs in levels),
        cells=flat_cells,
        occupancy=true_cells / max(1, flat_cells),
        occupancy_levelwise=true_cells / max(1, levelwise_cells),
    )


def _sweep_program(
    bucket_data,   # tuple over buckets of (verts, nbr, w) device arrays
    chunk_bucket,
    chunk_off,
    ex_ids,
    ex_d,
    *,
    n: int,
    k: int,
    chunks: tuple[int, ...],   # static CHUNK per bucket (not derivable from shapes)
    use_pallas: bool,
    interpret: bool | None,
):
    """One full sweep as a single XLA program: fori_loop over chunks, switch
    over shape buckets. The V_k carry lives in HBM for the whole loop.

    Each branch returns its chunk's target rows and merged rows, padded to
    the widest CHUNK with row n + 1, which the in-place scatter in the loop
    body drops (see the module docstring for why the write is not in the
    branch).
    """
    vk_ids = jnp.full((n + 1, k), -1, jnp.int32)
    vk_d = jnp.full((n + 1, k), jnp.inf, jnp.float32)
    width = max(chunks)

    def make_branch(bverts, bnbr, bw, chunk):
        def branch(off, vk_ids, vk_d):
            verts = jax.lax.dynamic_slice_in_dim(bverts, off, chunk)
            t = bnbr.shape[0] // bverts.shape[0]
            nbr = jax.lax.dynamic_slice_in_dim(bnbr, off * t, chunk * t).reshape(chunk, t)
            w = jax.lax.dynamic_slice_in_dim(bw, off * t, chunk * t).reshape(chunk, t)
            m_ids, m_d = ops.sweep_merge_rows(
                nbr, verts, w, ex_ids, ex_d, vk_ids, vk_d, k,
                use_pallas=use_pallas, interpret=interpret,
            )
            pad = ((0, width - chunk), (0, 0))
            return (jnp.pad(verts, pad[:1], constant_values=n + 1),
                    jnp.pad(m_ids, pad), jnp.pad(m_d, pad))
        return branch

    branches = [
        make_branch(bv, bn_, bw, chunk)
        for (bv, bn_, bw), chunk in zip(bucket_data, chunks)
    ]

    def body(c, carry):
        vk_ids, vk_d = carry
        verts, m_ids, m_d = jax.lax.switch(
            chunk_bucket[c], branches, chunk_off[c], vk_ids, vk_d
        )
        return (vk_ids.at[verts].set(m_ids, mode="drop"),
                vk_d.at[verts].set(m_d, mode="drop"))

    return jax.lax.fori_loop(0, chunk_bucket.shape[0], body, (vk_ids, vk_d))


_sweep_program_jit = jax.jit(
    _sweep_program,
    static_argnames=("n", "k", "chunks", "use_pallas", "interpret"),
)


def sweep_compile_count() -> int:
    """Distinct XLA programs compiled for sweeps so far in this process.

    Returns -1 when the jit cache introspection hook (a private JAX API) is
    unavailable, so callers can degrade to "unknown" instead of crashing.
    """
    cache_size = getattr(_sweep_program_jit, "_cache_size", None)
    return int(cache_size()) if cache_size is not None else -1


def run_sweep(
    plan: SweepPlan,
    extra_ids: jax.Array,  # (n+1, E) int32 per-vertex extra candidates, on device
    extra_d: jax.Array,    # (n+1, E) float32, on device
    k: int,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Run one direction of the construction. Returns device (n+1, k) tables.

    extra_* supply the non-neighbor candidate terms of Lemmas 5.12/5.21:
    bottom-up, the vertex itself when it is an object; top-down, the vertex's
    own V_k^< row. Both are (n+1)-row device tables (dummy row last) so the
    sweep gathers them on device — zero host traffic inside the loop, which is
    why callers may wrap this in ``jax.transfer_guard("disallow")``.
    """
    if plan.num_chunks == 0:  # empty graph: nothing to sweep
        return (
            jnp.full((plan.n + 1, k), -1, jnp.int32),
            jnp.full((plan.n + 1, k), jnp.inf, jnp.float32),
        )
    bucket_data = tuple((b.verts, b.nbr, b.w) for b in plan.buckets)
    chunks = tuple(b.chunk for b in plan.buckets)
    return _sweep_program_jit(
        bucket_data,
        plan.chunk_bucket,
        plan.chunk_off,
        extra_ids,
        extra_d,
        n=plan.n,
        k=k,
        chunks=chunks,
        use_pallas=use_pallas,
        interpret=interpret,
    )


def object_extras(n: int, objects: np.ndarray, k: int) -> tuple[jax.Array, jax.Array]:
    """Bottom-up extras: each object is a distance-0 candidate for itself.

    Padded to E = k columns so both sweeps share extra shapes (and therefore
    compiled programs) wherever their bucket signatures coincide.
    """
    with span("build.extras"):
        is_obj = np.zeros(n, dtype=bool)
        is_obj[objects] = True
        ex_ids = np.full((n + 1, k), -1, np.int32)
        ex_ids[:n, 0] = np.where(is_obj, np.arange(n, dtype=np.int32), -1)
        ex_d = np.full((n + 1, k), _INF, np.float32)
        ex_d[:n, 0] = np.where(is_obj, np.float32(0), _INF)
        return jax.device_put(ex_ids), jax.device_put(ex_d)


def build_knn_tables_jax(
    bn: BNGraph,
    objects: np.ndarray,
    k: int,
    *,
    use_pallas: bool = True,
    plans: tuple[SweepPlan, SweepPlan] | None = None,
    mesh=None,
    shard_starts=None,
) -> tuple[jax.Array, jax.Array]:
    """Algorithm 3, fused device sweeps: V_k^< up, then V_k down, no host sync.

    The bottom-up tables (dummy row included) feed the top-down sweep directly
    as its extra-candidate tables — the two sweeps share device buffers and
    nothing is read back. Returns the live device (n+1, k) int32/float32
    tables (dummy row last) — the layout ``QueryEngine`` serves from.
    ``plans`` lets a caller that already ran ``prepare_sweep`` (e.g. to report
    schedule stats) reuse the uploaded (up, down) schedules.

    With ``mesh`` (a 1-D ``jax.sharding.Mesh``), the result is re-laid into
    the vertex-sharded layout ``ShardedQueryEngine`` serves from — contiguous
    vertex ranges per device (equal-width, or the ``shard_starts`` boundary
    vector of an uneven ``PartitionPlan``), padded to the max range width,
    one dummy gather row per shard — still without reading the tables back
    to the host (see ``repro.core.sharded.shard_tables``).
    """
    with span("build"):
        ex_ids, ex_d = object_extras(bn.n, objects, k)
        plan_up, plan_down = plans or (prepare_sweep(bn, "up"), prepare_sweep(bn, "down"))

        # ---- bottom-up: V_k^< (Lemma 5.12) ----
        with span("build.sweep", direction="up", cells=plan_up.cells):
            vkl_ids, vkl_d = run_sweep(plan_up, ex_ids, ex_d, k, use_pallas=use_pallas)
        # ---- top-down: V_k (Lemma 5.21), extras = own V_k^< rows, still on device ----
        with span("build.sweep", direction="down", cells=plan_down.cells):
            vk_ids, vk_d = run_sweep(plan_down, vkl_ids, vkl_d, k, use_pallas=use_pallas)
        if mesh is None:
            return vk_ids, vk_d
        from repro.core.sharded import shard_tables

        return shard_tables(vk_ids, vk_d, bn.n, mesh, starts=shard_starts)


def build_knn_index_jax(
    bn: BNGraph, objects: np.ndarray, k: int, *, use_pallas: bool = True
) -> KNNIndex:
    """Device construction + readback into the host ``KNNIndex`` view."""
    vk_ids, vk_d = build_knn_tables_jax(bn, objects, k, use_pallas=use_pallas)
    # np.array (not asarray): the index must own writable host buffers, the
    # update algorithms (core/updates.py) patch rows in place.
    ids = np.array(vk_ids[: bn.n])
    dists = np.where(ids >= 0, np.asarray(vk_d[: bn.n], np.float64), np.inf)
    return KNNIndex(ids=ids, dists=dists, k=k)


def batched_query(vk_ids: jax.Array, vk_d: jax.Array, queries: jax.Array):
    """Device-side batched kNN query: pure row gather (Theorem 4.3, O(k))."""
    return vk_ids[queries], vk_d[queries]
