"""Vertex-sharded multi-device serving engine over row-partitioned tables.

KNN-Index's core asset is a flat, size-bounded (n+1, k) table — embarrassingly
partitionable by vertex, unlike the hierarchical indexes it replaces (PAPER.md
Section 4). ``ShardedQueryEngine`` exploits exactly that: the id/dist tables
are split row-wise across a 1-D ``jax.sharding.Mesh`` into contiguous vertex
ranges, padded to equal shard rows, and the full ``QueryEngine`` surface
(batched queries, progressive prefixes, staged updates with the fused
purge+merge flush and Jacobi repair, save/load) is served on the partitioned
layout. The shared serving core (``repro.core.engine.EngineCore``) supplies
the layout-independent logic, so the two engines cannot drift.

Layout
------
Shard ``s`` of ``S`` owns the contiguous vertex range
``[starts[s], starts[s+1])`` — a ``ShardLayout`` of arbitrary sorted start
boundaries, equal-width (``starts[s] = s * ceil(n/S)``) by default and
traffic-driven uneven under a ``PartitionPlan`` with explicit or ``auto``
ranges. Every shard's local block is padded to the same
``R = max range width`` rows plus one local dummy gather row — a local
``(R+1, k)`` block per device, stored as one global ``(S*(R+1), k)`` array
with ``NamedSharding(mesh, P("shard"))``. Vertex ``v`` lives at global padded
row ``owner(v) * (R+1) + (v - starts[owner(v)])``. Rows past a shard's range
width and the per-shard dummy rows hold the pad sentinel (-1, +inf); they
cost ``S*(R+1) - n`` wasted rows (reported as ``row_padding_overhead`` in
``stats()`` and the exp13 benchmark, so scaling numbers stay honest about the
memory cost — uneven ranges trade extra pad rows on the cold shards for a
smaller max per-shard query batch on the hot one, the exp17 win).

Repartition-on-flush: ``stage_repartition(starts)`` (or
``repartition(starts)``, which also flushes) records pending boundaries;
the next flush re-lays the working tables under them on device — inside the
flush's fallible region, so a crash rolls back to the old boundaries with
the staged queue intact — and the same atomic ``_publish_epoch`` step then
makes the new tables and the new layout visible together. The routing table
versions its layout per epoch, so pinned reads on old epochs keep routing
by the OLD boundaries (bit-identical time travel) while new queries route
by the new ``_starts``.

Execution model
---------------
* Queries: the host routes each query to its owner shard (one stable argsort
  per batch), pads the per-shard batches to a shared pow2 width, and a single
  ``shard_map``-ped gather serves all shards in one device roundtrip; the
  results are scattered back to the caller's batch order inside the same
  jitted program. Bit-identical to the scalar engine's ``query_batch``.
* Flush: the delete scan and the fused ``rows_purge_merge`` pass run
  per-shard via ``shard_map`` (``ops.shard_rows_*`` variants, which localize
  the global row ids against the shard's row offset on device); coalescing
  and the flush orchestration are the shared host logic.
* checkIns frontier: the staged inserts' multi-source tentative-distance
  matrix is row-sharded exactly like the tables, and each pruned-relaxation
  round runs shard-locally — the owner of a frontier vertex gates its
  distance row by its own k-th column (the checkIns test) before the row is
  exchanged, so the pruning bound never leaves its shard and only frontier
  *vertex ids + tentative distances* cross shard boundaries between rounds,
  through the same halo path the repair rounds use.
* Repair rounds: each round, the rows under repair re-merge against their
  bridge neighbors' rows. Neighbor rows may live on other shards, so each
  round first exchanges the (unique) neighbor rows — the boundary-vertex
  exchange of distributed moving-object kNN serving (arXiv 2512.23399) —
  then applies a per-shard merge.
* Halo modes: under ``halo = "collective"`` (the default) those cross-shard
  rows move as capacity-padded ``all_gather`` multicasts inside the
  shard_map programs and the receiver-set expansion runs on device as a
  psum'd presence mask, so per round only the integer index plans go up
  and one changed-row mask comes back; a plan that overflows a set
  ``halo_capacity`` (none by default) falls back for that round. ``halo = "host"`` replays
  the routed-gather baseline (host-fetched unique rows, numpy set
  algebra) — kept as the exp18 measurable baseline and the collective
  path's bit-identity twin.

Epochs and routing
------------------
Ownership and epoch resolution go through ONE indirection, the
``ShardRoutingTable``: vertex -> owner shard (a searchsorted against the
stored shard-start boundaries — never inline ``v // R`` arithmetic at the
call sites) and epoch -> the sharded global buffers, with
``shard_buffers(epoch)`` resolving an individual shard to its device-local
buffer pair. ``flush_updates`` (the shared core) publishes each new epoch
through ``_publish_epoch``, which the sharded engine extends to swap the
routing table's epoch entry in the same atomic step — so a query dispatched
mid-flush routes to every shard's OLD buffers or every shard's NEW buffers,
never a mixture. The engine inherits the core's journal/WAL durability
unchanged (the journal records logical object updates, which are
layout-independent).

Replicated hot shards
---------------------
Skewed traffic (downtown absorbs most queries) makes one owner device the
ceiling no matter how many shards exist. ``set_replication({shard: R})``
expands the shard set into a *slot* set behind the same routing table:
slot ``j < S`` is shard ``j``'s primary, each extra replica appends one
slot on the next free device, and ``route(vs, policy=)`` spreads a hot
shard's queries across its slots (round-robin or least-outstanding).
Queries then run the SAME one-roundtrip shard_map gather on the wider
serving mesh; flushes keep writing only the primary layout, and each
``_publish_epoch`` ``jax.device_put``s the replicated shards' fresh local
blocks onto their replica devices in the same atomic swap — so every
replica serves exactly the primary's epoch snapshot (pinned reads stay
bit-identical mid-flush) and the seven-way oracle equality is untouched. A
replica fault degrades that batch to the primary-only path and counts a
``replica_errors`` stat instead of failing the query.

The engine is drop-in for ``QueryEngine``: same constructor shape, same
staged-update API, same artifact format. Artifacts always store the logical
(n, k) vertex-order tables, so an index saved at N shards loads at M shards
(or unsharded) — reshard-on-load.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from repro.core.bngraph import BNGraph
from repro.core.construct_jax import build_knn_tables_jax
from repro.core.engine import EngineCore, _pow2_pad, load_artifact
from repro.core.errors import EngineConfigError, EpochError, QueryError
from repro.core.index import KNNIndex
from repro.core.partition import PartitionPlan, propose_starts
from repro.kernels import ops


def make_mesh(shards: int | None = None) -> Mesh:
    """A 1-D device mesh over the first ``shards`` local devices.

    ``shards=None`` uses every visible device. On the CPU backend the device
    count is set at process start via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    devs = jax.devices()
    if shards is None:
        shards = len(devs)
    if not 1 <= shards <= len(devs):
        raise ValueError(
            f"shards={shards} but only {len(devs)} devices are visible "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count)"
        )
    return Mesh(np.array(devs[:shards]), ("shard",))


def shard_tables(
    vk_ids: jax.Array, vk_d: jax.Array, n: int, mesh: Mesh, *, starts=None
) -> tuple[jax.Array, jax.Array]:
    """Re-lay single-device (n+1, k) tables into the sharded global layout.

    Stays on device: one gather through the padded-row -> source-row index
    map, then a resharding ``device_put`` — the construction sweeps' result
    feeds the sharded engine with no host readback. ``starts=None`` is the
    equal-width split; an explicit boundary vector lays the tables under
    uneven ranges (every shard still padded to the max range width).
    """
    shards = mesh.devices.size
    layout = (
        ShardLayout.equal(n, shards) if starts is None
        else ShardLayout.from_starts(n, starts)
    )
    src = np.full(shards * layout.block, n, np.int64)  # pads read the dummy row
    v = np.arange(n, dtype=np.int64)
    src[layout.padded_rows(v)] = v
    spec = NamedSharding(mesh, P("shard", None))
    src_dev = jnp.asarray(src)
    return (
        jax.device_put(vk_ids[src_dev], spec),
        jax.device_put(vk_d[src_dev], spec),
    )


class ShardLayout:
    """Immutable row layout of one epoch: boundaries + uniform block size.

    ``starts`` is the sorted shard-start vector (first entry 0); shard ``s``
    owns ``[starts[s], starts[s+1])`` and every shard's local block is
    padded to ``shard_rows = max range width`` rows plus one dummy gather
    row, so one ``(devices, block, k)`` shard_map program serves any
    boundary vector with the same max width. The routing table versions one
    ``ShardLayout`` per published epoch — pinned reads on old epochs keep
    resolving addresses under the boundaries they were published with.
    """

    __slots__ = ("n", "num_shards", "starts", "shard_rows")

    def __init__(self, n: int, starts: np.ndarray, shard_rows: int):
        self.n = int(n)
        self.starts = np.asarray(starts, np.int64)
        self.num_shards = len(self.starts)
        self.shard_rows = int(shard_rows)

    @classmethod
    def equal(cls, n: int, num_shards: int) -> "ShardLayout":
        """The default split: ``starts[s] = s * ceil(n/S)`` (trailing shards
        may be empty when S nearly divides n — seed-identical layout)."""
        rows = -(-int(n) // int(num_shards))  # ceil
        return cls(n, np.arange(num_shards, dtype=np.int64) * rows, rows)

    @classmethod
    def from_starts(cls, n: int, starts) -> "ShardLayout":
        """An explicit (possibly uneven) boundary vector, validated: first
        boundary 0, strictly increasing, every shard's range non-empty."""
        arr = np.asarray(starts, np.int64).reshape(-1)
        if not arr.size or arr[0] != 0:
            raise EngineConfigError(
                f"shard range boundaries must start at vertex 0, got "
                f"{arr.tolist()!r}"
            )
        if arr.size > 1 and not np.all(np.diff(arr) > 0):
            raise EngineConfigError(
                f"shard range boundaries must be strictly increasing, got "
                f"{arr.tolist()!r}"
            )
        if int(arr[-1]) > max(int(n) - 1, 0):
            raise EngineConfigError(
                f"shard range boundary {int(arr[-1])} leaves an empty range "
                f"(vertices end at {int(n) - 1})"
            )
        widths = np.diff(np.append(arr, int(n)))
        return cls(n, arr, int(widths.max()))

    @property
    def block(self) -> int:
        """Local rows per shard including the dummy gather row."""
        return self.shard_rows + 1

    @property
    def widths(self) -> np.ndarray:
        """Owned vertices per shard (0 for an empty trailing shard)."""
        return np.maximum(np.diff(np.append(self.starts, self.n)), 0)

    @property
    def is_equal(self) -> bool:
        rows = -(-self.n // self.num_shards)
        return self.shard_rows == rows and bool(
            np.array_equal(
                self.starts, np.arange(self.num_shards, dtype=np.int64) * rows
            )
        )

    def same_as(self, other: "ShardLayout") -> bool:
        return (
            self is other
            or (
                self.shard_rows == other.shard_rows
                and np.array_equal(self.starts, other.starts)
            )
        )

    def owner(self, vs: np.ndarray) -> np.ndarray:
        """Owner shard per vertex. ``vs`` must lie in [0, n] — n is the
        shared dummy/pad address; anything outside raises ``QueryError``
        instead of silently resolving (a negative id used to underflow
        ``searchsorted - 1`` into a plausible-but-wrong row of the LAST
        shard)."""
        vs = np.asarray(vs, np.int64)
        if vs.size and (int(vs.min()) < 0 or int(vs.max()) > self.n):
            bad = vs[(vs < 0) | (vs > self.n)]
            raise QueryError(
                f"vertex id {int(bad[0])} is outside [0, {self.n}] and "
                f"cannot be routed to a shard"
            )
        return np.minimum(
            np.searchsorted(self.starts, vs, side="right") - 1,
            self.num_shards - 1,
        )

    def padded_rows(
        self, vs: np.ndarray, own: np.ndarray | None = None
    ) -> np.ndarray:
        """Global padded-row address of each vertex: the owner's block base
        plus the vertex's offset from the owner's start boundary."""
        vs = np.asarray(vs, np.int64)
        if own is None:
            own = self.owner(vs)
        return own * self.block + (vs - self.starts[own])

    def serving_rows(
        self, vs: np.ndarray, own: np.ndarray, slots: np.ndarray
    ) -> np.ndarray:
        """Serving-layout padded-row address: the chosen slot's block base
        plus the vertex's offset from its *owner's* start boundary (every
        slot of a shard holds a copy of the same local block)."""
        return slots * self.block + (np.asarray(vs, np.int64) - self.starts[own])


class ShardRoutingTable:
    """The single shard indirection: vertex -> owner shard -> buffers per epoch.

    Two jobs, one table:

    * **Ownership.** ``owner(vs)`` is a ``searchsorted`` against the stored
      shard-start vertex boundaries — arbitrary sorted ``ShardLayout``
      boundaries, equal-width by default and traffic-driven uneven after a
      repartition — and ``padded_rows(vs)`` is the vertex's global
      padded-row address derived from the owner's stored start. Every
      routing decision in the engine reads THIS table instead of inlining
      ``v // R``. The layout is versioned per epoch: ``publish`` records
      the current ``ShardLayout`` alongside the buffers and
      ``layout(epoch)`` resolves it back, so a pinned read on an epoch
      published before a repartition still routes by the OLD boundaries.
    * **Epoch resolution.** ``publish(epoch, buffers)`` records the sharded
      global id/dist arrays serving an epoch, in the same atomic step the
      core's ``EpochStore`` swap runs; ``buffers(epoch)`` resolves a
      retained epoch back to them, and ``shard_buffers(epoch)`` resolves
      one step further — shard id -> (device, local ids buffer, local dists
      buffer) via the arrays' addressable shards. That is the "shard ->
      device buffers per epoch" map: per-shard epoch swap behind one
      indirection.
    * **Replication.** ``set_replication({shard: extras})`` expands the
      shard set into a *slot* set: slot ``j < S`` is shard ``j``'s primary
      and every extra replica appends one more slot (``slot_shard`` maps
      slot -> logical shard). ``owner()`` keeps answering with the logical
      shard; ``route(vs, policy=)`` resolves one step further to the slot
      each query should hit, under ``round_robin`` (a per-shard cursor) or
      ``least_outstanding`` (water-fill over ``outstanding`` + this batch).
      The replica *buffers* for an epoch ride the same ``publish`` call
      (``serving=``) so an epoch's primaries and replicas become visible in
      the same atomic step and pinned reads stay bit-identical on every
      slot.
    """

    def __init__(self, n: int, num_shards: int, starts=None):
        self.n = int(n)
        self.num_shards = int(num_shards)
        if starts is None:
            self._layout = ShardLayout.equal(self.n, self.num_shards)
        else:
            self._layout = ShardLayout.from_starts(self.n, starts)
            if self._layout.num_shards != self.num_shards:
                raise EngineConfigError(
                    f"boundary vector names {self._layout.num_shards} shards, "
                    f"table has {self.num_shards}"
                )
        self._layout_by_epoch: dict[int, ShardLayout] = {}
        self._by_epoch: OrderedDict[int, tuple] = OrderedDict()
        self._serving_by_epoch: dict[int, tuple | None] = {}
        self.replication: dict[int, int] = {}
        self.slot_shard = np.arange(self.num_shards, dtype=np.int64)
        self._slots_of: dict[int, np.ndarray] = {}
        self._rr: dict[int, int] = {}
        self.outstanding = np.zeros(self.num_shards, np.int64)

    # -- ownership (delegated to the CURRENT layout; per-epoch resolution
    # goes through ``layout(epoch)`` so pinned reads survive a repartition) -

    @property
    def current_layout(self) -> ShardLayout:
        return self._layout

    def set_layout(self, layout: ShardLayout) -> None:
        """Swap the CURRENT layout (repartition-on-flush applies the new
        boundaries here, in the same step it swaps the working tables);
        already-published epochs keep the layout they were published with."""
        if layout.n != self.n or layout.num_shards != self.num_shards:
            raise EngineConfigError(
                f"layout is for n={layout.n} x {layout.num_shards} shards, "
                f"table is n={self.n} x {self.num_shards}"
            )
        self._layout = layout

    @property
    def shard_rows(self) -> int:
        return self._layout.shard_rows

    @property
    def starts(self) -> np.ndarray:
        """The current layout's shard-start boundary vector (copy)."""
        return self._layout.starts.copy()

    @property
    def _starts(self) -> np.ndarray:
        # legacy spelling, kept because callers predate ShardLayout
        return self._layout.starts

    def owner(self, vs: np.ndarray) -> np.ndarray:
        """Owner shard per vertex under the CURRENT layout (see
        ``ShardLayout.owner`` for the [0, n] validation contract)."""
        return self._layout.owner(vs)

    def padded_rows(
        self, vs: np.ndarray, own: np.ndarray | None = None
    ) -> np.ndarray:
        """Global padded-row address per vertex under the CURRENT layout."""
        return self._layout.padded_rows(vs, own)

    def serving_rows(
        self, vs: np.ndarray, own: np.ndarray, slots: np.ndarray
    ) -> np.ndarray:
        """Serving-layout padded-row address under the CURRENT layout."""
        return self._layout.serving_rows(vs, own, slots)

    @property
    def num_slots(self) -> int:
        return len(self.slot_shard)

    def set_replication(self, plan: dict[int, int]) -> np.ndarray:
        """Install a shard -> extra-replica-count plan; returns the new
        slot -> logical-shard map. Slot ``j < num_shards`` stays shard
        ``j``'s primary; each extra replica appends one slot, grouped by
        shard in ascending shard order. Resets the routing cursors."""
        clean: dict[int, int] = {}
        for s, r in (plan or {}).items():
            s, r = int(s), int(r)
            if not 0 <= s < self.num_shards:
                raise EngineConfigError(
                    f"replication plan names shard {s}, have {self.num_shards}"
                )
            if r < 0:
                raise EngineConfigError(
                    f"replica count for shard {s} must be >= 0, got {r}"
                )
            if r:
                clean[s] = r
        self.replication = clean
        extras: list[int] = []
        self._slots_of = {}
        for s in sorted(clean):
            slots = [s]
            for _ in range(clean[s]):
                extras.append(s)
                slots.append(self.num_shards + len(extras) - 1)
            self._slots_of[s] = np.asarray(slots, np.int64)
        self.slot_shard = np.concatenate(
            [np.arange(self.num_shards, dtype=np.int64),
             np.asarray(extras, np.int64)]
        )
        self._rr = {}
        self.outstanding = np.zeros(self.num_slots, np.int64)
        return self.slot_shard

    def route(
        self, vs: np.ndarray, policy: str = "round_robin"
    ) -> tuple[np.ndarray, np.ndarray]:
        """Resolve vertices one step past ``owner``: (owner shard, serving
        slot) per vertex. Unreplicated shards route to their primary slot;
        a replicated shard's queries spread across its slot set under
        ``policy`` (every slot serves byte-identical buffers, so the choice
        affects load only, never results)."""
        own = self.owner(vs)
        return own, self.assign_slots(own, policy)

    def assign_slots(self, own: np.ndarray, policy: str = "round_robin") -> np.ndarray:
        if policy not in ("round_robin", "least_outstanding"):
            raise QueryError(
                f"unknown replica routing policy {policy!r} "
                f"(want 'round_robin' or 'least_outstanding')"
            )
        own = np.asarray(own, np.int64)
        slots = own.copy()  # primary slot id == shard id
        for s, sl in self._slots_of.items():
            m = np.flatnonzero(own == s)
            if not len(m):
                continue
            if policy == "round_robin":
                base = self._rr.get(s, 0)
                slots[m] = sl[(base + np.arange(len(m))) % len(sl)]
                self._rr[s] = (base + len(m)) % len(sl)
            else:
                slots[m] = np.repeat(sl, self._water_fill(sl, len(m)))
        return slots

    def _water_fill(self, sl: np.ndarray, count: int) -> np.ndarray:
        """Per-slot assignment counts that level ``outstanding`` + this
        batch across the shard's slots (the least-outstanding policy)."""
        load = self.outstanding[sl]
        lo, hi = int(load.min()), int(load.min()) + count
        while lo < hi:  # max level the batch can fill to
            mid = (lo + hi + 1) // 2
            if int(np.maximum(0, mid - load).sum()) <= count:
                lo = mid
            else:
                hi = mid - 1
        add = np.maximum(0, lo - load)
        rem = count - int(add.sum())
        if rem:
            add[np.argsort(load + add, kind="stable")[:rem]] += 1
        return add

    def record_dispatch(self, slots: np.ndarray) -> None:
        self.outstanding += np.bincount(slots, minlength=self.num_slots)

    def record_complete(self, slots: np.ndarray) -> None:
        self.outstanding -= np.bincount(slots, minlength=self.num_slots)

    # -- epoch -> buffers ----------------------------------------------

    def publish(self, epoch: int, buffers: tuple, keep=None, serving=None) -> None:
        """Swap in an epoch's buffers — and, when a replication plan is
        active, the matching replica (serving-layout) buffers — as one
        step, so a query can never resolve an epoch to another epoch's
        replicas. The CURRENT layout is recorded as the epoch's layout in
        the same step: after a repartition, pinned reads on older epochs
        keep resolving addresses under the boundaries they were published
        with."""
        epoch = int(epoch)
        self._by_epoch[epoch] = buffers
        self._serving_by_epoch[epoch] = serving
        self._layout_by_epoch.setdefault(epoch, self._layout)
        if keep is not None:
            self.trim(keep)

    def trim(self, keep) -> None:
        kept = set(keep)
        for e in [e for e in self._by_epoch if e not in kept]:
            del self._by_epoch[e]
        self._serving_by_epoch = {
            e: s for e, s in self._serving_by_epoch.items() if e in kept
        }
        self._layout_by_epoch = {
            e: lay for e, lay in self._layout_by_epoch.items() if e in kept
        }

    def epochs(self) -> list[int]:
        return list(self._by_epoch)

    def buffers(self, epoch: int) -> tuple:
        epoch = int(epoch)
        if epoch not in self._by_epoch:
            raise EpochError(
                f"epoch {epoch} is not in the routing table "
                f"(have {self.epochs()})"
            )
        return self._by_epoch[epoch]

    def layout(self, epoch: int) -> ShardLayout:
        """The ``ShardLayout`` a retained epoch was published under."""
        epoch = int(epoch)
        if epoch not in self._layout_by_epoch:
            raise EpochError(
                f"epoch {epoch} has no retained layout "
                f"(have {sorted(self._layout_by_epoch)})"
            )
        return self._layout_by_epoch[epoch]

    def shard_buffers(self, epoch: int) -> dict[int, tuple]:
        """shard id -> (device, local ids buffer, local dists buffer)."""
        ids_g, d_g = self.buffers(epoch)
        block = self.layout(epoch).block
        out: dict[int, tuple] = {}
        for si, sd in zip(ids_g.addressable_shards, d_g.addressable_shards):
            s = (si.index[0].start or 0) // block
            out[s] = (si.device, si.data, sd.data)
        return out

    def serving(self, epoch: int):
        """The epoch's replica (serving-layout) buffer pair, or None when
        it was published without an active replication plan."""
        return self._serving_by_epoch.get(int(epoch))

    def replica_buffers(self, epoch: int) -> dict[int, tuple]:
        """slot id -> (logical shard, device, local ids, local dists) for a
        retained epoch's serving layout — the replica-set analogue of
        ``shard_buffers`` (empty when the epoch has no replicas)."""
        serving = self.serving(epoch)
        if serving is None:
            return {}
        s_ids, s_d = serving
        block = self.layout(epoch).block
        out: dict[int, tuple] = {}
        for si, sd in zip(s_ids.addressable_shards, s_d.addressable_shards):
            slot = (si.index[0].start or 0) // block
            out[slot] = (int(self.slot_shard[slot]), si.device, si.data, sd.data)
        return out


_DEVICE_FN_CACHE: dict[tuple, dict] = {}


def _device_fns(mesh: Mesh, block: int, k: int) -> dict:  # replint: disable=REP003(jits are built once per devices/block/k key and memoized in _DEVICE_FN_CACHE)
    """The jitted shard_map programs for one (mesh, block-rows, k) layout.

    Cached at module level keyed by the device ids so every engine on the
    same layout shares one compile cache (the scalar engine gets this for
    free from its module-level jitted ops).
    """
    key = (tuple(d.id for d in mesh.devices.flat), block, k)
    if key in _DEVICE_FN_CACHE:
        return _DEVICE_FN_CACHE[key]

    spec2 = P("shard", None)

    def gather_epi(gi, gd, fidx, ks):
        """The gathers' epilogue: the per-shard tiles back in batch order,
        masked per query and packed (``ops.pack_answer``)."""
        gi = gi.reshape(-1, k)[fidx]
        gd = gd.reshape(-1, k)[fidx]
        return ops.pack_answer(*ops.mask_answer(gi, gd, ks))

    def gather(ids_g, d_g, qglob, fidx, ks):
        def blk(ti, td, q):
            off = jax.lax.axis_index("shard") * block
            gi, gd = ops.shard_gather_rows(ti, td, q[0], off)
            return gi[None], gd[None]

        gi, gd = shard_map(
            blk, mesh=mesh,
            in_specs=(spec2, spec2, spec2),
            out_specs=(P("shard", None, None), P("shard", None, None)),
        )(ids_g, d_g, qglob)
        return gather_epi(gi, gd, fidx, ks)

    def scan(ids_g, del_arr):
        def blk(ti, dl):
            return ops.shard_rows_containing(ti, dl)[None]

        return shard_map(
            blk, mesh=mesh, in_specs=(spec2, P(None)), out_specs=spec2
        )(ids_g, del_arr)

    def purge(ids_g, d_g, rglob, del_arr, ci, cd):
        def blk(ti, td, rq, dl, bci, bcd):
            off = jax.lax.axis_index("shard") * block
            ni, nd, ch = ops.shard_rows_purge_merge(
                ti, td, rq[0], off, dl, bci[0], bcd[0], k,
                use_pallas=False,  # XLA merge form inside shard_map, as in repair
            )
            return ni, nd, ch[None]

        return shard_map(
            blk, mesh=mesh,
            in_specs=(spec2, spec2, spec2, P(None),
                      P("shard", None, None), P("shard", None, None)),
            out_specs=(spec2, spec2, spec2),
        )(ids_g, d_g, rglob, del_arr, ci, cd)

    # -- batched checkIns frontier (shard-local pruned relaxation) ---------
    # The multi-source tentative-distance matrix lives row-sharded exactly
    # like the tables: shard s owns the distance rows of its vertex range.
    # Each round the OWNER computes gated "send" rows (dist gated by its own
    # k-th column — the checkIns test), so the pruning bound never leaves
    # its shard; only frontier vertex ids and those tentative-distance rows
    # cross shard boundaries, through the same routed-gather halo path the
    # repair rounds use.

    def finit(src_grow):
        """(B,) global padded source rows (-1 pad) -> sharded dist matrix."""
        b = src_grow.shape[0]
        dist = jnp.full((mesh.devices.size * block, b), jnp.inf, jnp.float32)
        rows = jnp.where(src_grow >= 0, src_grow, block - 1)
        vals = jnp.where(src_grow >= 0, 0.0, jnp.inf).astype(jnp.float32)
        return dist.at[rows, jnp.arange(b)].set(vals)

    def fsend(d_g, dist_g, qglob, fidx, src_grow):
        """Routed gather of GATED distance rows: each owner applies the
        checkIns gate (dist < own kth, or the row is the column's source)
        before its rows leave the shard."""
        def blk(td, fd, q, sg):
            off = jax.lax.axis_index("shard") * block
            loc = ops.shard_local_rows(block, q[0], off)
            own = fd[loc]
            kth = td[loc][:, -1]
            gate = (own < kth[:, None]) | (q[0][:, None] == sg[None, :])
            return jnp.where(gate, own, jnp.inf)[None]

        out = shard_map(
            blk, mesh=mesh,
            in_specs=(spec2, spec2, spec2, P(None)),
            out_specs=P("shard", None, None),
        )(d_g, dist_g, qglob, src_grow)
        return out.reshape(-1, dist_g.shape[1])[fidx]

    def fmin(dist_g, rglob, vals):
        """Shard-local min-update of the receiver rows + changed mask."""
        def blk(fd, rq, v):
            off = jax.lax.axis_index("shard") * block
            loc = ops.shard_local_rows(block, rq[0], off)
            own = fd[loc]
            new = jnp.minimum(own, v[0])
            ch = jnp.any(new < own, axis=1)
            return fd.at[loc].set(new), ch[None]

        return shard_map(
            blk, mesh=mesh,
            in_specs=(spec2, spec2, P("shard", None, None)),
            out_specs=(spec2, spec2),
        )(dist_g, rglob, vals)

    def faff(d_g, dist_g, qglob, fidx, src_grow):
        """Post-convergence affected test, per owner shard: checkIns against
        the shard's k-th column plus the source rows themselves. Returns the
        (R, B) mask and distance tile in the caller's row order."""
        def blk(td, fd, q, sg):
            off = jax.lax.axis_index("shard") * block
            loc = ops.shard_local_rows(block, q[0], off)
            dd = fd[loc]
            kth = td[loc][:, -1]
            aff = (dd < kth[:, None]) | (q[0][:, None] == sg[None, :])
            return aff[None], dd[None]

        affs, ds = shard_map(
            blk, mesh=mesh,
            in_specs=(spec2, spec2, spec2, P(None)),
            out_specs=(P("shard", None, None), P("shard", None, None)),
        )(d_g, dist_g, qglob, src_grow)
        b = dist_g.shape[1]
        return affs.reshape(-1, b)[fidx], ds.reshape(-1, b)[fidx]

    # -- collective halo (device-resident cross-shard rounds) -----------
    # The host-routed halo above round-trips every cross-shard row through
    # the host (_fetch_rows / _fetch_send + numpy set algebra). These
    # programs keep the whole round on device: the host only computes the
    # *index bookkeeping* (who serves which row — see _halo_plan) and the
    # rows themselves move shard-to-shard as one tiled all_gather per
    # round. serve is (S, Umax): serve[src] holds the global padded row
    # ids shard src must serve (-1 pads) — each unique neighbor of the
    # round's receivers exactly once, at its owner. After the tiled
    # all_gather every shard's (S*Umax, ...) receive buffer holds block
    # src = the rows shard src served, in serve[src] order — which is
    # exactly how _halo_plan numbers the slot matrix (slot S*Umax = miss).
    # A multicast layout, not a per-(src, dst)-pair all_to_all split: a
    # row needed by several receiver shards occupies ONE slot instead of
    # one per pair, which keeps the padded exchange near the halo's true
    # size (per-pair padding measured under 10% utilization on skewed
    # grid boundaries). Candidate construction (ops.halo_candidates /
    # halo_fold_min) and the local merge are the same trace-level math as
    # the routed path, so the tables stay bit-identical across halo modes.
    size = mesh.devices.size * block  # >= n: every vertex id fits

    def expand(nbr_g, aglob):
        """Device receiver-set expansion: each shard scatters the neighbor
        ids of its own routed active rows into a shared presence mask (the
        last slot absorbs -1 pads) and one psum unions the shards — O(E)
        scatter work instead of sorting an all_gather'd id tensor. The
        host's flatnonzero of the mask readback is the ascending unique
        set, exactly ``np.unique`` of the valid neighbor ids."""
        def blk(na, aq):
            off = jax.lax.axis_index("shard") * block
            loc = ops.shard_local_rows(block, aq[0], off)
            ids = na[loc].ravel()
            idx = jnp.where(ids < 0, size, ids)
            mask = jnp.zeros((size + 1,), jnp.int32).at[idx].set(1, mode="drop")
            return jax.lax.psum(mask, "shard")

        return shard_map(
            blk, mesh=mesh, in_specs=(spec2, spec2), out_specs=P(None),
        )(nbr_g, aglob)

    def rhalo(ids_g, d_g, serve, slot, wmat, rglob, del_arr):
        """One collective repair round: owners serve their slice of the
        round's unique neighbor rows, one tiled all_gather moves them,
        purge+merge at the receivers."""
        def blk(ti, td, sv, sl, wm, rg, dl):
            off = jax.lax.axis_index("shard") * block
            loc = ops.shard_local_rows(block, sv[0], off)  # (U,) to serve
            ri = jax.lax.all_gather(ti[loc], "shard", tiled=True)  # (S*U, k)
            rd = jax.lax.all_gather(td[loc], "shard", tiled=True)
            ci, cd = ops.halo_candidates(ri, rd, sl[0], wm[0], k)
            ni, nd, ch = ops.shard_rows_purge_merge(
                ti, td, rg[0], off, dl, ci, cd, k,
                use_pallas=False,  # XLA merge form inside shard_map
            )
            return ni, nd, ch[None]

        return shard_map(
            blk, mesh=mesh,
            in_specs=(spec2, spec2, spec2, P("shard", None, None),
                      P("shard", None, None), spec2, P(None)),
            out_specs=(spec2, spec2, spec2),
        )(ids_g, d_g, serve, slot, wmat, rglob, del_arr)

    def fhalo(nbr_g, d_g, dist_g, serve, slot, wmat, rglob, src_grow):
        """One collective frontier round: owners gate their slice of the
        round's unique tentative-distance rows (the checkIns test — the
        k-th column never leaves its shard), one tiled all_gather moves
        the gated rows, and the receivers min-fold + min-update shard-
        locally. Also psums the NEXT round's receiver-set presence mask
        from the changed receivers' BNS rows, so the round-to-round
        expansion costs no extra program dispatch."""
        def blk(ng, td, fd, sv, sl, wm, rg, sg):
            off = jax.lax.axis_index("shard") * block
            loc = ops.shard_local_rows(block, sv[0], off)  # (U,) to serve
            own = fd[loc]                                  # (U, B)
            kth = td[:, -1][loc]                           # (U,)
            gate = (own < kth[:, None]) | (sv[0][:, None] == sg[None, :])
            recv = jax.lax.all_gather(                     # (S*U, B)
                jnp.where(gate, own, jnp.inf), "shard", tiled=True
            )
            cand = ops.halo_fold_min(recv, sl[0], wm[0])   # (R, B)
            lr = ops.shard_local_rows(block, rg[0], off)
            ownr = fd[lr]
            new = jnp.minimum(ownr, cand)
            ch = jnp.any(new < ownr, axis=1)
            # front-packed adjacency: a degree-t bucket's mask scatter
            # only needs the first t columns of the receivers' rows
            nb = jnp.where(ch[:, None], ng[lr][:, : sl.shape[-1]], -1)
            idx = jnp.where(nb < 0, size, nb).ravel()
            nmask = jnp.zeros((size + 1,), jnp.int32).at[idx].set(1, mode="drop")
            return fd.at[lr].set(new), ch[None], jax.lax.psum(nmask, "shard")

        return shard_map(
            blk, mesh=mesh,
            in_specs=(spec2, spec2, spec2, spec2, P("shard", None, None),
                      P("shard", None, None), spec2, P(None)),
            out_specs=(spec2, spec2, P(None)),
        )(nbr_g, d_g, dist_g, serve, slot, wmat, rglob, src_grow)

    def fhalo_round(nbr_g, d_g, dist_g, src_grow, serves, slots, wmats, rglobs):
        """One fused collective frontier ROUND: every degree bucket's
        gate + all_gather + min-fold + min-update runs inside a single
        program, each bucket over its OWN serve slab (so the exchange
        volume equals the per-bucket fhalo calls it replaces). The
        tentative-distance state threads bucket-to-bucket — bucket b+1
        gates and gathers rows bucket b just improved — which is exactly
        the sequential per-part schedule the scalar and host-routed
        pipelines run, so not only the fixpoint but the whole ROUND
        TRAJECTORY matches them (test_sharded pins round counts
        engine-to-engine). Fusing the round into one dispatch (plus the
        psum'd next-round receiver mask) is what cuts the per-round
        overhead ~3x against per-bucket fhalo calls."""
        def blk(ng, td, fd, sg, svs, sls, wms, rgs):
            off = jax.lax.axis_index("shard") * block
            chs = []
            nmask = jnp.zeros((size + 1,), jnp.int32)
            for sv, sl, wm, rg in zip(svs, sls, wms, rgs):
                loc = ops.shard_local_rows(block, sv[0], off)
                own = fd[loc]                              # (U, B)
                kth = td[:, -1][loc]                       # (U,)
                gate = (own < kth[:, None]) | (sv[0][:, None] == sg[None, :])
                recv = jax.lax.all_gather(                 # (S*U, B)
                    jnp.where(gate, own, jnp.inf), "shard", tiled=True
                )
                cand = ops.halo_fold_min(recv, sl[0], wm[0])
                lr = ops.shard_local_rows(block, rg[0], off)
                ownr = fd[lr]
                new = jnp.minimum(ownr, cand)
                ch = jnp.any(new < ownr, axis=1)
                fd = fd.at[lr].set(new)
                chs.append(ch[None])
                # receivers in a degree-t bucket have <= t live neighbors
                # and the packed adjacency is front-packed, so the mask
                # scatter only needs the first t columns of their rows
                nb = jnp.where(ch[:, None], ng[lr][:, : sl.shape[-1]], -1)
                idx = jnp.where(nb < 0, size, nb).ravel()
                nmask = nmask.at[idx].set(1, mode="drop")
            return fd, tuple(chs), jax.lax.psum(nmask, "shard")

        nb_ = len(slots)
        return shard_map(
            blk, mesh=mesh,
            in_specs=(spec2, spec2, spec2, P(None), [spec2] * nb_,
                      [P("shard", None, None)] * nb_,
                      [P("shard", None, None)] * nb_, [spec2] * nb_),
            out_specs=(spec2, (spec2,) * nb_, P(None)),
        )(nbr_g, d_g, dist_g, src_grow, serves, slots, wmats, rglobs)

    # -- replica fan-out gather, two-phase ------------------------------
    # The serving mesh is wider than the shard mesh (primaries + replica
    # slots), so the one-jit gather's epilogue — reshape + [fidx] on a
    # replicated tile — would repeat its work per device. Instead the
    # shard_map tile stays sharded, one explicit d2d device_put
    # consolidates it, and a single-device jit restores the caller's batch
    # order: the epilogue is paid once, not once per slot. Replication
    # balances the per-slot batches, so the consolidated tile is small.

    def gather_tile(ids_g, d_g, qglob):
        def blk(ti, td, q):
            off = jax.lax.axis_index("shard") * block
            gi, gd = ops.shard_gather_rows(ti, td, q[0], off)
            return gi[None], gd[None]

        return shard_map(
            blk, mesh=mesh,
            in_specs=(spec2, spec2, spec2),
            out_specs=(P("shard", None, None), P("shard", None, None)),
        )(ids_g, d_g, qglob)

    _DEVICE_FN_CACHE[key] = {
        "gather": jax.jit(gather),
        "gather_tile": jax.jit(gather_tile),
        "gather_epi": jax.jit(gather_epi),
        "scan": jax.jit(scan),
        "purge": jax.jit(purge),
        "kth": jax.jit(lambda d_g: d_g[:, -1]),
        "finit": jax.jit(finit, out_shardings=NamedSharding(mesh, P("shard", None))),
        "fsend": jax.jit(fsend),
        "fmin": jax.jit(fmin),
        "faff": jax.jit(faff),
        "expand": jax.jit(expand),
        "rhalo": jax.jit(rhalo),
        "fhalo": jax.jit(fhalo),
        "fhalo_round": jax.jit(fhalo_round, static_argnames=()),
    }
    return _DEVICE_FN_CACHE[key]


class ShardedQueryEngine(EngineCore):
    """Row-sharded multi-device drop-in for ``QueryEngine`` (see module doc)."""

    def __init__(
        self,
        ids,
        dists,
        k: int,
        objects,
        *,
        bn: BNGraph | None = None,
        shards: int | None = None,
        mesh: Mesh | None = None,
        use_pallas: bool = False,
        plan: PartitionPlan | None = None,
    ):
        plan = PartitionPlan.resolve(plan, shards=shards)
        self.mesh = mesh if mesh is not None else make_mesh(plan.shards)
        self.num_shards = int(self.mesh.devices.size)
        self.n, ids, dists = EngineCore.normalize_tables(ids, dists, k, bn)
        starts = self._plan_starts(plan, objects=objects)
        self._init_layout(int(k), starts=starts)
        self._ids_g, self._d_g = shard_tables(
            ids, dists, self.n, self.mesh, starts=starts
        )
        super().__init__(k, objects, bn=bn, use_pallas=use_pallas)
        self._apply_plan_replication(plan)

    def _plan_starts(self, plan: PartitionPlan, *, objects=None, saved=None):
        """Resolve a plan's ``ranges`` field to a boundary vector (or None
        for equal-width). Explicit ranges are used as given; ``auto`` asks
        the splitter for object-density-balanced boundaries (the build-time
        histogram; serve.py feeds the query histogram at runtime); None
        reuses a loader's ``saved`` boundaries when they still fit the
        shard count, else falls back to equal-width."""
        if isinstance(plan.ranges, tuple):
            starts = np.asarray(plan.ranges, np.int64)
            if len(starts) != self.num_shards:
                raise EngineConfigError(
                    f"plan names {len(starts)} range boundaries but the mesh "
                    f"has {self.num_shards} shards"
                )
            return starts
        if (
            saved is not None
            and len(saved) == self.num_shards
            and not ShardLayout.from_starts(self.n, saved).is_equal
        ):
            return np.asarray(saved, np.int64)
        if plan.ranges == "auto" and objects is not None and len(objects):
            if self.num_shards == 1:
                return None
            w = np.full(self.n, 1e-3)
            w[np.asarray(objects, np.int64)] += 1.0
            return propose_starts(w, self.num_shards)
        return None

    def _apply_plan_replication(self, plan: PartitionPlan) -> None:
        rep = plan.replication_dict()
        if rep:
            self.set_replication(rep, policy=plan.policy)
        elif plan.policy != self.replica_policy:
            self.replica_policy = plan.policy

    def _init_layout(self, k: int, starts=None) -> None:
        """Derive the host side of the partitioned layout (the routing
        table, shard_rows, the vertex -> global-padded-row map) and bind
        the shared device programs. Requires ``self.mesh``,
        ``self.num_shards`` and ``self.n`` to be set; the single source of
        the layout arithmetic for every constructor."""
        if self.num_shards > max(self.n, 1):
            raise EngineConfigError(
                f"cannot split n={self.n} rows into {self.num_shards} shards"
            )
        self.routing = ShardRoutingTable(self.n, self.num_shards, starts=starts)
        self.shard_rows = self.routing.shard_rows
        self._g_of_v = self.routing.padded_rows(np.arange(self.n, dtype=np.int64))
        self._make_device_fns(k)
        # repartition-on-flush state: boundaries staged for the next flush
        self._pending_layout: ShardLayout | None = None
        self._partition_stats = {"repartitions": 0}
        # collective halo state: the sharded BNS adjacency in the CURRENT
        # row layout (built lazily, dropped on every layout change so halo
        # row maps can never outlive their boundaries), plus the per-round
        # all_gather capacity cap — a round whose padded per-owner served-
        # row count exceeds it falls back to the routed host halo. None (the
        # default) never falls back: an owner serves at most the rows it
        # holds, so the receive buffer is at most S x block rows, the size
        # of the frontier's own dist matrix
        self._nbr_glob_g: jax.Array | None = None
        self.halo_capacity: int | None = None
        self._halo_stats = {
            "halo_rounds_collective": 0,
            "halo_fallbacks": 0,
        }
        # fused receiver-set expansion: collective frontier rounds psum
        # the next round's presence mask as a side output; None = not
        # armed (first round / host parts seen — expand runs standalone)
        self._fmask: list | None = None
        self._fmask_ok = True
        # replica serving state (inactive until set_replication installs a
        # plan): the serving mesh spans primaries + extra replica devices
        self.replica_policy = "round_robin"
        self.replica_fault_hook = None  # chaos seam: fn(engine) or None
        self._serving_mesh: Mesh | None = None
        self._serving_fns: dict | None = None
        self._cons_bufs: dict = {}  # pooled host staging buffers (see _consolidate)
        self._rstats = {
            "replica_queries": 0,
            "replica_batches": 0,
            "replica_errors": 0,
            "balanced_batches": 0,  # unreplicated two-phase gathers
        }

    # ------------------------------------------------------------------
    # construction / conversion
    # ------------------------------------------------------------------

    @classmethod
    def build(
        cls,
        bn: BNGraph,
        objects: np.ndarray,
        k: int,
        *,
        shards: int | None = None,
        use_pallas: bool = False,
        plan: PartitionPlan | None = None,
    ) -> "ShardedQueryEngine":
        """Construct on device (Algorithm 3 fused sweeps) and serve sharded:
        the sweep result tables are re-laid into the partitioned layout with
        no host readback (``build_knn_tables_jax(..., mesh=)``). ``plan``
        is the unified ``PartitionPlan`` surface (``shards=`` is the legacy
        shim); ``ranges="auto"`` splits by object density at build time."""
        plan = PartitionPlan.resolve(plan, shards=shards)
        eng = cls.__new__(cls)  # skip __init__: the tables are born sharded
        eng.mesh = make_mesh(plan.shards)
        eng.num_shards = int(eng.mesh.devices.size)
        eng.n = bn.n
        starts = eng._plan_starts(plan, objects=objects)
        eng._init_layout(int(k), starts=starts)
        eng._ids_g, eng._d_g = build_knn_tables_jax(
            bn, objects, k, use_pallas=use_pallas, mesh=eng.mesh,
            shard_starts=starts,
        )
        EngineCore.__init__(eng, k, objects, bn=bn, use_pallas=use_pallas)
        eng._apply_plan_replication(plan)
        return eng

    @classmethod
    def from_index(
        cls,
        index: KNNIndex,
        objects,
        *,
        bn: BNGraph | None = None,
        shards: int | None = None,
        use_pallas: bool = False,
        plan: PartitionPlan | None = None,
    ) -> "ShardedQueryEngine":
        """Upload a host ``KNNIndex`` (e.g. an oracle-built one), sharded."""
        dists = np.where(index.ids >= 0, index.dists, np.inf).astype(np.float32)
        return cls(
            index.ids, dists, index.k, objects,
            bn=bn, shards=shards, use_pallas=use_pallas, plan=plan,
        )

    @classmethod
    def load(
        cls,
        path,
        *,
        bn: BNGraph | None = None,
        shards: int | None = None,
        use_pallas: bool = False,
        journal=None,
        replication: dict[int, int] | None = None,
        plan: PartitionPlan | None = None,
    ) -> "ShardedQueryEngine":
        """Load a ``save`` artifact into a sharded engine — reshard-on-load.

        The artifact stores the logical vertex-order tables, so the writer's
        shard count does not constrain the reader: ``shards=None`` re-shards
        across the saved count capped at the visible device count (an
        artifact saved at 8 shards still loads on a 2-device host), and an
        explicit ``shards=M`` overrides it entirely.

        A saved replication plan (shard -> extra replicas) is re-applied
        when it still describes this engine — same shard count as the
        writer and enough free devices to seat every replica — and dropped
        otherwise (the plan is keyed by shard id, so a reshard invalidates
        it; replicas are a serving concern, not an artifact one). Pass
        ``replication={...}`` to install a different plan, or ``{}`` to
        force-drop the saved one.

        ``journal`` attaches + replays a write-ahead journal exactly as in
        ``QueryEngine.load`` — the journal records logical object updates,
        so a journal written by a scalar (or differently-sharded) engine
        replays here and recovers the same logical tables.

        Saved uneven range boundaries (``meta["starts"]``) are re-applied
        when the reader keeps the writer's shard count and the plan does
        not name explicit ranges; a reshard drops them (boundaries are
        keyed by shard count, and the loaded tables re-lay either way).
        """
        plan = PartitionPlan.resolve(plan, shards=shards, replication=replication)
        ids, dists, k, objects, meta = load_artifact(path)
        shards = plan.shards
        if shards is None:
            shards = min(int(meta.get("shards", 1)), len(jax.devices()))
        ranges = plan.ranges
        if not isinstance(ranges, tuple):
            saved_starts = meta.get("starts")
            if saved_starts is not None and len(saved_starts) == shards:
                ranges = tuple(int(s) for s in saved_starts)
        eng = cls(
            ids, dists.astype(np.float32), k, objects,
            bn=bn, use_pallas=use_pallas,
            plan=dataclasses.replace(
                plan, shards=shards, ranges=ranges, replication=None
            ),
        )
        rep = plan.replication_dict()
        if rep is None and not plan.auto_replicas():
            saved = {
                int(s): int(r)
                for s, r in (meta.get("replication") or {}).items()
            }
            extras = sum(saved.values())
            if (
                saved
                and shards == int(meta.get("shards", 1))
                and shards + extras <= len(jax.devices())
            ):
                rep = saved
        if rep:
            eng.set_replication(rep, policy=plan.policy)
        if journal is not None:
            eng.attach_journal(journal)
        return eng

    def to_index(self) -> KNNIndex:
        """Read the sharded tables back into the host ``KNNIndex`` view."""
        ids = np.asarray(self._ids_g)[self._g_of_v]
        d = np.asarray(self._d_g)[self._g_of_v]
        dists = np.where(ids >= 0, d.astype(np.float64), np.inf)
        return KNNIndex(ids=ids, dists=dists, k=self.k)

    @property
    def tables(self) -> tuple[jax.Array, jax.Array]:
        """The live sharded (S*(R+1), k) global id/dist tables."""
        return self._ids_g, self._d_g

    # ------------------------------------------------------------------
    # epoch hooks (per-shard swap behind the routing table)
    # ------------------------------------------------------------------

    def _table_snapshot(self) -> tuple[jax.Array, jax.Array]:
        # sharded global arrays are immutable too (the flush reassigns the
        # working refs), so a snapshot is the pair of references — each one
        # pinning its per-device buffers for the epoch's lifetime
        return self._ids_g, self._d_g

    def _restore_tables(self, snap: tuple) -> None:
        self._ids_g, self._d_g = snap
        # a failed flush may have died mid-repartition, AFTER the working
        # layout swapped: re-sync to the published epoch's layout (the
        # current epoch is untouched by a failed flush). The pending
        # boundaries stay staged, so a retry re-applies the repartition.
        lay = self.routing.layout(self.epoch)
        if not lay.same_as(self.routing.current_layout):
            self._apply_layout(lay)

    def _publish_epoch(self, epoch: int) -> None:
        # one atomic step: the EpochStore swap, the routing table's
        # epoch -> buffers entry, the epoch's layout (boundaries) AND the
        # epoch's replica buffers (when a plan is active) move together, so
        # the indirection can never resolve an epoch to another epoch's
        # shards or boundaries — and every replica of a shard serves
        # exactly the epoch the primary serves
        super()._publish_epoch(epoch)
        buffers = self._epochs.snapshot(epoch)
        serving = (
            self._build_serving(*buffers) if self._serving_mesh is not None else None
        )
        self.routing.publish(
            epoch, buffers, keep=self._epochs.epochs(), serving=serving
        )
        self._pending_layout = None  # a staged repartition is now live

    def _trim_epoch_stats(self) -> None:
        super()._trim_epoch_stats()
        self.routing.trim(self._epochs.epochs())

    def _table_bytes(self) -> int:
        # the sharded layout pays for the padded rows, count them honestly
        return self.num_shards * (self.shard_rows + 1) * self.k * 8

    # ------------------------------------------------------------------
    # repartition-on-flush: stage new boundaries, apply them inside the
    # next flush's fallible region (the _prepare_publish hook), publish
    # tables + layout in the same atomic _publish_epoch step
    # ------------------------------------------------------------------

    def stage_repartition(self, starts) -> None:
        """Stage new shard-range boundaries for the next flush.

        ``starts`` is a sorted boundary vector (one entry per shard, first
        0, strictly increasing — e.g. from ``propose_starts`` over a query
        histogram). Nothing changes until ``flush_updates``: the flush
        re-lays the working tables under the new boundaries on device and
        publishes tables + layout in one atomic epoch step, so pinned
        reads on older epochs stay bit-identical under their OLD
        boundaries. A flush that fails (or is killed) rolls back to the
        old boundaries with the repartition still staged for the retry.
        """
        lay = ShardLayout.from_starts(self.n, starts)
        if lay.num_shards != self.num_shards:
            raise EngineConfigError(
                f"boundary vector names {lay.num_shards} shards, engine "
                f"has {self.num_shards}"
            )
        self._pending_layout = lay

    def repartition(self, starts) -> dict:
        """``stage_repartition`` + ``flush_updates`` in one call; returns
        the flush stats (any staged object updates ride the same epoch)."""
        self.stage_repartition(starts)
        return self.flush_updates()

    @property
    def pending_repartition(self) -> np.ndarray | None:
        """The staged boundary vector, or None."""
        lay = self._pending_layout
        return None if lay is None else lay.starts.copy()

    def _prepare_publish(self) -> None:
        """Re-lay the working tables under the staged boundaries, on
        device: one gather through the new-layout -> old-layout row map
        (the same move ``shard_tables`` does at build) plus a resharding
        ``device_put``, then swap the host-side layout. Runs inside the
        flush's fallible region — the chaos seam fires ``pre-repartition``
        and ``mid-repartition`` checkpoints, and any failure rolls back
        through ``_restore_tables`` to the old boundaries."""
        lay = self._pending_layout
        if lay is None:
            return
        old = self.routing.current_layout
        if old.same_as(lay):
            self._pending_layout = None
            return
        self._checkpoint("pre-repartition")
        # old-layout source row per new-layout row; pad rows read the old
        # address of the shared dummy vertex n (a pad sentinel row)
        pad_row = int(old.padded_rows(np.array([self.n], np.int64))[0])
        src = np.full(self.num_shards * lay.block, pad_row, np.int64)
        v = np.arange(self.n, dtype=np.int64)
        src[lay.padded_rows(v)] = old.padded_rows(v)
        spec = NamedSharding(self.mesh, P("shard", None))
        src_dev = self._put_repl(src)
        new_ids = jax.device_put(jnp.take(self._ids_g, src_dev, axis=0), spec)
        new_d = jax.device_put(jnp.take(self._d_g, src_dev, axis=0), spec)
        self._checkpoint("mid-repartition")
        self._ids_g, self._d_g = new_ids, new_d
        self._apply_layout(lay)
        self._partition_stats["repartitions"] += 1

    def _apply_layout(self, lay: ShardLayout) -> None:
        """Swap the CURRENT layout: routing boundaries, the vertex ->
        padded-row map, and the device programs for the (possibly new)
        block size. Published epochs keep their own layouts."""
        self.routing.set_layout(lay)
        self.shard_rows = lay.shard_rows
        self._g_of_v = lay.padded_rows(np.arange(self.n, dtype=np.int64))
        self._make_device_fns(self.k)
        # the sharded BNS adjacency is laid out by vertex -> padded-row,
        # so a boundary change invalidates it (rebuilt lazily on the next
        # collective round — under the NEW layout's row map)
        self._nbr_glob_g = None
        if self._serving_mesh is not None:
            self._serving_fns = _device_fns(self._serving_mesh, lay.block, self.k)

    def partition_plan(self) -> PartitionPlan:
        """The active layout as a ``PartitionPlan`` (stats/introspection)."""
        lay = self.routing.current_layout
        rep = tuple(sorted(self.routing.replication.items()))
        return PartitionPlan(
            shards=self.num_shards,
            ranges=None if lay.is_equal else tuple(int(s) for s in lay.starts),
            replication=rep or None,
            policy=self.replica_policy,
        )

    # ------------------------------------------------------------------
    # replicated hot shards: a shard -> extra-replica plan expands the
    # shard set into a slot set served on a wider mesh (primaries on the
    # engine's own devices, replicas on the next free ones). Flushes keep
    # writing only the primary layout; each _publish_epoch re-copies the
    # replicated shards' fresh local blocks onto their replica devices, so
    # replicas are read-only copies refreshed at the swap.
    # ------------------------------------------------------------------

    def set_replication(
        self, plan: dict[int, int] | None, *, policy: str | None = None
    ) -> None:
        """Install (or with ``None``/``{}`` drop) a shard -> extra-replica
        plan and immediately re-publish every retained epoch's replica
        buffers, so pinned reads on any retained epoch can be served from
        replicas too. Raises ``EngineConfigError`` when the visible device
        pool cannot seat ``num_shards + total extras`` slots."""
        if policy is not None:
            if policy not in ("round_robin", "least_outstanding"):
                raise EngineConfigError(
                    f"unknown replica routing policy {policy!r}"
                )
            self.replica_policy = policy
        plan = {int(s): int(r) for s, r in (plan or {}).items() if int(r) > 0}
        if not plan:
            self.routing.set_replication({})
            self._serving_mesh = None
            self._serving_fns = None
            for e in self.routing.epochs():
                self.routing.publish(e, self.routing.buffers(e), serving=None)
            return
        slot_shard = self.routing.set_replication(plan)
        primaries = list(self.mesh.devices.flat)
        extra_pool = [d for d in jax.devices() if d not in primaries]
        extras_needed = len(slot_shard) - self.num_shards
        if extras_needed > len(extra_pool):
            self.routing.set_replication({})
            raise EngineConfigError(
                f"replication plan needs {extras_needed} extra devices beyond "
                f"the {self.num_shards} shard primaries, but only "
                f"{len(extra_pool)} are free (set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count)"
            )
        self._serving_mesh = Mesh(
            np.array(primaries + extra_pool[:extras_needed]), ("shard",)
        )
        self._serving_fns = _device_fns(self._serving_mesh, self.shard_rows + 1, self.k)
        for e in self.routing.epochs():
            buffers = self.routing.buffers(e)
            self.routing.publish(e, buffers, serving=self._build_serving(*buffers))

    def _build_serving(self, ids_g, d_g) -> tuple[jax.Array, jax.Array]:
        """Expand primary-layout global tables into the serving (slot)
        layout: each slot's device gets its logical shard's local (R+1, k)
        block — a no-op reuse for primary slots (the buffer already lives
        there) and one explicit ``jax.device_put`` per replica slot. The
        block size is read off the buffers themselves, so re-publishing an
        epoch that predates a repartition expands under ITS layout."""
        mesh = self._serving_mesh
        block = ids_g.shape[0] // self.num_shards
        slot_shard = self.routing.slot_shard
        spec = NamedSharding(mesh, P("shard", None))
        devs = list(mesh.devices.flat)
        out = []
        for arr in (ids_g, d_g):
            local = {}
            for sh in arr.addressable_shards:
                local[(sh.index[0].start or 0) // block] = sh.data
            bufs = [
                jax.device_put(local[int(s)], d) for s, d in zip(slot_shard, devs)
            ]
            out.append(
                jax.make_array_from_single_device_arrays(
                    (len(slot_shard) * block, arr.shape[1]), spec, bufs
                )
            )
        return tuple(out)

    # ------------------------------------------------------------------
    # device programs (cached per (device set, block, k) at module level —
    # engines built on the same mesh/layout share one jit compile cache, so
    # rebuilding an engine never recompiles; jit then caches per shape)
    # ------------------------------------------------------------------

    def _make_device_fns(self, k: int) -> None:
        fns = _device_fns(self.mesh, self.shard_rows + 1, k)
        self._gather_fn = fns["gather"]
        self._scan_fn = fns["scan"]
        self._purge_fn = fns["purge"]
        self._kth_fn = fns["kth"]
        self._finit_fn = fns["finit"]
        self._fsend_fn = fns["fsend"]
        self._fmin_fn = fns["fmin"]
        self._faff_fn = fns["faff"]
        self._expand_fn = fns["expand"]
        self._rhalo_fn = fns["rhalo"]
        self._fhalo_fn = fns["fhalo"]
        self._fhalo_round_fn = fns["fhalo_round"]

    # ------------------------------------------------------------------
    # explicit host -> mesh uploads. Every operand of the shard_map
    # programs is placed with the exact NamedSharding its in_spec expects,
    # so jit never inserts an implicit device-to-device reshard — which is
    # what the sanitizer's transfer guard (repro.analysis.sanitize) would
    # reject on the query/flush paths.
    # ------------------------------------------------------------------

    def _put_shard(self, x) -> jax.Array:
        """Upload splitting the leading axis across shards."""
        spec = P("shard", *([None] * (np.ndim(x) - 1)))
        return self._upload(x, NamedSharding(self.mesh, spec))

    def _put_repl(self, x) -> jax.Array:
        """Upload (or re-place) fully replicated across the mesh."""
        return self._upload(x, NamedSharding(self.mesh, P()))

    # ------------------------------------------------------------------
    # host-side routing (queries batched per shard, one roundtrip)
    # ------------------------------------------------------------------

    def _group_by_owner(
        self, owner: np.ndarray, groups: int | None = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """Stable group-by-owner used by query routing (``groups`` = shard
        count, or slot count on the replicated serving path) and the
        flush's row batching: (input order permutation, owner per sorted
        entry, slot within the owner's group, max group size)."""
        if groups is None:
            groups = self.num_shards
        order = np.argsort(owner, kind="stable")
        counts = np.bincount(owner, minlength=groups)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        o_sorted = owner[order]
        slot = np.arange(len(owner)) - starts[o_sorted]
        return order, o_sorted, slot, int(counts.max()) if len(owner) else 1

    def _route(
        self, vs: np.ndarray, layout: ShardLayout | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Group vertices by owner shard: ((S, Bmax) global padded rows with
        -1 padding, (B,) flat result positions restoring the input order).
        ``layout`` defaults to the CURRENT boundaries; a pinned read on an
        epoch published before a repartition passes that epoch's layout.

        Out-of-range ids get the scalar gather's jnp indexing semantics, so
        the bit-identical contract holds even for garbage queries: negative
        ids wrap once from the end of the (n+1)-row table (so -1 is the
        dummy row -> pad sentinel), everything still outside clamps into
        [0, n], and ids >= n read a dummy row -> pad sentinel (-1, +inf).
        """
        if layout is None:
            layout = self.routing.current_layout
        vs = np.asarray(vs, np.int64)
        vs = np.where(vs < 0, vs + self.n + 1, vs)  # jnp negative wraparound
        vs = np.clip(vs, 0, self.n)                 # then the XLA gather clamp
        oob = vs >= self.n
        owner = layout.owner(vs)
        order, o_sorted, slot, bmax = self._group_by_owner(owner)
        bmax = _pow2_pad(bmax, lo=8)
        qglob = np.full((self.num_shards, bmax), -1, np.int32)
        qglob[o_sorted, slot] = np.where(
            oob[order], -1, layout.padded_rows(vs[order], o_sorted)
        )
        fidx = np.empty(len(vs), dtype=np.int64)
        fidx[order] = o_sorted * bmax + slot
        return qglob, fidx

    def _route_slots(
        self, vs: np.ndarray, layout: ShardLayout | None = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Replicated-path analogue of ``_route``: group vertices by
        serving *slot* (shard or replica, per the routing policy) into the
        ((V, Bmax) serving-layout padded rows, (B,) flat result positions,
        (B,) chosen slots) triple. Same wraparound/clamp semantics as
        ``_route``, and every slot serves byte-identical buffers — so the
        results stay bit-identical to the unreplicated gather no matter
        which replica each query lands on."""
        if layout is None:
            layout = self.routing.current_layout
        vs = np.asarray(vs, np.int64)
        vs = np.where(vs < 0, vs + self.n + 1, vs)  # jnp negative wraparound
        vs = np.clip(vs, 0, self.n)                 # then the XLA gather clamp
        oob = vs >= self.n
        own = layout.owner(vs)
        slots = self.routing.assign_slots(own, self.replica_policy)
        nslots = self.routing.num_slots
        order, s_sorted, pos, bmax = self._group_by_owner(slots, groups=nslots)
        bmax = _pow2_pad(bmax, lo=8)
        rows = layout.serving_rows(vs, own, slots)
        qglob = np.full((nslots, bmax), -1, np.int32)
        qglob[s_sorted, pos] = np.where(oob[order], -1, rows[order])
        fidx = np.empty(len(vs), dtype=np.int64)
        fidx[order] = s_sorted * bmax + pos
        return qglob, fidx, slots

    def _consolidate(self, x: jax.Array) -> np.ndarray:
        """Sharded tile -> pooled host buffer (one memcpy per shard).

        ``np.asarray`` on a multi-MB tile allocates a fresh mmap'd buffer
        every call, and the page-fault churn is bimodal across processes —
        enough to flap the exp16 floor. Copying through a reused staging
        buffer (one copy of each shard, two rotating buffers
        per shape so the bytes a just-dispatched ``device_put`` reads are
        never overwritten by the next batch) keeps the copy on the warm
        memcpy path. Each shard is read with ``np.asarray``, which works on
        any backend's buffers (numpy's DLPack import takes CPU buffers
        only)."""
        key = (x.shape, str(x.dtype))
        pair = self._cons_bufs.get(key)
        if pair is None:
            pair = self._cons_bufs.setdefault(
                key, [np.empty(x.shape, x.dtype), np.empty(x.shape, x.dtype), 0]
            )
        buf = pair[pair[2]]
        pair[2] ^= 1
        for sh in x.addressable_shards:
            np.copyto(buf[sh.index], np.asarray(sh.data))
        return buf

    def _gather_replicated(
        self, us: np.ndarray, ks: jax.Array, serving: tuple,
        layout: ShardLayout | None = None,
    ):
        """Two-phase gather over the serving (slot) layout: the shard_map
        tile program on the wider replica mesh (hot shard's queries fanned
        out across its slot set), then one explicit consolidation onto the
        lead device where the batch-order epilogue runs exactly once —
        rather than replicated per slot, which would grow the epilogue cost
        with every replica added."""
        if self.replica_fault_hook is not None:
            self.replica_fault_hook(self)  # chaos seam: simulated replica loss
        if layout is None:
            layout = self.routing.current_layout
        s_ids, s_d = serving
        qglob, fidx, slots = self._route_slots(us, layout)
        mesh = self._serving_mesh
        fns = (
            self._serving_fns
            if layout.same_as(self.routing.current_layout)
            else _device_fns(mesh, layout.block, self.k)
        )
        lead = SingleDeviceSharding(mesh.devices.flat[0])
        self.routing.record_dispatch(slots)
        try:
            gi, gd = fns["gather_tile"](
                s_ids, s_d,
                jax.device_put(qglob, NamedSharding(mesh, P("shard", None))),
            )
            # consolidate through pooled host staging buffers: an explicit
            # readback + upload both take the plain memcpy path, where the
            # direct sharded->single-device device_put of a multi-MB tile
            # lands on a slow generic copy often enough to flap the exp16
            # floor
            out = fns["gather_epi"](
                jax.device_put(self._consolidate(gi), lead),
                jax.device_put(self._consolidate(gd), lead),
                jax.device_put(fidx, lead), jax.device_put(ks, lead),
            )
        finally:
            self.routing.record_complete(slots)
        self._rstats["replica_batches"] += 1
        self._rstats["replica_queries"] += int(np.sum(slots >= self.num_shards))
        return out

    def _gather_batch(self, us: np.ndarray, ks: jax.Array, snap: tuple, epoch: int):
        # resolve the epoch's OWN layout: after a repartition, a pinned
        # read on an old epoch routes by the boundaries it was published
        # with (and runs the matching block-size gather program)
        layout = self.routing.layout(epoch)
        serving = self.routing.serving(epoch)
        if serving is not None and self._serving_fns is not None:
            try:
                return self._gather_replicated(us, ks, serving, layout)
            except QueryError:
                raise  # routing misuse, not a replica fault
            except Exception as e:  # noqa: BLE001 — degrade, don't die
                self._rstats["replica_errors"] += 1
                self._rstats["last_replica_error"] = f"{type(e).__name__}: {e}"
        ids_g, d_g = snap
        if self.num_shards == 1:
            # one shard: the global layout IS the scalar (n+1, k) layout and
            # routing is the identity, so serve through the scalar gather
            # (same jitted program the plain engine runs — 1-shard parity)
            gather = ops.answer_program(ops.serve_gather)
            return gather(ids_g, d_g, jnp.asarray(us), ks)
        qglob, fidx = self._route(us, layout)
        fns = _device_fns(self.mesh, layout.block, self.k)
        if len(us) >= 4096 and qglob.size <= 2 * len(us):
            # Balanced tile (Bmax ~ B/S, e.g. traffic-balanced uneven
            # ranges, or equal-width under uniform traffic): consolidate
            # the sharded tile onto the lead device and run the
            # batch-order epilogue exactly once — the same two-phase split
            # the replica fan-out path uses. The one-jit form below pays
            # its epilogue per device, which swamps the tile savings. A
            # skew-padded tile (Bmax -> B, so S*Bmax >> B) flips the
            # trade: consolidating S*Bmax rows costs more than the
            # replicated epilogue, so the rectangle stays on the one-jit
            # path.
            lead = SingleDeviceSharding(self.mesh.devices.flat[0])
            gi, gd = fns["gather_tile"](ids_g, d_g, self._put_shard(qglob))
            self._rstats["balanced_batches"] += 1
            return fns["gather_epi"](
                jax.device_put(self._consolidate(gi), lead),
                jax.device_put(self._consolidate(gd), lead),
                jax.device_put(fidx, lead), jax.device_put(ks, lead),
            )
        return fns["gather"](
            ids_g, d_g, self._put_shard(qglob), self._put_repl(fidx),
            self._put_repl(ks),
        )

    def _fetch_rows(self, vs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Routed raw-row fetch (host result) for the repair halo exchange.

        The fetch count is pow2-padded (duplicate fetches of vertex 0 are
        free) so the gather's jit signature set stays bounded even though
        every repair round asks for a different number of halo rows.
        """
        m = len(vs)
        m_pad = _pow2_pad(m, lo=64)
        vs_p = np.zeros(m_pad, np.int32)
        vs_p[:m] = vs
        qglob, fidx = self._route(vs_p)
        ks = self._put_repl(np.full((m_pad,), self.k, np.int32))
        packed = self._gather_fn(
            self._ids_g, self._d_g, self._put_shard(qglob), self._put_repl(fidx), ks
        )
        ids, d = ops.unpack_answer(self._readback(packed), self.k, self.k)
        return ids[:m], d[:m]

    # ------------------------------------------------------------------
    # flush hooks (per-shard application)
    # ------------------------------------------------------------------

    def _scan_delete_rows(self, deletes: list[int]) -> np.ndarray:
        del_arr = self._put_repl(self._padded_deletes(deletes))
        # (S, shard_rows) per-shard hit masks: local row j of shard s is
        # vertex starts[s] + j while j < widths[s] (rows past a shard's
        # range width are all-pad under uneven ranges, never hit — but the
        # map back to vertex ids must still go through the boundaries)
        hits = self._readback(self._scan_fn(self._ids_g, del_arr))
        hits = hits.reshape(self.num_shards, -1)
        lay = self.routing.current_layout
        s_idx, j_idx = np.nonzero(hits)
        valid = j_idx < lay.widths[s_idx]
        return (lay.starts[s_idx] + j_idx)[valid].astype(np.int32)

    def _table_kth(self) -> np.ndarray:
        kth = self._readback(self._kth_fn(self._d_g))
        return kth[self._g_of_v].astype(np.float64)

    def _apply_rows(
        self, rows: np.ndarray, deletes: list[int],
        cand_ids: np.ndarray, cand_d: np.ndarray,
    ) -> np.ndarray:
        """Split a global row batch by owner shard and run the per-shard
        fused purge+merge; returns the per-row changed mask (input order)."""
        s = self.num_shards
        b = len(rows)
        order, o_sorted, slot, rmax = self._group_by_owner(self.routing.owner(rows))
        rmax = _pow2_pad(rmax, lo=16)
        p = cand_ids.shape[1]
        rglob = np.full((s, rmax), -1, np.int32)
        ci = np.full((s, rmax, p), -1, np.int32)
        cd = np.full((s, rmax, p), np.inf, np.float32)
        rglob[o_sorted, slot] = self.routing.padded_rows(rows[order], o_sorted)
        ci[o_sorted, slot] = cand_ids[order]
        cd[o_sorted, slot] = cand_d[order]
        self._ids_g, self._d_g, changed = self._purge_fn(
            self._ids_g, self._d_g, self._put_shard(rglob),
            self._put_repl(self._padded_deletes(deletes)),
            self._put_shard(ci), self._put_shard(cd),
        )
        changed = self._readback(changed)
        out = np.zeros(b, dtype=bool)
        out[order] = changed[o_sorted, slot]
        return out

    def _purge_merge(self, rows, deletes, cand_ids, cand_d) -> None:
        self._apply_rows(rows, deletes, cand_ids, cand_d)

    def _repair_part(self, part: np.ndarray) -> np.ndarray:
        """One Jacobi re-merge of ``part`` against its bridge neighborhoods.

        At one shard there is no boundary to exchange across — every
        neighbor row is local — so the round degenerates to the scalar
        engine's device-resident repair (the 1-shard global layout IS the
        scalar (n+1, k) layout), sharing its jitted program; that is what
        keeps the exp13 single-shard parity floor honest. Multi-shard, the
        cross-shard halo runs per ``self.halo``: the collective all_gather
        round (overflow falls back for this round), or the routed-gather
        baseline. Identical candidate multisets to the scalar engine's
        repair round either way, so the merged rows are bit-identical.
        """
        if self.num_shards == 1:
            from repro.core.engine import _repair_round

            nbr_tab, w_tab = self._nbr_slice(self._t_bucket(part))
            self._ids_g, self._d_g, changed = _repair_round(
                nbr_tab, w_tab, self._pad_rows(part), self._ids_g, self._d_g
            )
            return self._readback(changed)
        if self.halo == "collective":
            out = self._repair_part_collective(part)
            if out is not None:
                return out
            self._halo_stats["halo_fallbacks"] += 1
        return self._repair_part_host(part)

    def _repair_part_host(self, part: np.ndarray) -> np.ndarray:
        """Routed-gather repair round: fetch the unique neighbor rows
        (cross-shard halo, one routed gather through the host), build the
        shifted candidate lists on host, apply the shard-local merge."""
        k = self.k
        t = self._t_bucket(part)
        nbr = self._nbr_ids[part, :t]
        w = self._nbr_w[part, :t]
        valid = nbr >= 0
        uniq, inv = np.unique(nbr[valid], return_inverse=True)
        f_ids, f_d = self._fetch_rows(uniq)
        f_ids = np.concatenate([f_ids, np.full((1, k), -1, np.int32)])
        f_d = np.concatenate([f_d, np.full((1, k), np.inf, np.float32)])
        slot_idx = np.full(nbr.shape, len(uniq), dtype=np.int64)
        slot_idx[valid] = inv
        g_ids = f_ids[slot_idx]                    # (B, t, k)
        g_d = w[..., None] + f_d[slot_idx]         # float32 + float32
        cand_ids = g_ids.reshape(len(part), t * k)
        cand_d = g_d.reshape(len(part), t * k).astype(np.float32)
        cand_d = np.where(cand_ids < 0, np.float32(np.inf), cand_d)
        return self._apply_rows(part, [], cand_ids, cand_d)

    def _repair_part_collective(self, part: np.ndarray) -> np.ndarray | None:
        """Collective repair round: one fused rhalo program (serve rows,
        all_gather, purge+merge) — the rows never visit the host. Returns
        None when the round's halo exceeds ``halo_capacity`` (the caller
        falls back to the routed path for this round)."""
        t = self._t_bucket(part)
        plan = self._halo_plan(part, self._nbr_ids[part, :t], self._nbr_w[part, :t])
        if plan is None:
            return None
        serve, slotm, wm, rglob, order, o_sorted, slot = plan
        self._ids_g, self._d_g, changed = self._rhalo_fn(
            self._ids_g, self._d_g, self._put_shard(serve),
            self._put_shard(slotm), self._put_shard(wm),
            self._put_shard(rglob), self._put_repl(self._padded_deletes([])),
        )
        self._halo_stats["halo_rounds_collective"] += 1
        changed = self._readback(changed)
        out = np.zeros(len(part), dtype=bool)
        out[order] = changed[o_sorted, slot]
        return out

    def _halo_plan(self, part: np.ndarray, nbr: np.ndarray, w: np.ndarray):
        """Index bookkeeping for one collective halo round (repair or
        frontier): which unique neighbor rows each owner serves, and where
        each receiver finds its neighbors in the all_gather receive
        buffer.

        Returns ``(serve, slotm, wm, rglob, order, o_sorted, slot)`` or
        None when the padded per-owner served-row count exceeds
        ``halo_capacity``:

        - ``serve`` (S, Umax): global padded rows shard *src* serves
          (-1 pads) — every unique neighbor of ``part`` appears exactly
          once, in its owner's slice (multicast: receivers on every shard
          read the same served copy);
        - ``slotm`` (S, rmax, t): per-receiver position of each neighbor
          in the flattened (S*Umax) receive buffer (S*Umax = miss, which
          the device fold/candidate ops mask to (-1, +inf));
        - ``wm``    (S, rmax, t) edge weights, ``rglob`` (S, rmax) global
          receiver rows (-1 pads), both in the grouped-by-owner layout;
        - ``order/o_sorted/slot``: the group-by-owner permutation that
          maps the grouped changed-mask back to ``part`` order.

        Every row map goes through the CURRENT epoch's ``ShardLayout``
        (``owner`` / ``padded_rows``) — never flat ``vertex // block``
        arithmetic — so uneven ranges and live repartitions route the halo
        exactly like queries and deletes.
        """
        lay = self.routing.current_layout
        s = self.num_shards
        t = nbr.shape[1]
        valid = nbr >= 0
        uniq, inv = np.unique(nbr[valid], return_inverse=True)
        own_u = lay.owner(uniq)
        order_u, src_sorted, within, umax = self._group_by_owner(own_u)
        umax = _pow2_pad(umax, lo=16)
        if self.halo_capacity is not None and umax > self.halo_capacity:
            return None
        serve = np.full((s, umax), -1, np.int32)
        serve[src_sorted, within] = lay.padded_rows(uniq[order_u], src_sorted)
        pos = np.empty(len(uniq), np.int64)
        pos[order_u] = src_sorted * umax + within
        sm = np.full(nbr.shape, s * umax, np.int64)
        sm[valid] = pos[inv]
        order, o_sorted, slot, rmax = self._group_by_owner(lay.owner(part))
        rmax = _pow2_pad(rmax, lo=16)
        slotm = np.full((s, rmax, t), s * umax, np.int32)
        wm = np.zeros((s, rmax, t), np.float32)
        rglob = np.full((s, rmax), -1, np.int32)
        slotm[o_sorted, slot] = sm[order]
        wm[o_sorted, slot] = w[order]
        rglob[o_sorted, slot] = lay.padded_rows(part[order], o_sorted)
        return serve, slotm, wm, rglob, order, o_sorted, slot

    def _nbr_glob(self) -> jax.Array:
        """The sharded (S*(R+1), cap) BNS adjacency in the CURRENT row
        layout (vertex v's padded neighbor ids at row ``_g_of_v[v]``, all
        ``-1`` on pad rows), built lazily and dropped by ``_apply_layout``
        so the device expansion can never gather through stale boundaries."""
        if self._nbr_glob_g is None:
            self._nbr_tables()
            rows = self.num_shards * (self.shard_rows + 1)
            self._nbr_glob_g = self._put_shard(
                self.bn.bns_packed().relayout_rows(rows, self._g_of_v)
            )
        return self._nbr_glob_g

    def _expand_receivers(self, active: np.ndarray) -> np.ndarray:
        if self.num_shards == 1 or self.halo != "collective":
            return super()._expand_receivers(active)
        # if the previous frontier round ran fully collective, its fhalo
        # programs already psum'd this round's presence mask (neighbors of
        # exactly the changed = active rows) — read those instead of
        # dispatching a standalone expansion
        masks, ok = self._fmask, self._fmask_ok
        self._fmask, self._fmask_ok = [], True  # arm for the coming round
        if masks and ok:
            m = np.sum([self._readback(x)[:-1] for x in masks], axis=0)
            return np.flatnonzero(m).astype(np.int32)
        return self._expand_receivers_device(active)

    def _expand_receivers_device(self, active: np.ndarray) -> np.ndarray:
        """Device receiver-set expansion: route the active vertices to
        their owners, scatter their padded BNS rows into a psum'd presence
        mask on device, read back the mask and flatnonzero it — ascending
        unique. Exactly ``np.unique`` of the host CSR expansion — pinned
        by test."""
        aglob, _ = self._route(active)
        mask = self._readback(self._expand_fn(self._nbr_glob(), self._put_shard(aglob)))
        return np.flatnonzero(mask[:-1]).astype(np.int32)

    def _repair_receivers(
        self, changed: np.ndarray, rows: np.ndarray
    ) -> np.ndarray:
        if self.num_shards == 1 or self.halo != "collective":
            return super()._repair_receivers(changed, rows)
        self._nbr_tables()
        return np.intersect1d(
            self._expand_receivers_device(changed), rows
        ).astype(np.int32)

    # ------------------------------------------------------------------
    # frontier provider (shard-local checkIns)
    # ------------------------------------------------------------------

    def _frontier_init(self, src: np.ndarray):
        self._fmask, self._fmask_ok = None, True  # round 1 expands standalone
        srcp = self._frontier_pad_src(src)
        self._fsrc = self._upload(srcp)  # vertex ids (the 1-shard scalar path)
        grow = np.full(srcp.shape, -1, np.int64)
        m = srcp >= 0
        grow[m] = self._g_of_v[srcp[m]]
        self._fsrc_g = self._put_repl(grow.astype(np.int32))
        if self.num_shards == 1:
            from repro.core.engine import _frontier_init_prog

            return _frontier_init_prog(self._fsrc, self._ids_g.shape[0])
        return self._finit_fn(self._fsrc_g)

    def _frontier_part(self, state, part: np.ndarray):
        """One shard-local frontier round over one receiver bucket.

        At one shard every neighbor row is local and the global layout IS
        the scalar (n+1, B) layout, so the round degenerates to the scalar
        engine's device-resident program (shared jit cache, exp14 parity).
        Multi-shard, the cross-shard halo runs per ``self.halo`` — the
        fused collective fhalo round (overflow falls back for this round)
        or the routed-gather baseline below. Identical candidate values to
        the scalar engine's ``ops.frontier_relax`` round either way, so
        the dist trajectories — and hence the affected sets and candidate
        distances — are bit-identical.
        """
        if self.num_shards == 1:
            from repro.core.engine import _frontier_round

            nbr_tab, w_tab = self._nbr_slice(self._t_bucket(part))
            state, changed = _frontier_round(
                nbr_tab, w_tab, self._pad_rows(part), state, self._d_g,
                self._fsrc, self.use_pallas,
            )
            return state, self._readback(changed)
        if self.halo == "collective":
            out = self._frontier_part_collective(state, part)
            if out is not None:
                return out
            self._halo_stats["halo_fallbacks"] += 1
        # a routed part contributes nothing to the fused presence mask, so
        # the round's expansion must run standalone
        self._fmask_ok = False
        return self._frontier_part_host(state, part)

    def _frontier_part_collective(self, state, part: np.ndarray):
        """Collective frontier round: one fused fhalo program (gate,
        all_gather, min-fold, min-update) — gated distance rows move
        shard-to-shard without visiting the host. Returns None on capacity
        overflow (the caller falls back to the routed path for this
        round). The changed mask comes back as a thunk: the device value
        is only read when the round closes, so the plan/upload work for
        the round's remaining buckets overlaps the device compute instead
        of stalling on a per-part readback."""
        t = self._t_bucket(part)
        plan = self._halo_plan(part, self._nbr_ids[part, :t], self._nbr_w[part, :t])
        if plan is None:
            return None
        serve, slotm, wm, rglob, order, o_sorted, slot = plan
        state, changed, nmask = self._fhalo_fn(
            self._nbr_glob(), self._d_g, state, self._put_shard(serve),
            self._put_shard(slotm), self._put_shard(wm),
            self._put_shard(rglob), self._fsrc_g,
        )
        if self._fmask is not None:
            self._fmask.append(nmask)
        self._halo_stats["halo_rounds_collective"] += 1

        def resolve(changed=changed, order=order, o_sorted=o_sorted, slot=slot):
            cm = self._readback(changed)
            out = np.zeros(len(part), dtype=bool)
            out[order] = cm[o_sorted, slot]
            return out

        return state, resolve

    def _frontier_round(self, state, nbrs: np.ndarray):
        if self.num_shards == 1 or self.halo != "collective":
            return super()._frontier_round(state, nbrs)
        out = self._frontier_round_collective(state, nbrs)
        if out is not None:
            return out
        self._halo_stats["halo_fallbacks"] += 1
        # the fused round overflowed halo_capacity: re-run bucketed (each
        # part retries the per-part collective program, then the routed
        # host path), and let the round's expansion run standalone
        self._fmask_ok = False
        return super()._frontier_round(state, nbrs)

    def _frontier_round_collective(self, state, nbrs: np.ndarray):
        """One fused collective frontier round: a single fhalo_round
        program runs every degree bucket's gate/all_gather/fold/min-update
        back to back, each bucket over its own ``_halo_plan`` serve slab.
        Returns None when any bucket's serve set overflows
        ``halo_capacity`` (the caller falls back to the bucketed path).
        The state threads bucket-to-bucket inside the program — the same
        sequential schedule as the per-part paths — so the round
        trajectories, not just the fixpoint, match the scalar engine."""
        parts = list(self._bucket_parts(nbrs))
        if not parts:
            return state, []
        serves, slots, wms, rglobs, maps = [], [], [], [], []
        for part in parts:
            t = self._t_bucket(part)
            plan = self._halo_plan(
                part, self._nbr_ids[part, :t], self._nbr_w[part, :t]
            )
            if plan is None:
                return None
            serve, slotm, wm, rglob, order, o_sorted, slot = plan
            serves.append(self._put_shard(serve))
            slots.append(self._put_shard(slotm))
            wms.append(self._put_shard(wm))
            rglobs.append(self._put_shard(rglob))
            maps.append((part, order, o_sorted, slot))
        state, chs, nmask = self._fhalo_round_fn(
            self._nbr_glob(), self._d_g, state, self._fsrc_g,
            serves, slots, wms, rglobs,
        )
        if self._fmask is not None:
            self._fmask.append(nmask)
        self._halo_stats["halo_rounds_collective"] += len(parts)
        changed_parts = []
        for ch, (part, order, o_sorted, slot) in zip(chs, maps):
            cm = self._readback(ch)
            out = np.zeros(len(part), dtype=bool)
            out[order] = cm[o_sorted, slot]
            changed_parts.append(part[out])
        return state, changed_parts

    def _frontier_part_host(self, state, part: np.ndarray):
        """Routed-gather frontier round: fetch the gated neighbor send
        rows (cross-shard halo, one routed gather through the host — the
        owner applies the checkIns gate before its tentative distances
        leave the shard, so the k-th column itself never moves), fold the
        edge shift + min over neighbors on host, apply the per-shard
        min-update."""
        t = self._t_bucket(part)
        nbr = self._nbr_ids[part, :t]
        w = self._nbr_w[part, :t]
        valid = nbr >= 0
        uniq, inv = np.unique(nbr[valid], return_inverse=True)
        send = self._fetch_send(state, uniq)               # (U, B) float32
        b = send.shape[1]
        send = np.concatenate([send, np.full((1, b), np.inf, np.float32)])
        slot = np.full(nbr.shape, len(uniq), dtype=np.int64)
        slot[valid] = inv
        # fold the min over the neighbor columns one at a time — (P, B)
        # intermediates, never the (P, t, B) candidate tensor (the same
        # memory discipline as ops.frontier_relax's fori_loop form; min is
        # fold-order-insensitive, so the values stay bit-identical)
        cand = np.full((len(part), b), np.inf, np.float32)
        for j in range(t):
            np.minimum(cand, w[:, j, None] + send[slot[:, j]], out=cand)
        return self._apply_fmin(state, part, cand)

    def _fetch_send(self, state, vs: np.ndarray) -> np.ndarray:
        """Routed gated-row fetch (host result) for the frontier halo.

        pow2-padded fetch count, same signature-bounding trick as
        ``_fetch_rows`` (duplicate fetches of vertex 0 are free)."""
        m = len(vs)
        m_pad = _pow2_pad(m, lo=64)
        vs_p = np.zeros(m_pad, np.int32)
        vs_p[:m] = vs
        qglob, fidx = self._route(vs_p)
        out = self._fsend_fn(
            self._d_g, state, self._put_shard(qglob), self._put_repl(fidx),
            self._fsrc_g,
        )
        return self._readback(out)[:m]

    def _apply_fmin(self, state, rows: np.ndarray, vals: np.ndarray):
        """Split a receiver batch by owner shard and run the per-shard
        min-update; returns (new state, per-row changed mask) with the mask
        reordered back to the caller's row order."""
        s = self.num_shards
        order, o_sorted, slot, rmax = self._group_by_owner(self.routing.owner(rows))
        rmax = _pow2_pad(rmax, lo=16)
        b = vals.shape[1]
        rglob = np.full((s, rmax), -1, np.int32)
        vv = np.full((s, rmax, b), np.inf, np.float32)
        rglob[o_sorted, slot] = self.routing.padded_rows(rows[order], o_sorted)
        vv[o_sorted, slot] = vals[order]
        state, changed = self._fmin_fn(
            state, self._put_shard(rglob), self._put_shard(vv)
        )
        changed = self._readback(changed)
        out = np.zeros(len(rows), dtype=bool)
        out[order] = changed[o_sorted, slot]
        return state, out

    def _frontier_extract(self, state, rows: np.ndarray, src: np.ndarray):
        if self.num_shards == 1:
            from repro.core.engine import _frontier_affected

            aff, d = _frontier_affected(
                self._pad_rows(rows), state, self._d_g, self._fsrc
            )
            return (
                self._readback(aff)[: len(rows), : len(src)],
                self._readback(d)[: len(rows), : len(src)],
            )
        m = len(rows)
        m_pad = _pow2_pad(m, lo=64)
        vs_p = np.zeros(m_pad, np.int32)
        vs_p[:m] = rows
        qglob, fidx = self._route(vs_p)
        aff, d = self._faff_fn(
            self._d_g, state, self._put_shard(qglob), self._put_repl(fidx),
            self._fsrc_g,
        )
        return self._readback(aff)[:m, : len(src)], self._readback(d)[:m, : len(src)]

    # ------------------------------------------------------------------
    # persistence / stats
    # ------------------------------------------------------------------

    def _host_tables(self) -> tuple[np.ndarray, np.ndarray]:
        # always the logical vertex-order (n, k) layout: shard padding is a
        # runtime concern, not an artifact concern (enables reshard-on-load)
        return (
            np.asarray(self._ids_g)[self._g_of_v],
            np.asarray(self._d_g)[self._g_of_v],
        )

    def _save_meta(self) -> dict:
        meta = {"shards": self.num_shards, "shard_rows": self.shard_rows}
        lay = self.routing.current_layout
        if not lay.is_equal:
            # uneven boundaries persist with the artifact; load re-applies
            # them when the reader keeps the writer's shard count
            meta["starts"] = [int(s) for s in lay.starts]
        if self.routing.replication:
            # the plan is keyed by shard id, so it only transfers to a
            # reader at the same shard count (load re-applies or drops it)
            meta["replication"] = {
                str(s): r for s, r in self.routing.replication.items()
            }
        return meta

    def _extra_stats(self) -> dict:
        padded = self.num_shards * (self.shard_rows + 1)
        lay = self.routing.current_layout
        return {
            "num_shards": self.num_shards,
            "shard_rows": self.shard_rows,
            "padded_rows": padded,
            "row_padding_overhead": round((padded - self.n) / max(self.n, 1), 4),
            "shard_starts": [int(s) for s in lay.starts],
            "range_rows": [int(w) for w in lay.widths],
            "uneven_ranges": not lay.is_equal,
            "repartitions": self._partition_stats["repartitions"],
            "halo": self.halo,
            **self._halo_stats,
            "replication": dict(self.routing.replication),
            "replica_slots": self.routing.num_slots,
            "replica_policy": self.replica_policy,
            **self._rstats,
        }
