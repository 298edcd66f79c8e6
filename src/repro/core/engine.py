"""Device-resident batched kNN serving engine — the production query surface.

``KNNIndex`` (core/index.py) is the paper's host view: one numpy row scan per
query, one heap loop per update. That shape cannot serve heavy traffic — every
call pays Python dispatch, and nothing batches. ``QueryEngine`` keeps the
index as live device ``(n+1, k)`` id/dist tables (the construction sweeps'
layout, dummy row last) and exposes the paper's three operations in batched,
jitted form:

* ``query_batch(us, k)`` — one row gather + per-query k mask for a whole
  batch of queries (Theorem 4.3's O(k) scan, vectorized); and
  ``query_progressive_batch`` which yields the first-i prefix incrementally
  (Theorem 4.4) from a single gather.

* staged updates — ``stage_insert`` / ``stage_delete`` / ``stage_move``
  accumulate object updates in an arrival-order queue; ``flush_updates``
  coalesces the queue to its net object-set delta and applies it as ONE fused
  device batch against the tables.

  Coalescing semantics (per object, in queue order): an insert followed by a
  delete of the same object cancels to nothing; a delete followed by an
  insert of the same object is a no-op (the index is a pure function of the
  final object set — Theorems 6.2/6.4); move chains collapse to their
  endpoint (``a->b`` then ``b->c`` is ``a->c``; a chain returning to its
  origin cancels). The per-flush stats dict reports the pure insert/delete
  counts, the net move count, and ``coalesced`` — how many staged ops the
  folding eliminated.

  Application is a single fused pipeline, not a delete pass chased by an
  insert pass: one device scan finds every row naming a deleted object
  (``ops.rows_containing``); the checkIns frontier for ALL staged inserts
  runs as one jitted multi-source pruned-relaxation program on device
  (``ops.frontier_relax`` rounds with changed-frontier narrowing — see
  ``EngineCore._insert_frontier``; the host ``updates.insert_affected_set``
  heap search survives as the per-object oracle and as the ``frontier =
  "host"`` baseline pipeline) against the pre-update k-th distances —
  insert-first semantics, the same order the scalar ``move_object`` oracle
  uses; any insert-affected row the pruning misses lost an entry to the
  deletions and is repaired as part of the purge set (see
  ``flush_updates``); then one ``ops.rows_purge_merge`` over
  the union of the hit rows and the frontier drops the deleted entries,
  merges the insert candidates and recompacts every affected row in a single
  gather/merge/scatter. Jacobi rounds of the construction merge
  (``ops.sweep_merge`` over the purged rows' bridge neighborhoods) then
  repair the deletion holes to a fixpoint — Algorithm 5's processDel, run
  breadth-first on device — with the source- and destination-side work
  sharing one changed-row frontier and one repair pass per round. For a
  moving fleet (each object deleted here, re-inserted a street away) the
  destination entries are already in the tables when repair starts, so the
  holes close in about one round instead of pulling replacements from far
  away. The scalar ``core/updates.py`` path is kept as the reference oracle;
  the batched path is property-tested ``indices_equivalent`` against it.

  The repair rounds use the merge's XLA form (functional gather-then-scatter)
  on every engine. Repaired rows read each other, so a round must be pure
  Jacobi: every read sees the pre-round tables. The XLA form is; so is the
  Pallas ``sweep_merge``, which emits its merged rows for an XLA scatter and
  never writes the tables it reads.

* ``save`` / ``load`` — one ``.npz`` artifact (ids, dists, k, object set,
  format version + shard meta) shared by ``knn_build.py --out`` and the
  serving loop.

Queries always see the last *flushed* state: the staged queue is invisible
until ``flush_updates``, which is exactly the paper's batch-update-arrival
(BUA) serving model, and what lets a server interleave large query batches
with periodic update batches without locking.

Epochs and snapshot isolation: the tables are *epoch-versioned*. Every flush
builds epoch ``e+1`` functionally from epoch ``e`` — the pipeline is pure
device programs reassigning the working references, never overwriting the
published buffers — and then performs one atomic swap (``EpochStore.publish``)
that makes ``e+1`` current. ``query_batch`` resolves its table snapshot at
dispatch, so a query issued at ANY point during a flush reads a whole epoch —
``e`` before the swap, ``e+1`` after — never a partially-repaired mixture,
and a failed flush rolls the working references back to epoch ``e`` with the
staged queue intact (retryable; serving never stops). ``keep_epochs`` (the
retention E) bounds device memory at E table versions — ≤ E·(n+1)·k·8 bytes
— and lets callers pin an older epoch: ``query_batch(..., epoch=e)``.

Durability: ``attach_journal`` / ``load(..., journal=...)`` pair the engine
with a write-ahead ``repro.core.journal.UpdateJournal`` — staged ops are
fsync'd before acknowledgment, flush commits append an epoch marker, and
``load`` replays the journal through the staged path (flushing at each
commit marker, then rolling any uncommitted tail forward as one final
flush), so a killed process recovers to byte-identical tables. Artifacts
carry a content checksum + schema version; corruption raises a typed
``ArtifactError`` (see ``repro.core.errors``) instead of serving garbage.

Fault injection: ``EngineCore._checkpoint(phase)`` is the chaos seam — a
no-op unless ``engine.checkpoint_hook`` is set. It fires at
``"post-journal-append"`` (a staged op just hit disk), ``"mid-repair-round"``
(after each Jacobi repair round), ``"pre-swap"`` (epoch ``e+1`` built, not
yet published) and ``"post-swap"`` (published + journal-committed). The
``tests/chaos`` suite drives it to simulate kill-at-any-point and to assert
the snapshot-isolation contract above.

Host/device traffic per flush: the update script and affected-row indices go
up; a changed-row mask per frontier/repair round (which narrows the next
round's receiver set) and, once the frontier converges, the affected rows'
distance tiles come back. The (n,) k-th-distance column — the checkIns
pruning bound — never leaves the device: the frontier rounds read it
straight off the live distance table, so per-flush readback is proportional
to the affected set, not to n. Queries move only the query ids and ks up
and one packed (B, 2k) answer buffer back (``ops.pack_answer``: a batch's
one readback, the ``knn:query.readback`` span, ``stats()["query_readbacks"]``
and ``["query_readback_bytes"]``). Every readback of a flush goes through
``EngineCore._readback`` and every upload through ``_upload``, which count
them (``stats()["flush_readbacks"]`` ..., per flush in ``epoch_stats(e)``);
each readback is a ``knn:flush.readback`` span, the host waiting on the
device.

Host spans: queries, flush phases and rounds, and the build run in named
``knn:*`` spans (``repro.core.spans``), written into a ``jax.profiler``
trace when one is open and totalled in ``stats()["spans"]``.

A closed program set: every flush program is jitted on the shapes of its
arguments, and both engines pad each of them to a tier (``_rows_width``,
``_cand_width``, the BNS width buckets, ``_src_widths`` for the frontier's
sources and one width for the deletes: the delta cap, ``_delta_cap``). A
delta past the cap is applied in successive deltas of at most the cap and
published as one epoch. So ``QueryEngine`` can list every signature a
flush can dispatch (``_flush_signatures``), and its first flush compiles
them all (``_warm_flush``, the ``knn:flush.warm`` span): no later flush
compiles, whatever the size or the spread of its delta
(``stats()["flush_programs"]``). The sharded engine shares the tiers but
lists no set: its multi-shard programs also pad per-shard counts (owner
rows, halo sizes) that only the routing of a flush decides.

Everything above that is *layout-independent* — the staged queue and its
coalescing, query stat bookkeeping, the flush orchestration (delete scan ->
batched device checkIns frontier -> fused purge+merge -> breadth-first
repair with its changed-row frontier narrowing), persistence and the stats
surface (including the spans and the flush's transfer counts) — lives
in ``EngineCore``. ``QueryEngine`` supplies the single-device table layout
and device ops; ``repro.core.sharded.ShardedQueryEngine`` supplies the
vertex-sharded multi-device layout on top of the same core, which is what
keeps the two engines drop-in interchangeable (and exactly equivalent, see
tests/core/test_sharded.py).
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import zipfile
import zlib
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bngraph import BNGraph
from repro.core.errors import (
    ArtifactError,
    EngineConfigError,
    EpochError,
    QueryError,
    StagedUpdateError,
)
from repro.core.journal import UpdateJournal
from repro.core.construct_jax import build_knn_tables_jax
from repro.core.index import PAD_ID, KNNIndex
from repro.core.spans import span
from repro.core.updates import insert_affected_set
from repro.analysis import sanitize
from repro.kernels import ops

_FORMAT = "repro-knn-index"
# v2 added shard meta; v3 adds the content checksum. Load accepts v1/v2
# artifacts unchanged (no checksum to verify) and refuses versions > 3.
_FORMAT_VERSION = 3
_MAX_REPAIR_ROUNDS = 256
# the most staged inserts (and deletes) one delta of a flush applies: B,
# the frontier's (n+1, B) float32 state, stays within 1/_STATE_SHARE of the
# device's memory (a flush peaks near ten times the state: 2.7 GB at n =
# 131,044 and B = 512 on a TPU v5e), and within _MAX_DELTA
_MAX_DELTA = 512
_STATE_SHARE = 24
# the memory assumed where the device reports none (the CPU): a TPU v5e's
_DEVICE_BYTES = 16 << 30
# the most neighbor indices one gather of a repair round takes (``_repair_round``)
_GATHER_INDICES = 1 << 15
# a flush's host<->device transfers, counted by ``_readback`` / ``_upload``
_IO_KEYS = ("readbacks", "readback_bytes", "uploads", "upload_bytes")


def _tables_checksum(ids: np.ndarray, dists: np.ndarray, objects: np.ndarray) -> int:
    """Content checksum over the logical artifact payload (order matters)."""
    crc = zlib.crc32(np.ascontiguousarray(ids).tobytes())
    crc = zlib.crc32(np.ascontiguousarray(dists).tobytes(), crc)
    return zlib.crc32(np.ascontiguousarray(objects).tobytes(), crc)


class EpochStore:
    """Epoch number -> immutable table snapshot, with keep-last-E retention.

    The store is the engine's single source of "what do queries read": the
    newest published epoch is current, ``snapshot()`` resolves it at call
    time (dispatch-time snapshot = the snapshot-isolation contract), and
    ``snapshot(e)`` pins an older retained epoch. Retention is strict
    keep-last-E — publishing epoch ``e`` evicts everything below
    ``e - keep + 1`` — which is what bounds device memory at E table
    versions. Snapshots are tuples of immutable device arrays, so retaining
    one is a reference, not a copy.
    """

    def __init__(self, keep: int = 2):
        self._snaps: OrderedDict[int, tuple] = OrderedDict()
        self._keep = 0
        self.keep = keep

    @property
    def keep(self) -> int:
        return self._keep

    @keep.setter
    def keep(self, e: int) -> None:
        e = int(e)
        if e < 1:
            raise EpochError(f"keep_epochs must be >= 1, got {e}")
        self._keep = e
        self._evict()

    @property
    def current(self) -> int:
        return next(reversed(self._snaps)) if self._snaps else -1

    def epochs(self) -> list[int]:
        return list(self._snaps)

    def publish(self, epoch: int, snap: tuple) -> None:
        """Atomically make ``epoch`` current (one dict insert — a query
        that resolved its snapshot before this call keeps reading the old
        epoch's buffers, which stay alive via its reference)."""
        self._snaps[epoch] = snap
        self._evict()

    def _evict(self) -> None:
        while len(self._snaps) > self._keep:
            self._snaps.popitem(last=False)

    def snapshot(self, epoch: int | None = None) -> tuple:
        return self.resolve(epoch)[1]

    def resolve(self, epoch: int | None = None) -> tuple[int, tuple]:
        """Resolve ``epoch`` (None = current, at call time) to the concrete
        ``(epoch number, snapshot)`` pair — one atomic read, so a caller
        that needs both (e.g. replica routing keyed by epoch) can never see
        a number from one epoch and buffers from another."""
        if epoch is None:
            epoch = self.current
        else:
            epoch = int(epoch)
        if epoch not in self._snaps:
            raise EpochError(
                f"epoch {epoch} is not retained (have {self.epochs()}); "
                f"raise keep_epochs to pin more history"
            )
        return epoch, self._snaps[epoch]


def _pow2_pad(x: int, lo: int = 8) -> int:
    """Next power of two >= x (>= lo): bounds distinct jit signatures."""
    return max(lo, 1 << (max(1, x) - 1).bit_length())


def _tiers(width, limit: int) -> list[int]:
    """Every value ``width(x)`` takes for 1 <= x <= limit, for a ``width``
    that is constant between consecutive powers of two (and at the last
    one up to ``limit``): the tiers a flush can pad a size of at most
    ``limit`` to."""
    if limit < 1:
        return []
    return sorted({width(min(1 << i, limit)) for i in range(limit.bit_length() + 1)})


@functools.cache
def _device_bytes() -> int:
    """The first local device's memory, as it reports it, else
    ``_DEVICE_BYTES``."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", _DEVICE_BYTES))


class EngineCore:
    """Layout-independent serving core shared by the scalar and sharded engines.

    Subclasses own the table storage and implement the device hooks:

    * ``_gather_batch(us, ks, snap, epoch)`` — the batched row gather
      behind ``query_batch``, ending in the packed answer buffer at full
      index-k width (``ops.pack_answer``; the core reads it back once and
      applies stats and the per-query width slice). ``snap`` is the epoch
      snapshot resolved at dispatch and ``epoch`` its number — the gather
      must read the snapshot, never the working tables, so queries stay
      snapshot-isolated from an in-flight flush; the epoch number lets a
      subclass key per-epoch serving state (replica buffers) consistently.
    * ``_table_snapshot()`` — the current working tables as an immutable
      snapshot tuple (references; JAX arrays are immutable), published to
      the ``EpochStore`` at each flush commit.
    * ``_restore_tables(snap)`` — reset the working references to a
      snapshot (the failed-flush rollback path).
    * ``_scan_delete_rows(deletes)`` — global row ids naming any deleted
      object (the vectorized checkDel membership scan).
    * ``_purge_merge(rows, deletes, cand_ids, cand_d)`` — the fused
      purge + candidate merge over one (unpadded) global row batch.
    * ``_repair_part(part)`` — one Jacobi re-merge of ``part`` rows against
      their bridge neighborhoods; returns the per-row changed mask.
    * the frontier provider seam — ``_frontier_init(src)`` allocates the
      multi-source tentative-distance state for one staged insert batch,
      ``_frontier_part(state, part)`` runs one pruned-relaxation round over
      a receiver-row bucket (returning the new state + changed mask), and
      ``_frontier_extract(state, rows, src)`` reads back the affected mask
      and distances for the touched rows. The round loop, receiver-set
      expansion, bucketing and candidate compaction run here
      (``_insert_frontier``), so the scalar and sharded frontiers share one
      trajectory and cannot drift.
    * ``_table_kth()`` — the (n,) k-th-distance column (float64 host
      array). Only the ``frontier = "host"`` baseline pipeline reads it;
      the device frontier keeps the column on device end to end.
    * ``_host_tables()`` — the logical (n, k) id/dist tables for ``save``.
    * ``to_index()`` — readback into the host ``KNNIndex`` view.

    The flush pipeline, the frontier/repair rounds' narrowing and all
    validation/coalescing/stat bookkeeping run here, once, so a sharded
    engine cannot drift from the scalar one in anything but the device
    layout.
    """

    def __init__(self, k: int, objects, *, bn: BNGraph | None, use_pallas: bool):
        # subclasses set ``self.n`` (and their tables) before calling super()
        self.k = int(k)
        self.use_pallas = bool(use_pallas)
        self.bn = bn
        self.frontier = "device"  # validated setter, see the property below
        self.halo = "collective"  # validated setter, see the property below
        obj = {int(o) for o in np.asarray(objects).ravel()}
        self._objects = obj
        self._pending = set(obj)
        self._staged: list[tuple[str, int]] = []
        self._nbr_ids: np.ndarray | None = None
        self._nbr_w: np.ndarray | None = None
        self._nbr_deg: np.ndarray | None = None
        self._nbr_by_t: dict[int, tuple[jax.Array, jax.Array]] = {}
        self._stats = {
            "queries_served": 0,
            "query_batches": 0,
            "query_readbacks": 0,
            "query_readback_bytes": 0,
            "last_batch_size": 0,
            "flushes": 0,
            "flushes_failed": 0,
            "inserts_applied": 0,
            "deletes_applied": 0,
            "moves_applied": 0,
            "coalesced": 0,
            "rows_repaired": 0,
            "repair_rounds_last": 0,
            "frontier_rounds_last": 0,
            **{"flush_" + key: 0 for key in _IO_KEYS},
        }
        # host spans (``repro.core.spans``): name -> {"s": seconds, "n": calls}
        self._span_totals: dict[str, dict] = {}
        # the running flush's transfer counts (``_IO_KEYS``), else None
        self._io: dict[str, int] | None = None
        self._flush_warmed = False  # see ``_warm_flush``
        # epoch-versioned serving state: epoch 0 is the constructor tables;
        # every flush publishes the next epoch and queries resolve their
        # snapshot at dispatch (see the module docstring)
        self.checkpoint_hook = None  # chaos seam: fn(engine, phase) or None
        self._journal: UpdateJournal | None = None
        self._epochs = EpochStore(keep=2)
        self._epoch_stats: dict[int, dict] = {}
        self._publish_epoch(0)
        self._epoch_stats[0] = {"origin": "build"}

    @property
    def frontier(self) -> str:
        """Which checkIns pipeline ``flush_updates`` runs: ``"device"``
        (default) is the batched multi-source ``ops.frontier_relax`` rounds;
        ``"host"`` replays the per-object ``insert_affected_set`` heap
        search (kept as the measurable baseline — see benchmarks exp14 —
        and as the oracle's twin). A plain attribute rather than a
        constructor knob: flipping pipelines mid-life is safe (both produce
        identical tables); anything but the two known modes raises so a
        typo cannot silently select the wrong pipeline."""
        return self._frontier

    @frontier.setter
    def frontier(self, mode: str) -> None:
        if mode not in ("device", "host"):
            raise EngineConfigError(
                f"frontier must be 'device' or 'host', got {mode!r}"
            )
        self._frontier = mode

    @property
    def halo(self) -> str:
        """How cross-shard state moves during repair/frontier rounds:
        ``"collective"`` (default) exchanges neighbor rows and gated send
        rows as capacity-padded ``all_gather`` multicasts inside the
        shard_map programs, and runs the receiver-set expansion on device;
        ``"host"`` replays the routed-gather halo (host-mediated fetches,
        kept as the measurable baseline — see benchmarks exp18 — and as
        the collective path's bit-identity twin). Same seam pattern as
        ``frontier``: a plain attribute, safe to flip mid-life (both modes
        produce identical tables), unknown modes raise. The scalar engine
        and the 1-shard layout have no shard boundary to exchange across,
        so the setting is inert there."""
        return self._halo

    @halo.setter
    def halo(self, mode: str) -> None:
        if mode not in ("collective", "host"):
            raise EngineConfigError(
                f"halo must be 'collective' or 'host', got {mode!r}"
            )
        self._halo = mode

    # ------------------------------------------------------------------
    # epochs / durability / fault injection
    # ------------------------------------------------------------------

    @property
    def epoch(self) -> int:
        """The current serving epoch: 0 at construction, +1 per flush."""
        return self._epochs.current

    @property
    def keep_epochs(self) -> int:
        """Retention E: how many table epochs stay resident (>= 1). Device
        memory for tables is bounded by E·(n+1)·k·(id_bytes+dist_bytes);
        raising E lets callers pin older epochs via
        ``query_batch(..., epoch=e)``. Lowering it evicts immediately."""
        return self._epochs.keep

    @keep_epochs.setter
    def keep_epochs(self, e: int) -> None:
        self._epochs.keep = e
        self._trim_epoch_stats()

    def retained_epochs(self) -> list[int]:
        return self._epochs.epochs()

    def epoch_stats(self, epoch: int | None = None) -> dict:
        """Per-epoch provenance: how the retained epoch was produced
        (``origin`` build/flush/recovery plus the flush's stats dict and
        wall time). Raises ``EpochError`` for evicted/unknown epochs."""
        epoch = self._epochs.current if epoch is None else int(epoch)
        if epoch not in self._epoch_stats:
            raise EpochError(
                f"epoch {epoch} has no retained stats "
                f"(have {sorted(self._epoch_stats)})"
            )
        return dict(self._epoch_stats[epoch])

    def _trim_epoch_stats(self) -> None:
        kept = set(self._epochs.epochs())
        self._epoch_stats = {
            e: s for e, s in self._epoch_stats.items() if e in kept
        }

    def _publish_epoch(self, epoch: int) -> None:
        """Publish the working tables as ``epoch`` (the atomic swap).
        Subclasses that keep their own epoch-indexed structures (the
        sharded engine's routing table) extend this — it is the ONE place
        an epoch becomes visible."""
        self._epochs.publish(epoch, self._table_snapshot())

    def _prepare_publish(self) -> None:
        """Last hook inside the flush's fallible region, right before the
        pre-swap checkpoint. Subclasses that stage *layout* changes (the
        sharded engine's repartition-on-flush) re-lay the working tables
        here, so the subsequent ``_publish_epoch`` makes the new tables and
        the new layout visible in the same atomic step — and a failure
        anywhere in here still rolls back through ``_restore_tables``."""

    def _checkpoint(self, phase: str) -> None:
        """Fault-injection seam: no-op unless ``checkpoint_hook`` is set.

        The chaos tests install a hook that raises (simulated
        kill-at-this-point) or issues queries (snapshot-isolation probes).
        Phases fired: ``post-journal-append``, ``mid-repair-round``,
        ``pre-swap``, ``post-swap`` — plus ``pre-repartition`` /
        ``mid-repartition`` when the sharded engine has a staged
        repartition riding the flush.
        """
        hook = self.checkpoint_hook
        if hook is not None:
            hook(self, phase)

    def attach_journal(self, journal, *, replay: bool = True) -> UpdateJournal:
        """Pair the engine with a write-ahead update journal.

        ``journal`` is an ``UpdateJournal`` or a path (opened/created).
        With ``replay=True`` (default) any records already in the journal
        are first replayed through the staged path: flush at each commit
        marker — reproducing the original flush boundaries, so the tables
        land byte-identical to the uncrashed engine's — then any
        uncommitted tail is staged and rolled forward as one final flush
        (which appends its own commit marker, making recovery idempotent).
        From then on every ``stage_*`` call appends + fsyncs its record
        before acknowledging, every flush commits an epoch marker, and
        ``save`` truncates the journal once the artifact embodies it.
        """
        if self._journal is not None:
            raise ArtifactError("engine already has a journal attached")
        if self._staged:
            raise ArtifactError(
                "attach_journal before staging updates: the "
                f"{len(self._staged)} already-staged ops predate the journal "
                "and would not be durable"
            )
        if isinstance(journal, (str, os.PathLike)):
            journal = UpdateJournal(journal)
        if replay:
            self._replay_journal(journal)
        self._journal = journal
        return journal

    def _replay_journal(self, journal: UpdateJournal) -> None:
        """Roll the journal forward through the oracle-equivalent staged
        path (see ``attach_journal``). Journaling is suppressed while
        replaying committed segments — their records are already on disk —
        and re-enabled for the tail's roll-forward flush so its commit
        marker is appended."""
        records = journal.replay()
        tail = False
        for rec in records:
            if rec[0] == "commit":
                self.flush_updates()
                self._epoch_stats[self.epoch]["origin"] = "recovery"
                tail = False
            elif rec[0] == "ins":
                self.stage_insert(rec[1])
                tail = True
            elif rec[0] == "del":
                self.stage_delete(rec[1])
                tail = True
            else:  # ("mov", u, v)
                self.stage_move(rec[1], rec[2])
                tail = True
        if tail:
            self._journal = journal  # the tail flush commits its marker
            try:
                self.flush_updates()
                self._epoch_stats[self.epoch]["origin"] = "recovery"
            finally:
                self._journal = None

    def _journal_op(self, op: tuple) -> None:
        """WAL discipline: the record is on disk (fsync'd) before the
        stage call acknowledges. A kill right after this point is the
        ``post-journal-append`` chaos site — the op replays on reload even
        though the caller may never have seen the ack (fsync completed, so
        applying it is the correct recovery)."""
        if self._journal is not None:
            self._journal.append_op(op)
            self._checkpoint("post-journal-append")

    @staticmethod
    def normalize_tables(
        ids, dists, k: int, bn: BNGraph | None
    ) -> tuple[int, jax.Array, jax.Array]:
        """Validate and normalize constructor tables to the engine layout.

        Accepts host/device (n, k) tables or (n+1, k) tables straight from
        the construction sweeps (dummy gather row already last, only
        recognized when ``bn`` pins down n); returns ``(n, ids, dists)``
        with the dummy row (PAD_ID, +inf) guaranteed present. One shared
        normalizer so the scalar and sharded constructors cannot drift.
        """
        ids = jnp.asarray(ids, jnp.int32)
        dists = jnp.asarray(dists, jnp.float32)
        if ids.ndim != 2 or ids.shape != dists.shape or ids.shape[1] != k:
            raise ValueError(f"tables must be (n, k)={ids.shape} with k={k}")
        if bn is not None and ids.shape[0] not in (bn.n, bn.n + 1):
            raise ValueError(f"tables have {ids.shape[0]} rows but graph has n={bn.n}")
        if bn is not None and ids.shape[0] == bn.n + 1:
            return ids.shape[0] - 1, ids, dists
        n = int(ids.shape[0])
        ids = jnp.concatenate([ids, jnp.full((1, k), PAD_ID, jnp.int32)], axis=0)
        dists = jnp.concatenate(
            [dists, jnp.full((1, k), jnp.inf, jnp.float32)], axis=0
        )
        return n, ids, dists

    # ------------------------------------------------------------------
    # queries
    # ------------------------------------------------------------------

    def _ks_array(self, b: int, k) -> tuple[jax.Array, int]:
        # uploads are explicit device_puts of host arrays: an eager jnp.full
        # materializes its Python fill value through an implicit transfer,
        # which the sanitizer leg's transfer guard (rightly) rejects
        if k is None:
            return jax.device_put(np.full((b,), self.k, np.int32)), self.k
        ks = np.asarray(k, dtype=np.int32)
        if ks.ndim == 0:
            if int(ks) > self.k:
                raise QueryError(f"query k={int(ks)} exceeds index k={self.k}")
            return jax.device_put(np.full((b,), int(ks), np.int32)), int(ks)
        if ks.shape != (b,):
            raise QueryError(f"per-query k must have shape ({b},), got {ks.shape}")
        if ks.size and int(ks.max()) > self.k:
            raise QueryError(f"per-query k max={int(ks.max())} exceeds index k={self.k}")
        return jax.device_put(ks), self.k

    def _gather_batch(self, us: np.ndarray, ks: jax.Array, snap: tuple, epoch: int):
        """Batched row gather at full index-k width against the ``snap``
        epoch snapshot (never the working tables — see the class doc),
        ending in the packed answer buffer (``ops.pack_answer``); ``us``
        is a host array so a sharded engine can route queries by owner
        before the device roundtrip. ``epoch`` is the resolved epoch
        number of ``snap`` (for subclasses with epoch-keyed serving state,
        e.g. replica buffers behind the routing table)."""
        raise NotImplementedError

    def query_batch(self, us, k=None, *, epoch=None) -> tuple[np.ndarray, np.ndarray]:
        """Batched kNN: (B,) vertices -> ((B, k') int32 ids, (B, k') float32
        dists), host arrays read back in one transfer.

        ``k`` may be None (index k), a scalar, or a (B,) array for mixed-k
        traffic; columns past a query's k hold the pad sentinel (-1, +inf).
        Raises ``QueryError`` when any requested k exceeds the index's k.

        ``epoch`` pins the read to a retained older epoch (``EpochError``
        if evicted); by default the snapshot is resolved at dispatch — the
        current epoch at THIS moment — so a flush in progress can neither
        block the query nor leak it a partially-repaired table.
        """
        us = np.asarray(us, dtype=np.int32)
        if us.ndim != 1:
            raise QueryError(f"queries must be a 1-D vertex array, got {us.shape}")
        epoch_r, snap = self._epochs.resolve(epoch)
        b = int(us.shape[0])
        with self._span("query", batch=self._stats["query_batches"], epoch=epoch_r, b=b):
            with sanitize.guard("query"):
                with self._span("query.ks"):
                    ks, width = self._ks_array(b, k)
                with self._span("query.gather"):
                    packed = self._gather_batch(us, ks, snap, epoch_r)
            packed.copy_to_host_async()  # the copy overlaps the bookkeeping
            self._stats["queries_served"] += b
            self._stats["query_batches"] += 1
            self._stats["last_batch_size"] = b
        # the answer's one readback: the host waiting on the device
        with self._span("query.readback", bytes=int(packed.nbytes)):
            buf = np.asarray(packed)
        self._stats["query_readbacks"] += 1
        self._stats["query_readback_bytes"] += buf.nbytes
        return ops.unpack_answer(buf, self.k, width)

    def query_progressive_batch(
        self, us, k=None, *, epoch=None
    ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """Progressive batched output: yields the first-i prefix for
        i = 1..k from ONE gather — O(i) work to surface i results per query
        (Theorem 4.4, batched)."""
        ids, d = self.query_batch(us, k, epoch=epoch)
        for i in range(1, ids.shape[1] + 1):
            yield ids[:, :i], d[:, :i]

    # ------------------------------------------------------------------
    # staged updates
    # ------------------------------------------------------------------

    def _check_vertex(self, u: int) -> int:
        u = int(u)
        if not 0 <= u < self.n:
            raise StagedUpdateError(f"vertex {u} out of range [0, {self.n})")
        if self.bn is None:
            raise RuntimeError(
                "updates need the BN-Graph; build the engine with bn= or load(..., bn=)"
            )
        return u

    def stage_insert(self, u: int) -> int:
        """Queue an object insertion; returns the staged-queue depth."""
        u = self._check_vertex(u)
        if u in self._pending:
            raise StagedUpdateError(f"object {u} already present (or staged for insert)")
        self._journal_op(("ins", u))
        self._pending.add(u)
        self._staged.append(("ins", u))
        return len(self._staged)

    def stage_delete(self, u: int) -> int:
        """Queue an object deletion; returns the staged-queue depth."""
        u = self._check_vertex(u)
        if u not in self._pending:
            raise StagedUpdateError(f"object {u} absent (or staged for delete)")
        self._journal_op(("del", u))
        self._pending.discard(u)
        self._staged.append(("del", u))
        return len(self._staged)

    def stage_move(self, u: int, v: int) -> int:
        """Queue an object movement u -> v; returns the staged-queue depth.

        The moving-objects primitive: the object at vertex u relocates to
        vertex v (same object, new position). At flush time move chains
        collapse to their endpoints and the source purge, destination
        checkIns frontier and repair rounds all run as one fused device
        batch — cheaper than staging the delete and the insert separately.
        """
        u = self._check_vertex(u)
        v = self._check_vertex(v)
        if u == v:
            raise StagedUpdateError(f"move source and destination are both {u}")
        if u not in self._pending:
            raise StagedUpdateError(f"object {u} absent (or staged for delete)")
        if v in self._pending:
            raise StagedUpdateError(f"object {v} already present (or staged for insert)")
        self._journal_op(("mov", u, v))
        self._pending.discard(u)
        self._pending.add(v)
        self._staged.append(("mov", u, v))
        return len(self._staged)

    @property
    def queue_depth(self) -> int:
        return len(self._staged)

    @property
    def objects(self) -> np.ndarray:
        """The flushed candidate-object set M (staged updates not included)."""
        return np.array(sorted(self._objects), dtype=np.int32)

    def _nbr_tables(self) -> None:
        """Bind the BN-Graph's combined BNS adjacency (``bns_packed``).

        Valid neighbors are compacted to the front of each row so that a row
        with degree d is fully described by the first d columns; frontier and
        repair rounds then run on the (n+1, t) column slice of the smallest
        pow4 bucket t >= the batch rows' max degree instead of the global
        tau', mirroring the construction sweeps' shape bucketing. The padded
        host tables are built once per BNGraph and shared across engines;
        the per-width device slices are cached per engine (``_nbr_slice``).
        """
        if self._nbr_ids is None:
            packed = self.bn.bns_packed()
            self._nbr_ids = packed.ids
            self._nbr_w = packed.w
            self._nbr_deg = packed.deg
            self._nbr_indptr = packed.indptr
            self._nbr_indices = packed.indices

    def _t_bucket(self, rows: np.ndarray) -> int:
        """Smallest pow4 width (>= 8) covering the rows' max BNS degree."""
        t_max = int(self._nbr_deg[rows].max())
        t = 8
        while t < t_max:
            t *= 4
        return min(t, self._nbr_ids.shape[1])

    def _nbr_slice(self, t: int) -> tuple[jax.Array, jax.Array]:
        """Device (n+1, t) adjacency slice for one width bucket, cached."""
        if t not in self._nbr_by_t:
            self._nbr_by_t[t] = (
                self._upload(self._nbr_ids[:, :t]),
                self._upload(self._nbr_w[:, :t]),
            )
        return self._nbr_by_t[t]

    # the flush's shape tiers ----------------------------------------------

    def _delta_cap(self) -> int:
        """The most staged inserts, and deletes, one delta of a flush
        applies: the frontier's widest source width B and the one width of
        the padded deletes; a larger flush applies successive deltas and
        publishes once. The (n+1, B) float32 state within 1/_STATE_SHARE of
        the device's memory, B at most ``_MAX_DELTA`` and no wider than n
        needs; 128 at least on the Pallas path (lane-aligned columns)."""
        lo = 128 if self.use_pallas else 8
        fit = max(1, _device_bytes() // (_STATE_SHARE * 4 * (self.n + 1)))
        return max(lo, min(_MAX_DELTA, 1 << (fit.bit_length() - 1), _pow2_pad(self.n, lo)))

    def _src_widths(self) -> list[int]:
        """The frontier's source widths: the delta cap and one an eighth of
        it (at least the Pallas path's 128), so a small delta carries an
        eighth of the state. Each width adds a set of frontier programs to
        compile (about 60 on the benchmark's network), hence two."""
        cap = self._delta_cap()
        return sorted({max(cap // 8, 128 if self.use_pallas else 8), cap})

    def _rows_width(self, r: int) -> int:
        """Rows a batch of ``r`` is padded to: a power of two >= 64, at most
        the table's n+1 rows. lo=64 keeps the set of distinct jit row-count
        signatures small; merging a few dozen dummy rows costs nothing."""
        return min(_pow2_pad(r, lo=64), self.n + 1)

    def _cand_width(self, p: int) -> int:
        """Candidate columns for rows with at most ``p`` affected inserts:
        a power of four >= 4, at most the delta cap."""
        w = 4
        while w < p:
            w *= 4
        return min(w, self._delta_cap())

    def _t_tiers(self) -> list[int]:
        """The BNS-degree buckets a round splits its rows by (8/32/128/tau'),
        see ``_bucket_parts``."""
        cap = self._nbr_ids.shape[1]
        return [b for b in (8, 32, 128) if b < cap] + [cap]

    def _t_widths(self) -> list[tuple[int, int]]:
        """(width, rows) for every width a part is dispatched at
        (``_t_bucket``'s pow4 widths), with the most rows such a part holds:
        the vertices of the part's bucket of degree at most that width."""
        deg = self._nbr_deg[: self.n]
        cap = self._nbr_ids.shape[1]
        bounds = [0] + self._t_tiers()
        out, t = [], 8
        while True:
            width = min(t, cap)
            lo = max(b for b in bounds if b < width)
            out.append((width, int(((deg > lo) & (deg <= width)).sum())))
            if t >= cap:
                return out
            t *= 4

    def _pad_rows(self, rows: np.ndarray) -> jax.Array:
        """Pad a row batch to ``_rows_width`` with the dummy row id n."""
        out = np.full(self._rows_width(len(rows)), self.n, np.int32)
        out[: len(rows)] = rows
        return self._upload(out)

    # host spans and the flush's transfer counts -------------------------

    def _span(self, name: str, **attrs):
        """A ``knn:<name>`` span added to this engine's totals
        (``stats()["spans"]``)."""
        return span(name, self._span_totals, **attrs)

    def _span_s(self, name: str) -> float:
        tot = self._span_totals.get("knn:" + name)
        return tot["s"] if tot else 0.0

    def _readback(self, x: jax.Array) -> np.ndarray:
        """Every blocking device->host readback of the flush path: one
        ``knn:flush.readback`` span (the host waiting on the device) and
        one count with its bytes."""
        with self._span("flush.readback", bytes=int(x.nbytes)):
            out = np.asarray(x)
        if self._io is not None:
            self._io["readbacks"] += 1
            self._io["readback_bytes"] += out.nbytes
        return out

    def _upload(self, x, sharding=None) -> jax.Array:
        """An explicit host->device upload, counted while a flush runs."""
        if self._io is not None:
            self._io["uploads"] += 1
            self._io["upload_bytes"] += x.nbytes
        return jax.device_put(x, sharding)

    # hooks the flush pipeline drives -----------------------------------

    def _padded_deletes(self, deletes: list[int]) -> np.ndarray:
        """Deleted-object ids padded with the dummy id n (never an object
        id, so never a hit) to the delta cap: one width, since it multiplies
        the purge-merge's (rows, candidates) signatures, and a delete costs
        the purge a compare per table entry of the rows it passes."""
        padded = np.full(self._delta_cap(), self.n, np.int32)
        padded[: len(deletes)] = deletes
        return padded

    def _table_snapshot(self) -> tuple:
        raise NotImplementedError

    def _restore_tables(self, snap: tuple) -> None:
        raise NotImplementedError

    def _table_bytes(self) -> int:
        """Device bytes of ONE table epoch (int32 ids + float32 dists)."""
        return (self.n + 1) * self.k * 8

    def _scan_delete_rows(self, deletes: list[int]) -> np.ndarray:
        raise NotImplementedError

    def _table_kth(self) -> np.ndarray:
        raise NotImplementedError

    def _purge_merge(self, rows, deletes, cand_ids, cand_d) -> None:
        raise NotImplementedError

    def _repair_part(self, part: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def _frontier_init(self, src: np.ndarray):
        raise NotImplementedError

    def _frontier_part(self, state, part: np.ndarray):
        raise NotImplementedError

    def _frontier_round(self, state, nbrs: np.ndarray):
        """One frontier round over receiver set ``nbrs``: bucket by BNS
        degree, run each part, resolve the changed masks once the whole
        round is queued. A mask may be a deferred readback (the sharded
        collective halo returns a thunk): resolving after the loop lets
        the later buckets' plan/upload work overlap the earlier buckets'
        device compute. The sharded engine overrides this wholesale on
        the collective path to fuse the round into one program."""
        pending = []
        for part in self._bucket_parts(nbrs):
            with self._span("flush.frontier.part", rows=int(part.size),
                            t=self._t_bucket(part)):
                state, changed_mask = self._frontier_part(state, part)
            pending.append((part, changed_mask))
        changed_parts = [
            p[(m() if callable(m) else m)[: p.size]] for p, m in pending
        ]
        return state, changed_parts

    def _frontier_extract(self, state, rows: np.ndarray, src: np.ndarray):
        raise NotImplementedError

    def _bucket_parts(self, rows: np.ndarray):
        """Split a row batch by BNS-degree width bucket (8/32/128/tau').

        Shared by the repair and frontier rounds: each part runs against the
        (n+1, t) adjacency slice of its bucket so the per-round candidate
        work is sized to the batch, not to the global tau'. The split is a
        pure function of the row ids, so the scalar and sharded engines
        partition identically (their round trajectories must match).
        """
        deg = self._nbr_deg[rows]
        prev = 0
        for t in self._t_tiers():
            part = rows[(deg > prev) & (deg <= t)]
            prev = t
            if part.size:
                yield part

    def _repair(self, rows: np.ndarray) -> int:
        """Jacobi repair rounds over the purged rows; returns the round count.

        Round 1 re-merges every purged row; later rounds only the frontier:
        a row can improve again only if a BNS neighbor's row changed last
        round (BN adjacency is symmetric, so BNS(changed) IS that set).
        The frontier collapses fast, so later rounds are tiny batches.
        Within a round, rows are split by BNS-degree width bucket so the
        candidate tensor is sized to the batch, not to the global tau'.
        Only the frontier's *vertex ids* survive a round boundary — the row
        data itself never leaves the owning table (or, sharded, the owning
        shard) between rounds.
        """
        self._nbr_tables()
        active = rows
        rounds = 0
        while active.size and rounds < _MAX_REPAIR_ROUNDS:
            rounds += 1
            with self._span("flush.repair.round", round=rounds, rows=int(active.size)):
                changed_parts = []
                for part in self._bucket_parts(active):
                    with self._span("flush.repair.part", rows=int(part.size),
                                    t=self._t_bucket(part)):
                        changed_mask = self._repair_part(part)
                    changed_parts.append(part[changed_mask[: part.size]])
                self._checkpoint("mid-repair-round")
                changed_rows = (
                    np.concatenate(changed_parts)
                    if changed_parts
                    else np.empty(0, np.int32)
                )
                if changed_rows.size == 0:
                    break
                active = self._repair_receivers(changed_rows, rows)
        else:
            if active.size:
                raise RuntimeError(
                    f"delete repair did not reach a fixpoint in "
                    f"{_MAX_REPAIR_ROUNDS} rounds"
                )
        return rounds

    def _frontier_pad_src(self, src: np.ndarray) -> np.ndarray:
        """Pad the staged-insert sources to the narrowest ``_src_widths``
        column count that holds them (-1 pads).

        Bounds the distinct jit signatures across flush sizes, exactly like
        ``_pad_rows`` does for row batches; the Pallas relax kernel wants a
        lane-aligned column count, so that path pads to 128 columns at least.
        """
        b = next(w for w in self._src_widths() if w >= len(src))
        out = np.full(b, -1, np.int32)
        out[: len(src)] = src
        return out

    def _insert_frontier(
        self, inserts: list[int]
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """Batched checkIns frontier on device: Algorithm 4 lines 1-8 for
        ALL staged inserts as one multi-source pruned-relaxation program.

        Round r relaxes the BNS edges of every vertex whose tentative
        distance changed in round r-1 (round 1: the sources themselves),
        pruned on device by the live k-th-distance column — the checkIns
        test ``d < kth[w]``. Only changed-row masks and, after convergence,
        the affected rows' distance tiles cross the host boundary; the
        (n,) kth column never does. Returns ``(rows, cand_ids, cand_d,
        rounds)``: the affected rows (sorted) with their per-row compacted
        (inserted object, exact distance) candidate lists — the same
        contract as the ``frontier = "host"`` pipeline, which it is
        property-tested exact-set-equal against (the pruned-relaxation
        fixpoint is schedule-independent, so the Dijkstra oracle and these
        Jacobi rounds land on identical sets and distances).
        """
        self._nbr_tables()
        src = np.asarray(inserts, np.int32)
        state = self._frontier_init(src)
        active = np.unique(src)
        touched = [active]
        rounds = 0
        while active.size and rounds < _MAX_REPAIR_ROUNDS:
            rounds += 1
            with self._span("flush.frontier.round", round=rounds, rows=int(active.size)):
                nbrs = self._expand_receivers(active)
                state, changed_parts = self._frontier_round(state, nbrs)
                active = (
                    np.concatenate(changed_parts)
                    if changed_parts
                    else np.empty(0, np.int32)
                )
            if active.size:
                touched.append(active)
        if active.size:
            raise RuntimeError(
                f"checkIns frontier did not reach a fixpoint in "
                f"{_MAX_REPAIR_ROUNDS} rounds"
            )
        rows = np.unique(np.concatenate(touched)).astype(np.int32)
        aff, dvals = self._frontier_extract(state, rows, src)
        return (*self._compact_candidates(rows, aff, dvals, src), rounds)

    def _repair_receivers(
        self, changed: np.ndarray, rows: np.ndarray
    ) -> np.ndarray:
        """Next repair round's active set: the BNS neighborhoods of the
        rows that changed, narrowed to the purged batch. BN adjacency is
        symmetric, so BNS(changed) IS the set of rows that can improve.
        The sharded engine overrides this to expand the neighborhood on
        device when ``halo == "collective"`` — the set is identical (the
        packed BNS adjacency is exactly lo ∪ hi), only where the set
        algebra runs moves."""
        nbrs = np.unique(
            np.concatenate(
                [self.bn.lo_ids[changed].ravel(),
                 self.bn.hi_ids[changed].ravel()]
            )
        )
        return np.intersect1d(nbrs[nbrs >= 0], rows).astype(np.int32)

    def _expand_receivers(self, active: np.ndarray) -> np.ndarray:
        """Next round's receiver set: the union of BNS neighborhoods of the
        changed vertices, via the packed adjacency's CSR triple (touches
        exactly the live edges, no padded columns)."""
        starts = self._nbr_indptr[active]
        counts = self._nbr_indptr[active + 1] - starts
        total = int(counts.sum())
        if total == 0:
            return np.empty(0, np.int32)
        exc = np.concatenate([[0], np.cumsum(counts)[:-1]])
        idx = np.repeat(starts - exc, counts) + np.arange(total)
        return np.unique(self._nbr_indices[idx]).astype(np.int32)

    def _compact_candidates(
        self, rows: np.ndarray, aff: np.ndarray, dvals: np.ndarray, src: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(touched rows, (R, B) affected mask + distances) -> the flush's
        per-row candidate arrays: affected columns compacted to the front in
        source order, width padded to ``_cand_width`` — the exact layout the
        host frontier builds, so ``_purge_merge`` sees identical inputs
        either way."""
        keep = aff.any(axis=1)
        rows, aff, dvals = rows[keep], aff[keep], dvals[keep]
        if rows.size == 0:
            return rows, np.empty((0, 1), np.int32), np.empty((0, 1), np.float32)
        p = self._cand_width(int(aff.sum(axis=1).max()))
        if p > aff.shape[1]:
            pad = ((0, 0), (0, p - aff.shape[1]))
            aff = np.pad(aff, pad)
            dvals = np.pad(dvals, pad, constant_values=np.inf)
            src = np.pad(src, (0, p - len(src)), constant_values=-1)
        order = np.argsort(~aff, axis=1, kind="stable")[:, :p]
        taken = np.take_along_axis(aff, order, axis=1)
        cand_ids = np.where(taken, src[order], -1).astype(np.int32)
        cand_d = np.where(
            taken, np.take_along_axis(dvals, order, axis=1), np.inf
        ).astype(np.float32)
        return rows, cand_ids, cand_d

    def _insert_frontier_host(
        self, inserts: list[int]
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """The pre-batching checkIns pipeline: one sequential host heap
        search per staged insert (``insert_affected_set``, shared with the
        scalar oracle) fed by a full (n,) k-th-distance readback. Kept as
        the ``frontier = "host"`` baseline the exp14 benchmark measures the
        device pipeline against, and as the property tests' twin."""
        kth = self._table_kth()
        per_row: dict[int, list[tuple[int, float]]] = {}
        for u in inserts:
            affected = insert_affected_set(self.bn, lambda v: float(kth[v]), u)
            for v, d in affected.items():
                per_row.setdefault(v, []).append((u, d))
        rows = np.fromiter(sorted(per_row), np.int32, len(per_row))
        if rows.size == 0:
            return rows, np.empty((0, 1), np.int32), np.empty((0, 1), np.float32), 0
        p = self._cand_width(max(len(c) for c in per_row.values()))
        cand_ids = np.full((len(rows), p), -1, np.int32)
        cand_d = np.full((len(rows), p), np.inf, np.float32)
        for i, v in enumerate(rows.tolist()):
            for j, (u, d) in enumerate(per_row[v]):
                cand_ids[i, j] = u
                cand_d[i, j] = d
        return rows, cand_ids, cand_d, 0

    def _coalesced_moves(self, deletes: set, inserts: set) -> list[tuple[int, int]]:
        """Fold the staged queue's move chains to (origin, endpoint) pairs.

        Only chains whose origin is a net delete AND whose endpoint is a net
        insert count as moves — everything else has already coalesced away in
        the object-set delta (a chain that returns home, a moved-then-deleted
        object, ...). Purely a classification for the stats dict: the applied
        work is always the net set delta.
        """
        chain: dict[int, int] = {}  # current endpoint -> chain origin
        for op in self._staged:
            if op[0] == "mov":
                _, u, v = op
                chain[v] = chain.pop(u, u)
            else:
                chain.pop(op[1], None)  # a delete at the endpoint kills the chain
        # Two chains can share an origin (move away, re-insert at the origin,
        # move away again), so pair each origin/endpoint at most once.
        avail_o, avail_c = set(deletes), set(inserts)
        moves = []
        for c, o in sorted(chain.items()):
            if o != c and o in avail_o and c in avail_c:
                moves.append((o, c))
                avail_o.discard(o)
                avail_c.discard(c)
        return moves

    def flush_updates(self) -> dict:
        """Apply the staged queue as one fused vectorized device batch.

        The queue is coalesced to its net object-set delta (the index is a
        pure function of the final object set — Theorems 6.2/6.4 make the
        sequential replay land on the same tables; see the module docstring
        for the per-object folding rules). Application: find the delete-hit
        rows, run the batched device checkIns frontier for ALL insertions at
        once against the pre-update k-th distances (insert-first semantics —
        see the inline comment; ``self.frontier = "host"`` selects the
        per-object baseline pipeline instead), purge + merge the union of
        both row sets in one ``rows_purge_merge`` pass, then repair the
        deletion holes with breadth-first Jacobi rounds that source- and
        destination-side work share. Returns the per-flush stats dict (net
        insert/delete/move counts plus ``coalesced``, the staged ops the
        folding eliminated, and the frontier/repair round counts).

        An engine's first flush first compiles every program a flush can
        dispatch (``_warm_flush``), so no later flush compiles; a delta
        larger than the delta cap is applied in successive deltas of at
        most the cap, and published once.

        Each phase runs in a ``knn:flush.*`` span (``repro.core.spans``);
        ``stats()`` carries the span totals (``t_frontier_s`` and
        ``t_repair_s`` read them) and the cumulative transfer counts
        (``flush_readbacks`` ...), and ``epoch_stats(e)`` the flush's own
        (``t_wall_s``, ``readbacks`` ...).
        """
        staged = len(self._staged)
        del_set = self._objects - self._pending
        ins_set = self._pending - self._objects
        deletes = sorted(del_set)
        inserts = sorted(ins_set)
        moves = self._coalesced_moves(del_set, ins_set)
        n_pure_ins = len(inserts) - len(moves)
        n_pure_del = len(deletes) - len(moves)
        new_epoch = self.epoch + 1

        self._io = dict.fromkeys(_IO_KEYS, 0)
        try:
            with self._span("flush", epoch=new_epoch, staged=staged,
                            inserts=len(inserts), deletes=len(deletes)) as sp:
                if not self._flush_warmed:
                    self._warm_flush()
                    self._flush_warmed = True
                purged, merged, rounds, f_rounds = self._apply_delta(
                    deletes, inserts, new_epoch
                )
        finally:
            io, self._io = self._io, None
            for key, value in io.items():
                self._stats["flush_" + key] += value

        self._stats["flushes"] += 1
        self._stats["inserts_applied"] += n_pure_ins
        self._stats["deletes_applied"] += n_pure_del
        self._stats["moves_applied"] += len(moves)
        self._stats["coalesced"] += staged - (n_pure_ins + n_pure_del + len(moves))
        self._stats["rows_repaired"] += purged + merged
        self._stats["repair_rounds_last"] = rounds
        self._stats["frontier_rounds_last"] = f_rounds
        result = {
            "staged": staged,
            "inserts": n_pure_ins,
            "deletes": n_pure_del,
            "moves": len(moves),
            "coalesced": staged - (n_pure_ins + n_pure_del + len(moves)),
            "rows_purged": purged,
            "rows_merged": merged,
            "repair_rounds": rounds,
            "frontier_rounds": f_rounds,
        }
        self._epoch_stats[new_epoch] = {
            "origin": "flush",
            "flush": dict(result),
            "t_wall_s": sp.s,
            **io,
        }
        self._trim_epoch_stats()
        self._checkpoint("post-swap")
        if sanitize.enabled():
            ids_h, d_h = self._host_tables()
            sanitize.scan_tables(
                ids_h, d_h, self.n, context=f"flush -> epoch {new_epoch}"
            )
        return result

    def _warm_flush(self) -> None:
        """Compile every program a flush of this engine can dispatch (the
        first flush calls it, before its work). The sharded engine lists no
        set (module docstring), so nothing here."""

    def _flush_guard(self):
        """Sanitizer rail: the device flush pipeline runs under the transfer
        guard (all uploads must be explicit device_puts); the "host"
        frontier is the measured host baseline, exempt by definition."""
        if self._frontier == "device":
            return sanitize.guard("flush")
        return contextlib.nullcontext()

    @contextlib.contextmanager
    def _rollback_on_failure(self, base: tuple):
        """Any failure (a device error, or a chaos hook's simulated kill)
        rolls the working references back to the published epoch ``base``
        with the staged queue intact — the flush is retryable and serving
        never stops."""
        try:
            yield
        except BaseException:
            self._restore_tables(base)
            self._stats["flushes_failed"] += 1
            raise

    def _apply_phases(
        self, deletes: list[int], inserts: list[int]
    ) -> tuple[int, int, int, int]:
        """One delta's phases on the working tables. Returns (rows purged,
        rows merged, repair rounds, frontier rounds)."""
        # -- delete side: which rows name a deleted object (device scan) --
        purged_rows = np.empty(0, np.int32)
        if deletes:
            with self._span("flush.scan"):
                purged_rows = self._scan_delete_rows(deletes)

        # -- insert side: batched checkIns frontier, insert-first semantics --
        # The frontier prunes against the CURRENT (pre-update) k-th bounds,
        # exactly Algorithm 4 run before Algorithm 5 (the same order the
        # scalar ``move_object`` oracle uses). A row the pruning misses that
        # still needs a new object in the *final* tables must have had its
        # k-th distance raised by the deletions — i.e. it lost an entry, so
        # it is in the purge set and the repair rounds rebuild it from its
        # bridge neighbors anyway. Keeping the pre-update bounds keeps the
        # frontier as tight as the oracle's, instead of the unpruned sweep a
        # post-purge (unbounded) k-th would trigger.
        f_rounds = 0
        frows = np.empty(0, np.int32)
        fc_ids = fc_d = None
        if inserts:
            provider = (
                self._insert_frontier_host
                if self.frontier == "host"
                else self._insert_frontier
            )
            with self._span("flush.frontier"):
                frows, fc_ids, fc_d, f_rounds = provider(inserts)

        # -- one fused purge + merge over the union of both row sets --
        rounds = 0
        if purged_rows.size or frows.size:
            with self._span("flush.purge_merge"):
                rows = np.union1d(purged_rows, frows).astype(np.int32)
                p = fc_ids.shape[1] if frows.size else self._cand_width(1)
                cand_ids = np.full((len(rows), p), -1, np.int32)
                cand_d = np.full((len(rows), p), np.inf, np.float32)
                if frows.size:
                    pos = np.searchsorted(rows, frows)
                    cand_ids[pos] = fc_ids
                    cand_d[pos] = fc_d
                self._purge_merge(rows, deletes, cand_ids, cand_d)
            # -- breadth-first repair of the deletion holes (shared frontier) --
            if purged_rows.size:
                with self._span("flush.repair"):
                    rounds = self._repair(purged_rows)
        return int(purged_rows.size), int(frows.size), rounds, f_rounds

    def _apply_delta(
        self, deletes: list[int], inserts: list[int], new_epoch: int
    ) -> tuple[int, int, int, int]:
        """The flush's phases, once per delta of at most the delta cap, then
        the publish of ``new_epoch``. Returns (rows purged, rows merged,
        repair rounds, frontier rounds), summed over the deltas."""
        # Epoch e+1 is built on the working references; the published epoch
        # e snapshot keeps its own references to the old buffers, so queries
        # dispatched anywhere in here still read a whole epoch.
        base = self._epochs.snapshot()
        size = max(len(deletes), len(inserts))
        cap = self._delta_cap()
        totals = np.zeros(4, np.int64)
        with self._rollback_on_failure(base), self._flush_guard():
            # each delta is a valid object-set change (the net deletes and
            # inserts are disjoint), and the tables after it are exact for
            # its object set, so the last one's are the flush's
            for i in range(0, size, cap):
                totals += self._apply_phases(deletes[i:i + cap], inserts[i:i + cap])

        # -- staged layout changes (repartition-on-flush) ride the same
        # epoch: the hook re-lays the working tables so the swap below
        # publishes tables AND layout atomically; then the swap and the
        # journal segment's commit
        with self._span("flush.publish"):
            with self._rollback_on_failure(base):
                with self._flush_guard():
                    self._prepare_publish()
                self._checkpoint("pre-swap")
            self._objects = set(self._pending)
            self._staged.clear()
            self._publish_epoch(new_epoch)
            if self._journal is not None:
                self._journal.commit(new_epoch)
        return tuple(int(x) for x in totals)

    # ------------------------------------------------------------------
    # persistence / stats
    # ------------------------------------------------------------------

    def _host_tables(self) -> tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError

    def _save_meta(self) -> dict:
        return {"shards": 1}

    def save(self, path) -> None:
        """Write the index artifact: one npz shared by build and serving.

        Saving with a non-empty staged queue raises ``ArtifactError`` (rather
        than silently flushing): staged updates are invisible to queries, so
        an implicit flush would make the saved artifact disagree with what
        the engine was serving at save time. Call ``flush_updates()`` first;
        the tables are then exactly the flushed state and round-trip
        bit-identically through ``load``.

        The stored tables are always the *logical* (n, k) layout in vertex
        order — shard padding is stripped — so an artifact saved by a
        sharded engine at N shards loads into a scalar engine or a sharded
        engine at M shards (reshard-on-load); the writer's shard count is
        recorded in the meta as provenance. The meta also carries a content
        checksum over (ids, dists, objects) that ``load_artifact`` verifies,
        so a corrupted file raises instead of serving garbage tables.

        If a journal is attached it is truncated AFTER the artifact is
        written: the artifact now embodies every committed record (staged
        queue is empty here), so the journal restarts empty.
        """
        if self._staged:
            raise ArtifactError(
                "flush_updates() before save(): staged updates pending"
            )
        ids, dists = self._host_tables()
        objects = self.objects
        meta = {
            "format": _FORMAT,
            "version": _FORMAT_VERSION,
            "n": self.n,
            "k": self.k,
            "epoch": self.epoch,
            "checksum": _tables_checksum(ids, dists, objects),
            **self._save_meta(),
        }
        np.savez_compressed(
            path,
            ids=ids,
            dists=dists,
            k=np.int64(self.k),
            objects=objects,
            meta=np.bytes_(json.dumps(meta).encode()),
        )
        if self._journal is not None:
            self._journal.truncate()

    def _extra_stats(self) -> dict:
        return {}

    def stats(self) -> dict:
        """Serving counters (merged into benchmark/serve JSON output)."""
        retained = self.retained_epochs()
        return {
            "n": self.n,
            "k": self.k,
            "num_objects": len(self._objects),
            "staged_queue_depth": len(self._staged),
            "epoch": self.epoch,
            "epochs_retained": len(retained),
            "keep_epochs": self.keep_epochs,
            "epoch_table_bytes": len(retained) * self._table_bytes(),
            **self._extra_stats(),
            **self._stats,
            "t_frontier_s": self._span_s("flush.frontier"),
            "t_repair_s": self._span_s("flush.repair"),
            "spans": {name: dict(tot) for name, tot in self._span_totals.items()},
        }


def load_artifact(path) -> tuple[np.ndarray, np.ndarray, int, np.ndarray, dict]:
    """Read a ``save``/``knn_build --out`` npz: (ids, dists, k, objects, meta).

    Accepts the pre-engine ``knn_build`` npz too (no object set stored):
    M is recovered as the distance-0 entries — every object is its own
    0-th nearest neighbor, so exactly the objects appear at distance 0.

    Robustness (all raise ``ArtifactError``): a truncated or otherwise
    unreadable npz; a schema version newer than this code (forward skew —
    refusing beats misreading fields that did not exist yet); a content
    checksum that no longer matches the stored tables (bit rot, torn
    write). v1/v2 artifacts carry no checksum and load unverified.
    """
    try:
        with np.load(path) as z:
            ids = z["ids"]
            dists = z["dists"]
            k = int(z["k"])
            if "objects" in z.files:
                objects = z["objects"]
            else:
                objects = np.unique(ids[dists == 0.0])
                objects = objects[objects >= 0]
            meta = json.loads(bytes(z["meta"])) if "meta" in z.files else {}
    except (
        OSError,
        ValueError,
        EOFError,
        KeyError,
        zlib.error,
        zipfile.BadZipFile,
    ) as e:
        raise ArtifactError(f"{path}: truncated or corrupt artifact ({e})") from e
    version = int(meta.get("version", 1))
    if version > _FORMAT_VERSION:
        raise ArtifactError(
            f"{path}: artifact schema version {version} is newer than this "
            f"code understands (max {_FORMAT_VERSION}); refusing to guess"
        )
    if "checksum" in meta:
        got = _tables_checksum(ids, dists, objects)
        if got != int(meta["checksum"]):
            raise ArtifactError(
                f"{path}: content checksum mismatch "
                f"(stored {meta['checksum']}, computed {got}) — the file is "
                f"corrupt; rebuild or restore from a good copy"
            )
    return ids, dists, k, objects, meta


class QueryEngine(EngineCore):
    """Batched kNN serving over device-resident index tables (see module doc)."""

    def __init__(
        self,
        ids: np.ndarray | jax.Array,
        dists: np.ndarray | jax.Array,
        k: int,
        objects,
        *,
        bn: BNGraph | None = None,
        use_pallas: bool = False,
    ):
        self.n, self._vk_ids, self._vk_d = self.normalize_tables(ids, dists, k, bn)
        # every flush signature compiled or dispatched (``_flush_signatures``)
        self._flush_programs: set[tuple] = set()
        super().__init__(k, objects, bn=bn, use_pallas=use_pallas)

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
        use_pallas: bool = False,
    ) -> "QueryEngine":
        """Construct on device (Algorithm 3 fused sweeps) and serve in place:
        the sweep result tables become the engine's live tables, no readback."""
        vk_ids, vk_d = build_knn_tables_jax(bn, objects, k, use_pallas=use_pallas)
        return cls(vk_ids, vk_d, k, objects, bn=bn, use_pallas=use_pallas)

    @classmethod
    def from_index(
        cls,
        index: KNNIndex,
        objects,
        *,
        bn: BNGraph | None = None,
        use_pallas: bool = False,
    ) -> "QueryEngine":
        """Upload a host ``KNNIndex`` (e.g. an oracle-built one)."""
        dists = np.where(index.ids >= 0, index.dists, np.inf).astype(np.float32)
        return cls(index.ids, dists, index.k, objects, bn=bn, use_pallas=use_pallas)

    def to_index(self) -> KNNIndex:
        """Read the tables back into the host ``KNNIndex`` view (oracle dtype)."""
        ids = np.array(self._vk_ids[: self.n])
        dists = np.where(ids >= 0, np.asarray(self._vk_d[: self.n], np.float64), np.inf)
        return KNNIndex(ids=ids, dists=dists, k=self.k)

    @property
    def tables(self) -> tuple[jax.Array, jax.Array]:
        """The live device (n+1, k) id/dist tables (dummy row last)."""
        return self._vk_ids, self._vk_d

    # ------------------------------------------------------------------
    # device hooks (single-device layout)
    # ------------------------------------------------------------------

    def _table_snapshot(self) -> tuple[jax.Array, jax.Array]:
        # JAX arrays are immutable and the flush pipeline reassigns the
        # working refs rather than writing through them, so a snapshot is
        # just the pair of references — zero-copy epoch retention.
        return self._vk_ids, self._vk_d

    def _restore_tables(self, snap: tuple) -> None:
        self._vk_ids, self._vk_d = snap

    def _gather_batch(self, us: np.ndarray, ks: jax.Array, snap: tuple, epoch: int):
        gather = ops.answer_program(ops.serve_gather)
        return gather(snap[0], snap[1], jax.device_put(us), ks)

    # the closed flush program set --------------------------------------

    def _flush_signatures(self) -> list[tuple]:
        """Every (program, shape) a flush of this engine can dispatch, from
        the tier helpers the flush pads with: the padded deletes are the
        delta cap, the sources B each of ``_src_widths``; the candidate
        widths are ``_cand_width``'s up to the cap; rows are
        ``_rows_width``'s up to n, and for a round's part at width t up to
        the vertices it can hold (``_t_widths``: a part holds each vertex
        once)."""
        self._nbr_tables()
        srcs = self._src_widths()
        sigs = [("rows_containing", self._delta_cap())]
        sigs += [("frontier_init", b) for b in srcs]
        for t, rows in self._t_widths():
            for r in _tiers(self._rows_width, rows):
                sigs += [("frontier_round", t, r, b) for b in srcs]
                sigs.append(("repair_round", t, r))
        for r in _tiers(self._rows_width, self.n):
            sigs += [("frontier_affected", r, b) for b in srcs]
            sigs += [("rows_purge_merge", r, p)
                     for p in _tiers(self._cand_width, self._delta_cap())]
        return sigs

    def _flush_program(self, sig: tuple):
        """The program of ``sig`` with the abstract arguments and static
        keywords a flush calls it with, argument for argument as the
        dispatch passes them: ``(fn, args, kwargs)``."""
        name, *dims = sig
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
        n1 = self.n + 1
        ids, d = i32(n1, self.k), f32(n1, self.k)
        if name == "rows_containing":
            return ops.rows_containing, (ids, i32(*dims)), {}
        if name == "frontier_init":
            return _frontier_init_prog, (i32(*dims), n1), {}
        if name == "frontier_affected":
            r, b = dims
            return _frontier_affected, (i32(r), f32(n1, b), d, i32(b)), {}
        if name == "rows_purge_merge":
            r, p = dims
            args = (ids, d, i32(r), i32(self._delta_cap()), i32(r, p), f32(r, p), self.k)
            return ops.rows_purge_merge, args, {"use_pallas": self.use_pallas,
                                                "sorted_rows": True}
        t, r, *b = dims
        nbr, w = i32(self._nbr_ids.shape[0], t), f32(self._nbr_ids.shape[0], t)
        if name == "frontier_round":
            return _frontier_round, (nbr, w, i32(r), f32(n1, *b), d, i32(*b),
                                     self.use_pallas), {}
        return _repair_round, (nbr, w, i32(r), ids, d), {}

    def _lower(self, sig: tuple):
        """The program of ``sig`` lowered on the shapes a flush calls it
        with (``_flush_program``)."""
        fn, args, kwargs = self._flush_program(sig)
        return fn.lower(*args, **kwargs)

    def _warm_flush(self) -> None:
        """Compile every flush signature (``_flush_signatures``), the
        backend compiles in parallel: lowering holds the interpreter, the
        compile does not. Runs nothing and touches no table. A second
        engine of the same shapes finds them in jax's own caches."""
        if self.bn is None:
            return
        sigs = self._flush_signatures()
        with self._span("flush.warm", programs=len(sigs)):
            lowered = [self._lower(sig) for sig in sigs]
            with ThreadPoolExecutor(os.cpu_count()) as pool:
                list(pool.map(lambda low: low.compile(), lowered))
        self._flush_programs.update(sigs)

    def _dispatched(self, *sig) -> None:
        self._flush_programs.add(sig)

    def _extra_stats(self) -> dict:
        # distinct flush signatures compiled or dispatched so far: after the
        # first flush, len(_flush_signatures()), and it never grows
        return {"flush_programs": len(self._flush_programs)}

    def _scan_delete_rows(self, deletes: list[int]) -> np.ndarray:
        del_arr = self._upload(self._padded_deletes(deletes))
        self._dispatched("rows_containing", del_arr.shape[0])
        hit = self._readback(ops.rows_containing(self._vk_ids, del_arr))
        return np.flatnonzero(hit).astype(np.int32)

    def _table_kth(self) -> np.ndarray:
        return self._readback(self._vk_d[: self.n, -1]).astype(np.float64)

    def _purge_merge(self, rows, deletes, cand_ids, cand_d) -> None:
        r_pad = self._rows_width(len(rows))
        pad = ((0, r_pad - len(rows)), (0, 0))
        cand_ids = np.pad(cand_ids, pad, constant_values=-1)
        cand_d = np.pad(cand_d, pad, constant_values=np.inf)
        self._dispatched("rows_purge_merge", r_pad, cand_ids.shape[1])
        self._vk_ids, self._vk_d = ops.rows_purge_merge(
            self._vk_ids, self._vk_d, self._pad_rows(rows),
            self._upload(self._padded_deletes(deletes)),
            self._upload(cand_ids), self._upload(cand_d), self.k,
            use_pallas=self.use_pallas, sorted_rows=True,
        )

    def _repair_part(self, part: np.ndarray) -> np.ndarray:
        nbr_tab, w_tab = self._nbr_slice(self._t_bucket(part))
        rows = self._pad_rows(part)
        self._dispatched("repair_round", nbr_tab.shape[1], rows.shape[0])
        self._vk_ids, self._vk_d, changed_mask = _repair_round(
            nbr_tab, w_tab, rows, self._vk_ids, self._vk_d
        )
        return self._readback(changed_mask)

    # frontier provider (single-device layout): the multi-source tentative
    # distance state is one (n+1, B) device matrix; the pruning column is
    # read straight off the live table inside the jitted round program, so
    # no kth values ever cross the host boundary.

    def _frontier_init(self, src: np.ndarray) -> jax.Array:
        self._fsrc = self._upload(self._frontier_pad_src(src))
        self._dispatched("frontier_init", self._fsrc.shape[0])
        return _frontier_init_prog(self._fsrc, self._vk_ids.shape[0])

    def _frontier_part(self, state, part: np.ndarray):
        nbr_tab, w_tab = self._nbr_slice(self._t_bucket(part))
        rows = self._pad_rows(part)
        self._dispatched("frontier_round", nbr_tab.shape[1], rows.shape[0],
                         self._fsrc.shape[0])
        state, changed = _frontier_round(
            nbr_tab, w_tab, rows, state, self._vk_d, self._fsrc, self.use_pallas,
        )
        return state, self._readback(changed)

    def _frontier_extract(self, state, rows: np.ndarray, src: np.ndarray):
        padded = self._pad_rows(rows)
        self._dispatched("frontier_affected", padded.shape[0], self._fsrc.shape[0])
        aff, d = _frontier_affected(padded, state, self._vk_d, self._fsrc)
        b = len(src)
        return self._readback(aff)[: len(rows), :b], self._readback(d)[: len(rows), :b]

    def _host_tables(self) -> tuple[np.ndarray, np.ndarray]:
        return np.asarray(self._vk_ids[: self.n]), np.asarray(self._vk_d[: self.n])

    @classmethod
    def load(
        cls,
        path,
        *,
        bn: BNGraph | None = None,
        use_pallas: bool = False,
        journal=None,
    ) -> "QueryEngine":
        """Load a ``save``/``knn_build --out`` artifact. ``bn`` enables updates.

        Accepts v1 artifacts and the pre-engine ``knn_build`` npz (see
        ``load_artifact``); shard meta from a sharded writer is ignored —
        the stored tables are always the logical vertex-order layout.

        ``journal`` (path or ``UpdateJournal``) attaches a write-ahead
        journal and REPLAYS it first: updates journaled after the artifact
        was saved — committed flushes and the uncommitted tail — are rolled
        forward through the staged path, recovering exactly the tables a
        killed process was serving (see ``attach_journal``). Requires
        ``bn`` when the journal is non-empty.
        """
        ids, dists, k, objects, _ = load_artifact(path)
        eng = cls(
            ids, dists.astype(np.float32), k, objects, bn=bn, use_pallas=use_pallas
        )
        if journal is not None:
            eng.attach_journal(journal)
        return eng


@functools.partial(jax.jit, static_argnames=("n1",))
def _frontier_init_prog(src, n1: int):
    """Allocate the (n+1, B) multi-source tentative-distance matrix: +inf
    everywhere except 0 at (src[i], i). Padded source columns (src = -1)
    park their zero on the dummy row, which is +inf by convention and never
    read unclamped, so they stay inert."""
    b = src.shape[0]
    dist = jnp.full((n1, b), jnp.inf, jnp.float32)
    rows = jnp.where(src >= 0, src, n1 - 1)
    vals = jnp.where(src >= 0, 0.0, jnp.inf).astype(jnp.float32)
    return dist.at[rows, jnp.arange(b)].set(vals)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _frontier_round(nbr_tab, w_tab, rows, dist, vk_d, src, use_pallas: bool):
    """One jitted frontier round: gather the receiver rows' BNS slices, run
    ``ops.frontier_relax`` against the live table's k-th column (device
    resident — sliced inside the program), and derive the changed mask that
    narrows the next round's receiver set. Distances only ever decrease, so
    ``new < old`` is exactly "changed". Every row batch of a flush is
    ascending (its pads, the dummy row n, last), so the writes say so."""
    nbr = nbr_tab[rows]
    w = w_tab[rows]
    kth = vk_d[:, -1]
    new = ops.frontier_relax(nbr, rows, w, dist, kth, src, use_pallas=use_pallas,
                             sorted_rows=True)
    changed = jnp.any(new[rows] < dist[rows], axis=1)
    return new, changed


@jax.jit
def _frontier_affected(rows, dist, vk_d, src):
    """Affected test for the touched rows after convergence: checkIns
    against the k-th column, plus the source rows themselves (Algorithm 4
    admits the inserted object unconditionally). Returns the (R, B) mask
    and the distance tile — the only frontier data read back to host."""
    kth = vk_d[:, -1]
    d = dist[rows]
    aff = (d < kth[rows][:, None]) | (rows[:, None] == src[None, :])
    return aff, d


@jax.jit
def _repair_round(nbr_tab, w_tab, rows, vk_ids, vk_d):
    """One Jacobi repair round: every row in ``rows`` re-merges its own
    entries (extras tables = the live tables themselves) with its bridge
    neighbors' rows; returns the per-row changed mask the caller uses to
    narrow the next round's frontier (Jacobi: see the module docstring).
    The rows ascend, as in ``_frontier_round``.

    One gather of many k-wide table rows takes the TPU's compiler a time
    that grows with its number of indices (36 s at 131,072 x 8), so past
    ``_GATHER_INDICES`` the rows merge a block at a time, each block's
    gather within it; every block reads the pre-round tables.
    """
    k = vk_ids.shape[1]
    r, t = rows.shape[0], nbr_tab.shape[1]

    def merge(part):
        return ops.sweep_merge_rows(nbr_tab[part], part, w_tab[part], vk_ids, vk_d,
                                    vk_ids, vk_d, k, use_pallas=False)

    rb = max(8, _GATHER_INDICES // t // 8 * 8)
    if r <= rb:
        m_ids, m_d = merge(rows)
    else:
        def block(j, acc):
            # the last block ends at row r, overlapping the one before it:
            # its rows are merged twice, to the same values
            start = jnp.minimum(j * rb, r - rb)
            got = merge(jax.lax.dynamic_slice_in_dim(rows, start, rb))
            return tuple(jax.lax.dynamic_update_slice_in_dim(a, g, start, axis=0)
                         for a, g in zip(acc, got))

        m_ids, m_d = jax.lax.fori_loop(
            0, -(-r // rb), block,
            (jnp.zeros((r, k), vk_ids.dtype), jnp.zeros((r, k), vk_d.dtype)))
    new_ids = vk_ids.at[rows].set(m_ids, indices_are_sorted=True)
    new_d = vk_d.at[rows].set(m_d, indices_are_sorted=True)
    changed = jnp.any(
        (new_ids[rows] != vk_ids[rows]) | (new_d[rows] != vk_d[rows]), axis=1
    )
    return new_ids, new_d, changed
