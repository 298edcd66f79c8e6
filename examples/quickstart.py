"""Quickstart: the paper end to end in under a minute on CPU.

    PYTHONPATH=src python examples/quickstart.py

Builds a synthetic road network, constructs the KNN-Index with the
bidirectional algorithm (host reference AND the TPU-style level-synchronous
sweeps), answers queries progressively, maintains the index through object
insertions/deletions, serves batched traffic through the ``repro.knn``
QueryEngine facade, runs the moving-fleet workload (vehicles on shortest-path
trips whose per-tick moves are staged with ``stage_move`` and flushed as one
fused device batch between query batches), and finishes with the durability
surface: epoch-versioned snapshot-isolated flushes, pinned time-travel reads,
and write-ahead-journal crash recovery.
"""
import os
import tempfile

import numpy as np

from repro import knn
from repro.core.bngraph import build_bngraph
from repro.core.construct_jax import build_knn_index_jax, prepare_sweep
from repro.core.index import indices_equivalent
from repro.core.reference import knn_index_cons_plus
from repro.core.updates import delete_object, insert_object
from repro.graph.generators import pick_objects, road_network


def main():
    k = 10
    print("== 1. road network ==")
    g = road_network(40, 40, seed=0)
    objects = pick_objects(g.n, mu=0.02, seed=0)
    print(f"n={g.n} m={g.m} |M|={len(objects)} k={k}")

    print("\n== 2. BN-Graph (Algorithm 1) ==")
    bn = build_bngraph(g)
    plan = prepare_sweep(bn, "up")
    print(f"rho={bn.rho} tau={bn.tau} levels={plan.num_levels} "
          f"chunks={plan.num_chunks} shape-buckets={len(plan.buckets)} "
          f"pad-occupancy={plan.occupancy:.2f}")

    print("\n== 3. construction: Algorithm 3 (host) vs level-sync sweeps (device) ==")
    idx_host = knn_index_cons_plus(bn, objects, k)
    idx_dev = build_knn_index_jax(bn, objects, k, use_pallas=False)
    print(f"identical results: {indices_equivalent(idx_host, idx_dev)}")
    print(f"index size: {idx_dev.size_bytes(dist_bytes=4) / 1024:.1f} KiB "
          f"(= n*k*8 bytes on device, Theorem 4.5)")

    print("\n== 4. queries (O(k), progressive) ==")
    u = 777
    print(f"kNN({u}) = {idx_dev.query(u, 5)}")
    print("progressive:", end=" ")
    for i, (v, d) in enumerate(idx_dev.query_progressive(u, 3)):
        print(f"#{i + 1}:({v},{d:.0f})", end=" ")
    print()

    print("\n== 5. maintenance (Algorithms 4/5) ==")
    new_obj = int(np.setdiff1d(np.arange(g.n), objects)[0])
    delta = insert_object(bn, idx_dev, new_obj)
    print(f"insert {new_obj}: {delta} rows touched; kNN({u}) = {idx_dev.query(u, 5)}")
    delta = delete_object(bn, idx_dev, new_obj)
    print(f"delete {new_obj}: {delta} rows touched")
    print(f"back to original: {indices_equivalent(idx_host, idx_dev)}")

    print("\n== 6. serving (repro.knn facade: batched device-resident engine) ==")
    engine = knn.build_engine(bn, objects, k)
    us = np.arange(0, g.n, 7, dtype=np.int32)
    ids, dists = engine.query_batch(us)              # one gather, whole batch
    print(f"query_batch({len(us)} queries): ids {ids.shape}, "
          f"first row {np.asarray(ids[0, :3]).tolist()}")
    for prefix_ids, _ in engine.query_progressive_batch(us[:4], 3):
        pass                                          # first-i prefixes, one gather
    print(f"progressive prefixes up to i={prefix_ids.shape[1]} for "
          f"{prefix_ids.shape[0]} queries")
    engine.stage_insert(new_obj)                      # queued, not yet visible
    print(f"staged queue depth: {engine.queue_depth}; "
          f"flush: {engine.flush_updates()}")
    path = os.path.join(tempfile.mkdtemp(), "index.npz")
    engine.save(path)                                 # same artifact knn_build --out writes
    engine2 = knn.load_engine(path, bn=bn)
    print(f"save/load round-trip equivalent: "
          f"{indices_equivalent(engine.to_index(), engine2.to_index())}")
    print(f"engine stats: {engine.stats()}")

    print("\n== 7. moving fleet (build -> simulate -> query while moving) ==")
    sim = knn.FleetSim(g, fleet_size=64, seed=0)      # vehicles on sp trips
    fleet_engine = knn.build_engine(bn, sim.positions, k)
    for _ in range(3):                                # one serving tick each
        moves = sim.tick()                            # vehicles advance a street
        for src, dst in moves:
            fleet_engine.stage_move(src, dst)         # staged, not yet visible
        fleet_engine.query_batch(us[:64])             # queries see flushed state
        stats = fleet_engine.flush_updates()          # one fused move batch
    print(f"tick: {len(moves)} moves staged -> flush {stats}")
    print(f"fleet sim: {sim.stats()}")

    print("\n== 8. sharded serving (vertex-partitioned multi-device engine) ==")
    # The flat (n+1, k) table is embarrassingly partitionable by vertex:
    # shard s owns the contiguous range [s*R, (s+1)*R), R = ceil(n/S), one
    # local block per device on a 1-D mesh. Queries route to their owner
    # shard (one device roundtrip per batch); flushes run per shard with
    # only frontier vertex ids crossing shard boundaries between repair
    # rounds. On CPU, expose more devices BEFORE the process starts:
    #     XLA_FLAGS=--xla_force_host_platform_device_count=8
    # (serve.py --shards N and knn_build artifacts work the same way; this
    # demo uses however many devices the current process can see.)
    import jax

    shards = min(2, len(jax.devices()))
    sharded = knn.build_sharded_engine(bn, objects, k, shards=shards)
    s_ids, _ = sharded.query_batch(us)                # routed gather
    print(f"shards={shards} ({len(jax.devices())} devices visible); "
          f"bit-identical to scalar engine: "
          f"{bool(np.array_equal(np.asarray(s_ids), np.asarray(ids)))}")
    st = sharded.stats()
    # Padding cost of equal shard rows: S*(R+1) - n wasted rows. Tiny here,
    # but worth watching when n is small relative to the shard count or when
    # a hot shard forces replication — see stats()['row_padding_overhead'].
    print(f"shard rows={st['shard_rows']} padded rows={st['padded_rows']} "
          f"(overhead {st['row_padding_overhead']:.2%})")
    sharded.save(path)                                # artifact is shard-free
    resharded = knn.load_engine(path, bn=bn, shards=1)   # reshard-on-load
    print(f"reshard-on-load equivalent: "
          f"{indices_equivalent(sharded.to_index(), resharded.to_index())}")

    print("\n== 9. batched checkIns frontier (device-resident insert flushes) ==")
    # A flush with many staged inserts runs Algorithm 4's checkIns frontier
    # for the WHOLE batch as one multi-source pruned-relaxation program on
    # device: round r relaxes the BNS edges of every vertex whose tentative
    # distance changed in round r-1, pruned by the live k-th-distance column
    # (which never leaves the device — only changed-row masks and the final
    # affected rows' distances come back). The pre-batching pipeline — one
    # host heap search per object fed by an (n,) kth readback — survives as
    # engine.frontier = "host"; both produce identical tables, so the choice
    # is purely a throughput knob (exp14: device >= 1.3x at batch 512).
    batch_engine = knn.build_engine(bn, objects, k)
    absent = np.setdiff1d(np.arange(g.n), objects)[:64]
    for v in absent:
        batch_engine.stage_insert(int(v))
    flush = batch_engine.flush_updates()
    print(f"staged {len(absent)} inserts -> one flush: "
          f"{flush['rows_merged']} rows merged in "
          f"{flush['frontier_rounds']} frontier rounds")
    st = batch_engine.stats()
    # host span totals (cumulative, repro.core.spans): where a flush's host
    # time goes — frontier rounds, purge-merge enqueue, repair rounds, and
    # the readbacks that wait on the device; the same knn:* spans land in a
    # jax.profiler trace when one is open
    print("flush span seconds (calls): " + ", ".join(
        f"{name}={tot['s']:.4f} ({tot['n']})"
        for name, tot in st["spans"].items() if name.startswith("knn:flush")))
    print(f"flush transfers: {st['flush_readbacks']} readbacks "
          f"({st['flush_readback_bytes']} B), {st['flush_uploads']} uploads "
          f"({st['flush_upload_bytes']} B)")

    print("\n== 10. durability & epochs (crash-safe serving) ==")
    # Every flush publishes a new immutable epoch: queries resolve their
    # dispatch-time snapshot, so a slow reader never observes a half-built
    # table, and keep_epochs retains older epochs for pinned reads
    # (query_batch(..., epoch=e)). Attaching a write-ahead journal makes
    # staged updates durable BEFORE they are acknowledged: a process killed
    # mid-flush replays the journal on load and recovers byte-identical
    # tables (tests/chaos drives a kill at every pipeline checkpoint).
    wal = os.path.join(tempfile.mkdtemp(), "updates.wal")
    dur = knn.load_engine(path, bn=bn, journal=wal)   # journal from here on
    dur.keep_epochs = 3
    pinned = dur.epoch                                # epoch to time-travel to
    before = np.asarray(dur.query_batch(us)[0])
    dur.stage_insert(int(np.setdiff1d(np.arange(g.n), dur.objects)[0]))
    dur.flush_updates()                               # journal commit + swap
    print(f"epoch {pinned} -> {dur.epoch}; retained={dur.retained_epochs()}; "
          f"origin={dur.epoch_stats()['origin']}")
    old = np.asarray(dur.query_batch(us, epoch=pinned)[0])
    print(f"pinned read of epoch {pinned} unchanged: "
          f"{bool(np.array_equal(old, before))}")
    # crash recovery: a NEW process loads artifact + journal -> same tables
    rec = knn.load_engine(path, bn=bn, journal=wal)
    print(f"journal replay recovers epoch {rec.epoch}: bit-identical "
          f"{bool(np.array_equal(np.asarray(rec.to_index().ids), np.asarray(dur.to_index().ids)))}")
    try:                                              # corruption is typed
        knn.UpdateJournal(path)                       # npz is not a journal
    except knn.JournalError as e:
        print(f"typed corruption error: JournalError: {e}")
    print(f"epoch stats: {dur.stats()['epochs_retained']} retained, "
          f"{dur.stats()['epoch_table_bytes']} table bytes")

    print("\n== 11. replicated hot shards (shard -> replica-set fan-out) ==")
    # Skewed urban traffic pins one vertex range: with equal shard ranges,
    # one device saturates while the rest idle. set_replication({shard: R})
    # copies the hot shard's epoch buffers onto R extra devices at publish
    # time — same atomic epoch step, so pinned reads stay bit-identical on
    # every replica — and query batches fan out across the replica set
    # (round_robin or least_outstanding). Flushes still go to the primary
    # only: replicas are a serving concern, not a write path. Worth it when
    # the hot shard's share of traffic dwarfs the padding a narrower
    # per-replica batch pays (exp16: zipf-skewed mix, >= 1.5x q/s at
    # 4 shards x 3 replicas); serve.py --replicate SHARD:R or auto:R picks
    # the hottest shard from a sliding query histogram.
    import jax

    free = len(jax.devices()) - sharded.num_shards
    if free > 0:
        hot = 0
        sharded.set_replication({hot: min(3, free)}, policy="round_robin")
        r_ids, _ = sharded.query_batch(us)
        rst = sharded.stats()
        print(f"plan {rst['replication']} -> {rst['replica_slots']} slots "
              f"({rst['replica_policy']}); bit-identical through replicas: "
              f"{bool(np.array_equal(np.asarray(r_ids), np.asarray(ids)))}")
        print(f"replica traffic: {rst['replica_queries']} queries in "
              f"{rst['replica_batches']} batches, "
              f"errors={rst['replica_errors']}")
        sharded.set_replication(None)                 # drop back to primaries
    else:
        print(f"no devices free beyond the {sharded.num_shards} shard "
              f"primaries - start with "
              f"XLA_FLAGS=--xla_force_host_platform_device_count=8 to see "
              f"the fan-out")

    print("\n== 12. uneven shard ranges (traffic-aware repartition) ==")
    # The other answer to skew: instead of paying replica copies for a hot
    # range, move the range *boundaries* so every shard owns an equal share
    # of the observed traffic. knn.PartitionPlan is the one layout surface —
    # shards, ranges (explicit boundary vector or "auto"), replication and
    # routing policy in a single value accepted by build_sharded_engine,
    # load_engine and serve.py --partition; the old shards=/replication=
    # kwargs survive as deprecation shims. propose_starts turns a per-vertex
    # query histogram into balanced boundaries, and repartition() stages
    # them for the next flush: the tables are re-laid on device and
    # published with the layout in ONE atomic epoch step, so pinned reads
    # on older epochs keep serving under their OLD boundaries, and a flush
    # killed mid-repartition rolls back whole (never a torn layout, the
    # repartition stays staged for the retry — tests/core/test_repartition
    # drives every checkpoint). Prefer ranges over replicas when the skew is
    # broad (a hot *region*, zipf-ish traffic: exp17 holds >= 1.3x q/s over
    # equal-width with ZERO extra devices); prefer replicas when one range
    # is hot beyond what any boundary move can dilute. serve.py
    # --partition shards=4,ranges=auto does this live from the query stream.
    if sharded.num_shards > 1:
        hist = np.bincount(np.repeat(us, 3), minlength=g.n).astype(np.float64)
        starts = knn.propose_starts(hist, sharded.num_shards)
        pinned = sharded.epoch
        sharded.repartition(starts)                   # stage + flush in one
        u_ids, _ = sharded.query_batch(us)
        pst = sharded.stats()
        print(f"boundaries {pst['shard_starts']} (uneven={pst['uneven_ranges']}, "
              f"repartitions={pst['repartitions']})")
        old_ids = np.asarray(sharded.query_batch(us, epoch=pinned)[0])
        print(f"bit-identical after repartition: "
              f"{bool(np.array_equal(np.asarray(u_ids), np.asarray(ids)))}; "
              f"pinned epoch {pinned} still serves the old layout: "
              f"{bool(np.array_equal(old_ids, np.asarray(ids)))}")
        plan = knn.PartitionPlan.parse(f"shards={sharded.num_shards}")
        print(f"plan surface: {sharded.partition_plan().describe()} "
              f"(parse('shards=N') == legacy shards=N: "
              f"{plan.shards == sharded.num_shards})")
    else:
        print("single shard - boundaries have nowhere to move")

    print("\n== 13. collective halo exchange (device-resident flush repair) ==")
    # Multi-shard flushes need a halo: when a repair round changes rows on
    # one shard, the BNS neighborhoods of those rows — wherever they live —
    # become the next round's candidates, and the frontier's gated rows
    # cross boundaries the same way. halo="host" (the original seam) routes
    # those rows through host readbacks + numpy set algebra; the default
    # halo="collective" keeps every row device-resident: receiver sets
    # expand as a psum'd presence mask over the sharded BNS CSR, and the
    # rows themselves move shard-to-shard as capacity-padded
    # all_gather multicasts — only the integer routing plans go up and one
    # changed-mask comes back per round. Both modes are bit-identical to
    # the scalar oracle (tests/core/test_halo.py pins this, and the traffic
    # guard proves collective flushes never touch the routed host
    # fetchers); exp18 holds collective >= 1.2x host flush throughput at
    # 8 shards, batch 512. engine.halo_capacity, when set, bounds the
    # padded per-owner served-row count (rounded up to powers of two): a
    # round too wide to fit falls back to the routed host path for that
    # round only — counted in stats()['halo_fallbacks'], never visible in
    # results. Unset (the default) no round falls back, since an owner
    # serves at most its own rows; set it to cap exchange buffer memory on
    # wide fan-outs.
    if sharded.num_shards > 1:
        sharded.stage_insert(int(np.setdiff1d(np.arange(g.n), sharded.objects)[0]))
        sharded.flush_updates()
        hst = sharded.stats()
        print(f"halo={hst['halo']}: {hst['halo_rounds_collective']} collective "
              f"rounds, {hst['halo_fallbacks']} overflow fallbacks")
    else:
        print("single shard - nothing crosses a boundary")
    # Cold boots recompile every serving program; a persistent compilation
    # cache makes the SECOND process boot warm. serve.py and knn_build.py
    # turn it on before anything compiles, in JAX_COMPILATION_CACHE_DIR if
    # set, else in the checkout's .jax_cache; programmatically it is one
    # call, safe to leave on:
    #     from repro.analysis import sanitize
    #     sanitize.enable_compile_cache()
    # sanitize.count_compiles() splits real compiles from cache hits
    # (counter.uncached), which is how the cold-boot budget test holds a
    # warm-cache boot to the *warm* serving budgets.


if __name__ == "__main__":
    main()
