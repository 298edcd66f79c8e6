"""Serving driver: a device-resident kNN ``QueryEngine`` loop under mixed
traffic (batched queries + staged object updates, the paper's BUA arrival
model):

  PYTHONPATH=src python -m repro.launch.serve --smoke \
      --batch 1024 --ops 50000 --update-frac 0.05

The loop builds (or loads, --artifact) the index, then serves rounds of
``query_batch`` with updates staged into the engine's queue and flushed once
per round, printing queries/s, updates/s and the engine's serving stats as
JSON. On the CPU container use --smoke.

``--workload fleet`` swaps the random insert/delete churn for the
moving-objects workload: a ``FleetSim`` drives vehicles along shortest-path
trips, each serving tick stages the tick's (src, dst) moves via
``stage_move`` and flushes them as one fused device batch while query
batches interleave. Reports sustained ticks/s and query p50/p99:

  PYTHONPATH=src python -m repro.launch.serve --smoke \
      --workload fleet --fleet-size 96 --ticks 50 --batch 256

``--shards N`` serves from the vertex-sharded multi-device engine
(``ShardedQueryEngine``) instead — same results, tables row-partitioned
across N devices. On CPU, force the device count first:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --smoke \
      --shards 8 --batch 1024 --ops 20000

``--seed`` seeds everything host-side — the network, the object draw, the
query stream AND the staged-update stream (it threads into
``knn.stage_random_updates`` / ``FleetSim``), so two runs with the same seed
serve the identical op sequence; the default seed is 0.

``--replicate SHARD:R`` (sharded engine only) replicates one shard's epoch
buffers onto R extra devices and fans its queries across the replica set —
the answer to skewed traffic where one owner device is the ceiling.
``--replicate auto:R`` instead watches a sliding per-shard query histogram
and replicates whichever shard is hottest once the warmup rounds have
seen enough traffic. ``--hot-shard S --hot-frac F`` skews the synthetic
query stream so F of each batch lands in shard S's vertex range (the
zipf-city downtown); a replica failure mid-batch degrades that batch to
the primary path and counts ``replica_errors`` in the engine stats
instead of failing the run:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --smoke \
      --shards 4 --hot-shard 0 --hot-frac 0.8 --replicate auto:3

``--partition SPEC`` is the unified layout surface that replaces
``--shards``/``--replicate`` (both kept as deprecation shims; mixing them
with --partition is an error). One spec names the whole partition layout —
shard count, range boundaries, replication and routing policy:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --smoke \
      --partition shards=4,ranges=auto --hot-shard 0 --hot-frac 0.9

``ranges=auto`` watches the same sliding query histogram the auto-replica
watcher uses, but per *vertex*, as a continuous drift detector: whenever
the window's balance ratio decays past ``--rebalance-ratio`` it proposes
traffic-balanced boundaries (``propose_starts``) and repartitions on the
next flush — pinned readers on old epochs keep their old boundaries, new
queries route by the new ones — then keeps watching, so a traffic shift
mid-run (``--hot-flip-round``) triggers a second re-split after the
cooldown. The JSON stats report the active plan under ``"partition"`` and
the re-split history under ``"repartition_rounds"``.

Compiled XLA executables persist across processes in the directory
``JAX_COMPILATION_CACHE_DIR`` names, or else in the checkout's fixed
``.jax_cache`` (``sanitize.enable_compile_cache``), so a cold boot over a
warm cache skips the expensive compiles.
"""
from __future__ import annotations

import argparse
import json
import time
from collections import deque

import numpy as np

def _knn_partition_plan(args):
    """Resolve ``--partition`` vs the legacy ``--shards``/``--replicate``
    flags into one ``PartitionPlan`` (None = scalar engine)."""
    from repro import knn

    if args.partition:
        if args.shards or args.replicate:
            raise SystemExit(
                "--partition replaces --shards/--replicate: name the whole "
                "layout in one spec, e.g. --partition shards=4,replicate=auto:2"
            )
        try:
            plan = knn.PartitionPlan.parse(args.partition)
        except knn.EngineConfigError as e:
            raise SystemExit(f"--partition: {e}")
        if plan.shards is None:
            raise SystemExit("--partition must name shards=N")
        return plan
    if not args.shards:
        if args.replicate:
            raise SystemExit(
                "--replicate / --partition replication need the sharded "
                "engine (--shards N or --partition shards=N)"
            )
        return None
    rep = _parse_replicate(args.replicate) if args.replicate else None
    replication = None
    if rep is not None:
        replication = rep if rep[0] == "auto" else (rep,)
    return knn.PartitionPlan(shards=args.shards, replication=replication)


def _build_knn_engine(args, bn, objects, k: int, plan=None):
    """Scalar or sharded engine, per the resolved partition plan (the
    serving loops are engine-agnostic: both expose the same
    query/stage/flush surface)."""
    from repro import knn

    if plan is not None:
        return knn.build_sharded_engine(
            bn, objects, k, plan=plan, use_pallas=args.use_pallas
        )
    return knn.QueryEngine.build(bn, objects, k, use_pallas=args.use_pallas)


def serve_knn_fleet(args, g, bn, k: int, batch: int, t_bn: float, plan=None) -> dict:
    """Moving-fleet serving loop: fused ``stage_move`` flushes per tick."""
    from repro import knn
    from repro.workloads import drive_fleet_ticks

    sim = knn.FleetSim(g, fleet_size=args.fleet_size, seed=args.seed)
    t0 = time.perf_counter()
    engine = _build_knn_engine(args, bn, sim.positions, k, plan=plan)
    t_build = time.perf_counter() - t0

    rng = np.random.default_rng(args.seed + 1)
    # warmup: compile the gather once outside the timed loop
    engine.query_batch(rng.integers(0, g.n, size=batch))

    r = drive_fleet_ticks(
        engine, (sim.tick() for _ in range(args.ticks)), batch=batch, rng=rng
    )
    wall, lat = r["wall_s"], r["lat"]

    stats = {
        "workload": "fleet",
        "n": g.n,
        "k": k,
        "batch": batch,
        "fleet_size": sim.fleet_size,
        "ticks": args.ticks,
        "bngraph_s": round(t_bn, 3),
        "build_s": round(t_build, 3),
        "ticks_per_s": round(args.ticks / max(wall, 1e-9), 2),
        "moves_per_tick": round(sim.moves_total / max(args.ticks, 1), 1),
        "queries_per_s": round(args.ticks * batch / max(sum(lat), 1e-9), 1),
        "query_p50_us": round(float(np.percentile(lat, 50)) * 1e6, 1),
        "query_p99_us": round(float(np.percentile(lat, 99)) * 1e6, 1),
        "partition": engine.partition_plan().describe() if plan is not None else None,
        "sim": sim.stats(),
        "engine": engine.stats(),
    }
    print(json.dumps(stats, indent=2))
    return stats


def _parse_replicate(spec: str) -> tuple:
    """``SHARD:R`` -> (shard, R); ``auto:R`` -> ("auto", R)."""
    try:
        shard_s, _, r_s = spec.partition(":")
        r = int(r_s)
        if r < 1:
            raise ValueError
        return ("auto", r) if shard_s == "auto" else (int(shard_s), r)
    except ValueError:
        raise SystemExit(f"--replicate wants SHARD:R or auto:R (R >= 1), got {spec!r}")


def _hot_range(engine, shard: int, n: int) -> tuple[int, int]:
    """The hot shard's vertex range, read from the live routing boundaries
    (under uneven or repartitioned ranges the shards are not equal-width
    slices — always derive the range from ``engine.routing.starts``)."""
    starts = engine.routing.starts
    shard = shard % len(starts)
    lo = int(starts[shard])
    hi = int(starts[shard + 1]) if shard + 1 < len(starts) else n
    return (min(lo, n - 1), min(max(hi, lo + 1), n))


def _draw_queries(rng, n: int, batch: int, hot_range, hot_frac: float) -> np.ndarray:
    """Uniform query batch, with ``hot_frac`` of it redirected into
    ``hot_range`` (the skewed-city traffic model exp16 benchmarks)."""
    us = rng.integers(0, n, size=batch)
    if hot_frac > 0 and hot_range is not None:
        m = rng.random(batch) < hot_frac
        us[m] = rng.integers(hot_range[0], hot_range[1], size=int(m.sum()))
    return us


def _arm_injected_flush_failure(engine) -> None:
    """One-shot fault: the next flush dies just before its epoch swap (the
    worst-case point — all the work done, nothing published). Exercises the
    degrade-gracefully path end to end from the CLI."""

    def hook(e, phase):
        if phase == "pre-swap":
            e.checkpoint_hook = None
            raise RuntimeError("injected flush failure (--inject-flush-failure)")

    engine.checkpoint_hook = hook


def serve_knn(args) -> dict:
    """kNN serving loop: batched queries + staged updates on a QueryEngine."""
    from repro import knn

    # defaults: a USA-scale network (n = 2^24, k = 20, the paper's default)
    # or, with --smoke, a 512-vertex one
    grid, k, batch = (23, 5, 32) if args.smoke else (4096, 20, 4096)
    grid, k, batch = args.grid or grid, args.k or k, args.batch or batch

    g = knn.road_network(grid, grid, seed=args.seed)
    objects = knn.pick_objects(g.n, args.mu, seed=args.seed)
    t0 = time.perf_counter()
    bn = knn.build_bngraph(g)
    t_bn = time.perf_counter() - t0
    plan = _knn_partition_plan(args)
    if args.workload == "fleet":
        if args.artifact:
            # the fleet engine's object set must equal the sim's vehicle
            # positions, which a saved artifact cannot know about
            raise SystemExit("--artifact cannot be combined with --workload fleet")
        return serve_knn_fleet(args, g, bn, k, min(batch, 4096), t_bn, plan=plan)
    t0 = time.perf_counter()
    if args.artifact:
        # The artifact must come from the same (grid, seed) network: the
        # engine stores tables + objects, the BN-Graph supplies adjacency.
        # A plan (or --shards) reshards it on load: the artifact stores the
        # logical vertex-order tables plus any uneven boundaries the writer
        # served under, reused when the shard count matches.
        engine = knn.load_engine(
            args.artifact, bn=bn, plan=plan, use_pallas=args.use_pallas,
        )
        if engine.n != g.n or engine.k != k:
            raise SystemExit(
                f"artifact shape (n={engine.n}, k={engine.k}) does not match "
                f"--grid/--k (n={g.n}, k={k})"
            )
    else:
        engine = _build_knn_engine(args, bn, objects, k, plan=plan)
    t_build = time.perf_counter() - t0

    if args.hot_frac and plan is None:
        raise SystemExit(
            "--hot-frac needs the sharded engine (--shards N or "
            "--partition shards=N)"
        )
    auto_reps = plan.auto_replicas() if plan is not None else 0
    replicated_shard = None
    if plan is not None and engine.routing.replication:
        # explicit plan replication was applied at build/load time
        replicated_shard = min(engine.routing.replication)
    hot_range = None
    if plan is not None and args.hot_frac:
        hot_range = _hot_range(engine, args.hot_shard, g.n)
    # sliding query histograms: per-shard owner counts pick the hot shard
    # for --replicate auto; the per-vertex window feeds the ranges=auto
    # drift detector (continuous re-splits, see below)
    hist: deque = deque(maxlen=16)
    auto_ranges = plan is not None and plan.ranges == "auto" and engine.num_shards > 1
    # ranges=auto is a continuous drift detector, not a one-shot warmup
    # split: a sliding per-vertex histogram window tracks live traffic, and
    # whenever its balance ratio (the hottest shard's share x S, 1.0 =
    # perfectly balanced) decays past --rebalance-ratio — the initial
    # unbalanced boundaries, or the zipf city moving after a traffic flip —
    # the splitter proposes fresh boundaries and the engine repartitions on
    # the next flush. --rebalance-cooldown rounds separate re-splits so one
    # drift doesn't thrash the layout while the window still mixes old and
    # new traffic.
    vwin: deque = deque(maxlen=args.rebalance_window)
    repartition_rounds: list[int] = []
    balance_ratio = None

    rng = np.random.default_rng(args.seed + 1)
    mset = set(engine.objects.tolist())
    n_upd_round = int(round(batch * args.update_frac))
    rounds = max(1, args.ops // (batch + n_upd_round))

    # warmup: compile the gather once outside the timed loop
    engine.query_batch(_draw_queries(rng, g.n, batch, hot_range, args.hot_frac))

    # A failed flush (device error, corrupted batch, injected fault) must
    # not kill serving: the engine rolls back to the last good epoch with
    # the staged queue intact, so we log it, keep answering queries, and
    # retry the accumulated queue next round. --fail-fast restores the old
    # die-on-first-error behavior for debugging.
    t_query = t_update = 0.0
    queries = updates = 0
    errors = 0
    last_error = None
    for rnd in range(rounds):
        if args.hot_flip_round and rnd + 1 == args.hot_flip_round:
            # the zipf city moves: re-aim the skewed traffic at another
            # shard's vertex range (read from the *current* boundaries,
            # which a prior re-split may have moved)
            flip_to = (
                args.hot_shard2
                if args.hot_shard2 is not None
                else (args.hot_shard + engine.num_shards // 2) % engine.num_shards
            )
            hot_range = _hot_range(engine, flip_to, g.n)
        us = _draw_queries(rng, g.n, batch, hot_range, args.hot_frac)
        t0 = time.perf_counter()
        engine.query_batch(us)  # host arrays: the answer is read back
        t_query += time.perf_counter() - t0
        queries += batch

        if auto_ranges:
            vwin.append(np.bincount(us, minlength=g.n))
            wsum = np.sum(vwin, axis=0)
            starts = engine.routing.starts
            bounds = np.append(starts, g.n)
            shares = np.add.reduceat(wsum, bounds[:-1])
            balance_ratio = float(
                shares.max() * engine.num_shards / max(wsum.sum(), 1)
            )
            cooled = (
                not repartition_rounds
                or rnd + 1 - repartition_rounds[-1] >= args.rebalance_cooldown
            )
            if (
                rnd + 1 >= 3  # enough warmup traffic to trust the window
                and cooled
                and balance_ratio > args.rebalance_ratio
            ):
                proposed = knn.propose_starts(wsum, engine.num_shards)
                if not np.array_equal(proposed, starts):
                    engine.repartition(proposed)  # rides a fresh epoch; old
                    repartition_rounds.append(rnd + 1)  # epochs keep theirs
                    hist.clear()  # owner counts now track the new boundaries

        if auto_reps and replicated_shard is None:
            hist.append(
                np.bincount(engine.routing.owner(us), minlength=engine.num_shards)
            )
            warmup = 3 if not auto_ranges else 6  # let ranges settle first
            if rnd + 1 >= warmup and hist:
                hot = int(np.argmax(np.sum(hist, axis=0)))
                engine.set_replication({hot: auto_reps}, policy=plan.policy)
                replicated_shard = hot

        if n_upd_round:
            t0 = time.perf_counter()
            knn.stage_random_updates(engine, mset, rng, n_upd_round)
            depth = engine.queue_depth
            if args.inject_flush_failure and rnd + 1 == args.inject_flush_failure:
                _arm_injected_flush_failure(engine)
            try:
                engine.flush_updates()
                updates += depth
            except Exception as e:
                if args.fail_fast:
                    raise
                errors += 1
                last_error = f"{type(e).__name__}: {e}"
            finally:
                engine.checkpoint_hook = None
            t_update += time.perf_counter() - t0

    wall = t_query + t_update
    stats = {
        "n": g.n,
        "k": k,
        "batch": batch,
        "rounds": rounds,
        "bngraph_s": round(t_bn, 3),
        "build_s": round(t_build, 3),
        "queries": queries,
        "updates": updates,
        "errors": errors,
        "last_error": last_error,
        "replicate": args.replicate,
        "replicated_shard": replicated_shard,
        "partition": engine.partition_plan().describe() if plan is not None else None,
        "repartitioned_at_round": (
            repartition_rounds[0] if repartition_rounds else None
        ),
        "repartition_rounds": repartition_rounds,
        "balance_ratio": round(balance_ratio, 4) if balance_ratio else None,
        "hot_frac": args.hot_frac,
        "queries_per_s": round(queries / max(t_query, 1e-9), 1),
        "updates_per_s": round(updates / max(t_update, 1e-9), 1) if updates else 0.0,
        "ops_per_s": round((queries + updates) / max(wall, 1e-9), 1),
        "us_per_query": round(t_query / max(queries, 1) * 1e6, 3),
        "engine": engine.stats(),
    }
    print(json.dumps(stats, indent=2))
    return stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="a 512-vertex network (grid 23, k 5, batch 32) "
                         "instead of grid 4096, k 20, batch 4096")
    ap.add_argument("--batch", type=int, default=None, help="query batch")
    # --query-batch is an alias for --batch
    ap.add_argument("--query-batch", type=int, default=None, dest="batch")
    ap.add_argument("--grid", type=int, default=None, help="grid side; n = grid^2")
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--mu", type=float, default=0.02)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the network, object draw, query stream and "
                         "the staged-update stream (stage_random_updates / "
                         "FleetSim), so equal seeds replay identical traffic")
    ap.add_argument("--ops", type=int, default=50_000)
    ap.add_argument("--update-frac", type=float, default=0.05)
    ap.add_argument("--workload", choices=("random", "fleet"), default="random",
                    help="update traffic: random insert/delete churn or the "
                         "moving-fleet stage_move workload")
    ap.add_argument("--fleet-size", type=int, default=96)
    ap.add_argument("--ticks", type=int, default=50,
                    help="fleet workload: serving ticks (one flush per tick)")
    ap.add_argument("--artifact", default=None, help="serve a knn_build --out npz")
    ap.add_argument("--fail-fast", action="store_true",
                    help="die on the first failed flush instead of "
                         "logging it (errors/last_error in the JSON stats) "
                         "and continuing on the last good epoch")
    ap.add_argument("--inject-flush-failure", type=int, default=0,
                    metavar="ROUND",
                    help="make the flush of round ROUND fail just "
                         "before its epoch swap (fault-injection smoke for "
                         "the graceful-degradation path)")
    ap.add_argument("--partition", default=None, metavar="SPEC",
                    help="the whole partition layout as one spec, e.g. "
                         "'shards=4,replicate=auto:2,ranges=auto' (keys: "
                         "shards, ranges [equal | auto | 0:B1:B2...], "
                         "replicate [SHARD:R | auto:R], policy). ranges=auto "
                         "repartitions on flush from the sliding query "
                         "histogram. Replaces --shards/--replicate")
    ap.add_argument("--shards", type=int, default=0,
                    help="[deprecated: use --partition shards=N] serve from "
                         "the vertex-sharded multi-device engine with this "
                         "many shards (0 = scalar engine); needs >= N "
                         "visible devices, e.g. "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--replicate", default=None, metavar="SHARD:R",
                    help="[deprecated: use --partition replicate=...] "
                         "sharded: replicate shard SHARD onto R extra "
                         "devices and fan its queries across the replica "
                         "set; 'auto:R' picks the hottest shard from a "
                         "sliding query histogram after a short warmup")
    ap.add_argument("--hot-shard", type=int, default=0,
                    help="sharded: which shard --hot-frac concentrates "
                         "queries into (default 0)")
    ap.add_argument("--hot-frac", type=float, default=0.0,
                    help="sharded: fraction of each query batch drawn "
                         "from the hot shard's vertex range (skewed-city "
                         "traffic; 0 = uniform)")
    ap.add_argument("--hot-flip-round", type=int, default=0, metavar="ROUND",
                    help="sharded: at round ROUND re-aim --hot-frac "
                         "traffic at another shard's range (the zipf city "
                         "moving mid-run; exercises the ranges=auto drift "
                         "detector's second re-split)")
    ap.add_argument("--hot-shard2", type=int, default=None,
                    help="sharded: the shard --hot-flip-round re-aims "
                         "traffic at (default: the shard opposite "
                         "--hot-shard)")
    ap.add_argument("--rebalance-ratio", type=float, default=1.25,
                    help="ranges=auto: re-split when the sliding "
                         "window's balance ratio (hottest shard share x S, "
                         "1.0 = balanced) exceeds this")
    ap.add_argument("--rebalance-window", type=int, default=16,
                    help="ranges=auto: rounds of per-vertex query "
                         "history the drift detector slides over")
    ap.add_argument("--rebalance-cooldown", type=int, default=4,
                    help="ranges=auto: minimum rounds between re-splits")
    ap.add_argument("--use-pallas", action="store_true")
    args = ap.parse_args()

    from repro.analysis import sanitize

    # must run before anything compiles: the cache dir only helps programs
    # compiled after it is configured
    sanitize.enable_compile_cache()
    return serve_knn(args)


if __name__ == "__main__":
    main()
