"""Bring-up smoke of the KNN-Index main path on a TPU, in one process.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded engine on a four-chip host

One chip: build the index tables of a 256x256 road network (n = 65,536,
k = 20, mu = 0.02) with the XLA sweeps and with the compiled Pallas sweeps and
require them bit-identical; then serve rounds of mixed-k ``query_batch`` at
B = 4096 with 1% random insert/delete churn flushed every round, on an XLA
engine and a Pallas engine side by side, requiring equal answers and equal
tables after every flush; sampled queries are checked against the Dijkstra
oracle before the updates and after the last flush.

Four chips (``--chips 4``, and only this): ``PartitionPlan(shards=4,
ranges=auto)`` with collective-halo insert, delete and move flushes and a
repartition, then ``shards=2,replicate=0:2``, each compared after every flush
with a one-chip scalar engine fed the same updates; no halo fallback and no
replica error may occur, and each shard's tables must sit on its own chip.

It drives the same functions ``launch/knn_build.py`` and ``launch/serve.py``
run. The compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else the
checkout's ``.jax_cache``. The earlier lines are plain readings; the last line
is ``{"ok": true, "device": {...}}``. Without a TPU it exits nonzero and
prints no result: there is no CPU fallback. ``one_chip_phase`` and
``four_chip_phase`` take the grid size and the devices, so tests call them on
the CPU at a tiny grid.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

K = 20
MU = 0.02
BATCH = 4096
ROUNDS = 20
ORACLE_SAMPLE = 64


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def reading(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


def _tables(engine) -> tuple[np.ndarray, np.ndarray]:
    idx = engine.to_index()
    return idx.ids, idx.dists


def _same_tables(a, b) -> bool:
    (ia, da), (ib, db) = _tables(a), _tables(b)
    return np.array_equal(ia, ib) and np.array_equal(da, db)


def _check_oracle(g, engine, mset: set, rng, what: str) -> None:
    """Sampled queries vs ``dijkstra_knn``: equal distances, and equal ids
    wherever a row's distances are unique (``indices_equivalent``)."""
    from repro import knn
    from repro.core.reference import dijkstra_knn

    is_obj = np.zeros(g.n, bool)
    is_obj[sorted(mset)] = True
    us = rng.choice(g.n, size=min(ORACLE_SAMPLE, g.n), replace=False).astype(np.int32)
    ref = knn.KNNIndex(
        ids=np.full((len(us), engine.k), -1, np.int32),
        dists=np.full((len(us), engine.k), np.inf),
        k=engine.k,
    )
    for r, u in enumerate(us):
        for c, (v, d) in enumerate(dijkstra_knn(g, is_obj, engine.k, int(u))):
            ref.ids[r, c], ref.dists[r, c] = v, d
    ids, d = engine.query_batch(us)
    ids = np.asarray(ids)
    got = knn.KNNIndex(
        ids=ids, dists=np.where(ids >= 0, np.asarray(d, np.float64), np.inf), k=engine.k
    )
    check(knn.indices_equivalent(ref, got), f"{what}: sampled queries differ from dijkstra_knn")


def _network(grid: int, seed: int):
    from repro import knn

    g = knn.road_network(grid, grid, seed=seed)
    objects = knn.pick_objects(g.n, MU, seed=seed)
    t0 = time.perf_counter()
    bn = knn.build_bngraph(g)
    reading("bngraph_host_s", time.perf_counter() - t0)
    reading("n", g.n)
    reading("k", K)
    return g, objects, bn


def one_chip_phase(grid: int, device, *, seed: int = 0, rounds: int = ROUNDS,
                   batch: int = BATCH) -> None:
    """XLA vs compiled-Pallas build, then serve + flush both side by side."""
    import jax

    from repro import knn
    from repro.core.construct_jax import build_knn_tables_jax, prepare_sweep

    g, objects, bn = _network(grid, seed)
    with jax.default_device(device):
        plans = (prepare_sweep(bn, "up"), prepare_sweep(bn, "down"))
        engines = {}
        for use_pallas in (False, True):
            t0 = time.perf_counter()
            tables = jax.block_until_ready(
                build_knn_tables_jax(bn, objects, K, use_pallas=use_pallas, plans=plans)
            )
            reading(f"cold_build_s[{'pallas' if use_pallas else 'xla'}]",
                    time.perf_counter() - t0)
            engines[use_pallas] = knn.QueryEngine(
                *tables, K, objects, bn=bn, use_pallas=use_pallas
            )
        xla, pal = engines[False], engines[True]
        check(_same_tables(xla, pal), "XLA and Pallas sweeps built different tables")

        rng = np.random.default_rng(seed + 1)
        _check_oracle(g, xla, set(objects.tolist()), rng, "before updates")
        msets = [set(objects.tolist()), set(objects.tolist())]
        n_upd = max(1, g.n // 100)
        t_query, t_flush = [], []
        for rnd in range(rounds):
            us = rng.integers(0, g.n, size=batch).astype(np.int32)
            ks = rng.integers(1, K + 1, size=batch).astype(np.int32)
            answers = []
            for eng in (xla, pal):
                t0 = time.perf_counter()
                answers.append(eng.query_batch(us, ks))
                t_query.append(time.perf_counter() - t0)
            check(all(np.array_equal(a, b) for a, b in zip(*answers)),
                  f"round {rnd}: XLA and Pallas engines answered differently")
            upd_rng = np.random.default_rng([seed, rnd])
            for eng, mset in zip((xla, pal), msets):
                knn.stage_random_updates(eng, mset, copy.deepcopy(upd_rng), n_upd)
                t0 = time.perf_counter()
                eng.flush_updates()
                jax.block_until_ready(eng.tables)
                t_flush.append(time.perf_counter() - t0)
            check(msets[0] == msets[1], "the two engines staged different updates")
            check(_same_tables(xla, pal), f"round {rnd}: tables differ after the flush")
        check(xla.stats()["flushes_failed"] == pal.stats()["flushes_failed"] == 0,
              "a flush failed")
        _check_oracle(g, pal, msets[1], rng, "after the last flush")
    # the first round of each engine compiles; later rounds are warm
    reading("warm_query_batch_s_median", float(np.median(t_query[2:])))
    reading("warm_flush_s_median", float(np.median(t_flush[2:])))
    reading("updates_per_flush", n_upd)
    reading("rounds", rounds)


def _check_sharded_stats(engine, what: str) -> None:
    st = engine.stats()
    check(st["flushes_failed"] == 0, f"{what}: {st['flushes_failed']} failed flushes")
    check(st.get("halo_fallbacks", 0) == 0,
          f"{what}: {st.get('halo_fallbacks')} halo fallbacks")
    check(st.get("replica_errors", 0) == 0,
          f"{what}: {st.get('replica_errors')} replica errors, last: "
          f"{st.get('last_replica_error')}")


def _sharded_rounds(g, sharded, scalar, objects, rng, rounds: int, batch: int,
                    what: str, *, moves: bool, repartition_at: int | None) -> None:
    """Serve both engines the same traffic, flush the same updates, and
    require bit-identical answers and tables after every flush."""
    import jax

    from repro import knn

    msets = [set(objects.tolist()), set(objects.tolist())]
    t_flush = ([], [])
    span = g.n  # queries are drawn from [0, span)
    for rnd in range(rounds):
        if rnd == repartition_at:
            # the traffic turns hot on the first quarter of the ids, and the
            # boundaries follow it (as serve.py's ranges=auto watcher does)
            span = g.n // 4
            hot = rng.integers(0, span, size=batch * 4)
            proposed = knn.propose_starts(np.bincount(hot, minlength=g.n), sharded.num_shards)
            check(not np.array_equal(proposed, sharded.routing.starts),
                  f"{what}: the skewed histogram proposed no new boundaries")
            sharded.repartition(proposed)
        us = rng.integers(0, span, size=batch).astype(np.int32)
        a = sharded.query_batch(us)
        b = scalar.query_batch(us)
        check(all(np.array_equal(x, y) for x, y in zip(a, b)),
              f"{what} round {rnd}: sharded and one-chip answers differ")
        upd_rng = np.random.default_rng([rnd, 1])
        for eng, mset in zip((sharded, scalar), msets):
            knn.stage_random_updates(eng, mset, copy.deepcopy(upd_rng), max(1, g.n // 200))
        check(msets[0] == msets[1], f"{what}: the two engines staged different updates")
        if moves:
            mv_rng = np.random.default_rng([rnd, 2])
            n_mv = min(4, len(msets[0]))
            src = mv_rng.choice(sorted(msets[0]), size=n_mv, replace=False)
            dst = mv_rng.choice(np.setdiff1d(np.arange(g.n), sorted(msets[0])), size=n_mv,
                                replace=False)
            for u, v in zip(src.tolist(), dst.tolist()):
                for eng, mset in zip((sharded, scalar), msets):
                    eng.stage_move(u, v)
                    mset.discard(u)
                    mset.add(v)
        for eng, times in zip((sharded, scalar), t_flush):
            t0 = time.perf_counter()
            eng.flush_updates()
            jax.block_until_ready(eng.tables)
            times.append(time.perf_counter() - t0)
        check(_same_tables(sharded, scalar), f"{what} round {rnd}: tables differ after the flush")
        _check_sharded_stats(sharded, what)
    # round 0 compiles; later rounds reuse what they can
    for name, times in zip(("sharded", "one_chip"), t_flush):
        reading(f"flush_s_median_after_round0[{what}][{name}]", float(np.median(times[1:])))


def four_chip_phase(grid: int, devices, *, seed: int = 0, rounds: int = 4,
                    batch: int = BATCH) -> None:
    """Sharded engines over four chips vs a one-chip scalar engine."""
    import jax

    from repro import knn

    check(len(devices) == 4 and list(devices) == jax.devices()[:4],
          "the four-chip phase runs on the first four devices")
    g, objects, bn = _network(grid, seed)
    with jax.default_device(devices[0]):
        for spec, moves, repart in (
            ("shards=4,ranges=auto", True, rounds // 2),
            ("shards=2,replicate=0:2", False, None),
        ):
            t0 = time.perf_counter()
            sharded = knn.build_sharded_engine(bn, objects, K, plan=spec)
            jax.block_until_ready(sharded._ids_g)
            reading(f"cold_build_s[{spec}]", time.perf_counter() - t0)
            scalar = knn.QueryEngine.build(bn, objects, K)
            check(sharded.halo == "collective", f"{spec}: halo is {sharded.halo}")
            placed = {sh.device for sh in sharded._ids_g.addressable_shards}
            check(placed == set(devices[: sharded.num_shards]),
                  f"{spec}: shard tables sit on {sorted(d.id for d in placed)}")
            check(_same_tables(sharded, scalar), f"{spec}: built tables differ")
            _sharded_rounds(g, sharded, scalar, objects, np.random.default_rng(seed + 2),
                            rounds, batch, spec, moves=moves, repartition_at=repart)
            st = sharded.stats()
            if repart is not None:
                check(st["repartitions"] >= 1, f"{spec}: no repartition ran")
                check(st["halo_rounds_collective"] > 0, f"{spec}: no collective halo round")
                check(st["balanced_batches"] > 0, f"{spec}: the balanced gather never ran")
            else:
                check(st["replica_batches"] > 0, f"{spec}: the replicated gather never ran")
            reading(f"stats[{spec}]", json.dumps(
                {k: st[k] for k in ("repartitions", "halo_rounds_collective",
                                    "halo_fallbacks", "balanced_batches",
                                    "replica_batches", "replica_errors", "flushes_failed")
                 if k in st}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--grid", type=int, default=256, help="grid side; n = grid^2")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 2

    from repro.analysis import sanitize

    reading("compile_cache_dir", sanitize.enable_compile_cache())
    reading("device_kind", devices[0].device_kind)
    t0 = time.perf_counter()
    with sanitize.count_compiles() as compiles:
        if args.chips == 4:
            four_chip_phase(args.grid, devices[:4], seed=args.seed)
        else:
            one_chip_phase(args.grid, devices[0], seed=args.seed)
    reading("smoke_wall_s", time.perf_counter() - t0)
    # a second run over the same cache directory finds these programs there
    reading("compiles", compiles.count)
    reading("compile_cache_hits", compiles.cache_hits)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
