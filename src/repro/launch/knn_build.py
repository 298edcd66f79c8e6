"""KNN-Index production build driver (the paper's pipeline, end to end):

  road network -> min-degree order + BN-Graph (host symbolic phase)
               -> level-synchronous device sweeps (bottom-up V_k^<, top-down V_k)
               -> QueryEngine artifact + stats

  PYTHONPATH=src python -m repro.launch.knn_build --grid 80 --k 20 --mu 0.05 \
      --out index.npz

The build goes through the ``repro.knn`` facade and the ``--out`` artifact is
``QueryEngine.save`` format, so ``serve.py --artifact`` (and
``knn.load_engine``) round-trip through one file.
"""
from __future__ import annotations

import argparse
import json
import time

from repro import knn
from repro.analysis import sanitize
from repro.core.construct_jax import build_knn_tables_jax, prepare_sweep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=60, help="grid side; n = grid^2")
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--mu", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--verify", action="store_true", help="check vs host reference")
    ap.add_argument("--out", default=None, help="write a QueryEngine.save npz")
    args = ap.parse_args()
    sanitize.enable_compile_cache()  # before anything compiles

    t0 = time.perf_counter()
    g = knn.road_network(args.grid, args.grid, seed=args.seed)
    objects = knn.pick_objects(g.n, args.mu, seed=args.seed)
    t1 = time.perf_counter()
    bn = knn.build_bngraph(g)
    t2 = time.perf_counter()
    # prepare the sweep schedules once: they drive the build AND the stats
    up = prepare_sweep(bn, "up")
    down = prepare_sweep(bn, "down")
    vk_ids, vk_d = build_knn_tables_jax(
        bn, objects, args.k, use_pallas=args.use_pallas, plans=(up, down)
    )
    engine = knn.QueryEngine(
        vk_ids, vk_d, args.k, objects, bn=bn, use_pallas=args.use_pallas
    )
    t3 = time.perf_counter()
    idx = engine.to_index()
    stats = {
        "n": g.n,
        "m": g.m,
        "|M|": int(objects.size),
        "k": args.k,
        "rho": bn.rho,
        "tau": bn.tau,
        "levels_up": up.num_levels,
        "levels_down": down.num_levels,
        "chunks_up": up.num_chunks,
        "chunks_down": down.num_chunks,
        "shape_buckets_up": len(up.buckets),
        "shape_buckets_down": len(down.buckets),
        "pad_occupancy_up": round(up.occupancy, 4),
        "pad_occupancy_down": round(down.occupancy, 4),
        "gen_s": round(t1 - t0, 3),
        "bngraph_s": round(t2 - t1, 3),
        "sweeps_s": round(t3 - t2, 3),
        # the paper's n*k*(4+4)-byte count = what the device tables occupy
        "index_bytes": idx.size_bytes(dist_bytes=4),
    }
    if args.verify:
        from repro.core.verify import certificate

        ref = knn.knn_index_cons_plus(bn, objects, args.k)
        stats["verified"] = bool(knn.indices_equivalent(ref, idx))
        if g.n <= 20000:  # dense tropical certificate at verification scale
            stats["bngraph_certificate"] = certificate(bn, use_pallas=False)
    print(json.dumps(stats, indent=2))
    if args.out:
        engine.save(args.out)
    return stats


if __name__ == "__main__":
    main()
