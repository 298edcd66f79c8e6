"""Stable public facade for the kNN road-network system.

One import surface for the whole pipeline — build, serve, maintain, persist:

    from repro import knn

    g = knn.road_network(64, 64, seed=0)
    objects = knn.pick_objects(g.n, 0.02, seed=0)
    engine = knn.build_engine(g, objects, k=20)        # device sweeps end to end

    ids, dists = engine.query_batch(us)                # (B, k) numpy, one readback
    engine.stage_insert(u); engine.stage_delete(v)
    engine.stage_move(a, b)                            # moving-objects traffic
    engine.flush_updates()                             # one fused batch repair
    engine.save("index.npz")

    engine = knn.load_engine("index.npz", bn=knn.build_bngraph(g))

Moving-fleet serving (see ``repro.workloads``): ``FleetSim`` drives vehicles
along shortest-path trips and each ``sim.tick()`` yields the (src, dst) moves
to stage; ``flush_updates`` applies them as one fused device batch.

Multi-device serving: ``build_sharded_engine`` (and ``load_engine(...,
shards=N)``) returns a ``ShardedQueryEngine`` — the same surface served from
vertex-sharded tables on a 1-D device mesh, exactly equivalent to the scalar
engine (tests/core/test_sharded.py). Everything re-exported here is covered
by the equivalence tests, so internal layouts may change under it without
breaking callers.

Durability and failure taxonomy: ``load_engine(..., journal="wal.bin")``
attaches a write-ahead ``UpdateJournal`` and replays any records a killed
process left behind (crash recovery to byte-identical tables — see
``repro.core.journal``). Every error the system raises subclasses
``RepError`` (``repro.core.errors``): catch it to handle exactly
"this system rejected the request / detected corruption".
"""
from __future__ import annotations

import numpy as np

from repro.core.bngraph import BNGraph, build_bngraph
from repro.core.construct_jax import build_knn_index_jax, build_knn_tables_jax
from repro.core.engine import QueryEngine
from repro.core.errors import (
    ArtifactError,
    EngineConfigError,
    EpochError,
    JournalError,
    QueryError,
    RepError,
    StagedUpdateError,
)
from repro.core.index import KNNIndex, indices_equivalent
from repro.core.journal import UpdateJournal
from repro.core.partition import PartitionPlan, propose_starts
from repro.core.reference import knn_index_cons_plus
from repro.core.sharded import ShardedQueryEngine, ShardRoutingTable, make_mesh
from repro.core.updates import delete_object, insert_object, move_object
from repro.graph.csr import Graph
from repro.graph.generators import pick_objects, road_network
from repro.workloads.fleet import FleetSim

__all__ = [
    "ArtifactError",
    "BNGraph",
    "EngineConfigError",
    "EpochError",
    "FleetSim",
    "Graph",
    "JournalError",
    "KNNIndex",
    "PartitionPlan",
    "QueryEngine",
    "QueryError",
    "RepError",
    "ShardRoutingTable",
    "ShardedQueryEngine",
    "StagedUpdateError",
    "UpdateJournal",
    "build_bngraph",
    "build_engine",
    "build_index",
    "build_knn_index_jax",
    "build_knn_tables_jax",
    "build_sharded_engine",
    "delete_object",
    "indices_equivalent",
    "insert_object",
    "knn_index_cons_plus",
    "load_engine",
    "make_mesh",
    "move_object",
    "pick_objects",
    "propose_starts",
    "road_network",
    "stage_random_updates",
]


def build_engine(
    graph: Graph | BNGraph,
    objects: np.ndarray,
    k: int,
    *,
    use_pallas: bool = False,
) -> QueryEngine:
    """Road network (or prebuilt BN-Graph) -> serving engine, on device."""
    bn = graph if isinstance(graph, BNGraph) else build_bngraph(graph)
    return QueryEngine.build(bn, objects, k, use_pallas=use_pallas)


def build_index(
    graph: Graph | BNGraph,
    objects: np.ndarray,
    k: int,
    *,
    use_pallas: bool = False,
) -> KNNIndex:
    """Road network (or prebuilt BN-Graph) -> host KNNIndex view."""
    bn = graph if isinstance(graph, BNGraph) else build_bngraph(graph)
    return build_knn_index_jax(bn, objects, k, use_pallas=use_pallas)


def build_sharded_engine(
    graph: Graph | BNGraph,
    objects: np.ndarray,
    k: int,
    *,
    plan: PartitionPlan | str | None = None,
    shards: int | None = None,
    use_pallas: bool = False,
    replication: dict[int, int] | None = None,
) -> ShardedQueryEngine:
    """Road network -> vertex-sharded multi-device serving engine.

    ``plan`` — a ``PartitionPlan`` (or its ``parse`` spec string, e.g.
    ``"shards=4,ranges=auto"``) — is the one place the whole partition
    layout is specified: shard count, range boundaries (equal-width,
    explicit, or object-density ``auto``), replication and routing policy.
    The sharded engine serves the exact same results as the scalar one
    under every layout; see ``repro.core.sharded``.

    ``shards=`` and ``replication=`` are the legacy pre-plan kwargs, kept
    as thin deprecation shims that construct the equivalent plan (passing
    them alongside ``plan`` raises ``EngineConfigError``). ``shards=None``
    with no plan spans every visible device (on CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before process
    start).
    """
    plan = PartitionPlan.resolve(plan, shards=shards, replication=replication)
    bn = graph if isinstance(graph, BNGraph) else build_bngraph(graph)
    return ShardedQueryEngine.build(bn, objects, k, plan=plan, use_pallas=use_pallas)


def load_engine(
    path,
    *,
    bn: BNGraph | None = None,
    plan: PartitionPlan | str | None = None,
    shards: int | None = None,
    use_pallas: bool = False,
    journal=None,
    replication: dict[int, int] | None = None,
) -> QueryEngine | ShardedQueryEngine:
    """Load a ``QueryEngine.save`` / ``knn_build --out`` artifact.

    ``plan`` (a ``PartitionPlan`` or spec string) naming a shard count
    loads into a ``ShardedQueryEngine`` under that layout regardless of how
    many shards wrote the artifact (reshard-on-load: the artifact stores
    the logical vertex-order tables, plus any uneven range boundaries the
    writer was serving under, which are reused when the shard count
    matches). No plan and ``shards=None`` keeps the scalar engine.

    ``shards=`` / ``replication=`` are the legacy deprecation-shim kwargs
    (mixing them with ``plan`` raises ``EngineConfigError``). A replication
    plan saved in the artifact is re-applied when compatible (same shard
    count, enough devices) and dropped otherwise; an explicit plan or
    ``replication={...}`` overrides it, ``{}`` force-drops it.

    ``journal`` (a path or ``UpdateJournal``) attaches the write-ahead
    journal and replays whatever a killed process left in it — committed
    flush segments and the uncommitted tail — recovering the exact tables
    that process was serving. Requires ``bn`` when the journal is
    non-empty (replay runs real updates).
    """
    plan = PartitionPlan.resolve(plan, shards=shards, replication=replication)
    if plan.shards is not None or plan.ranges is not None or plan.replication is not None:
        return ShardedQueryEngine.load(
            path, bn=bn, plan=plan, use_pallas=use_pallas, journal=journal,
        )
    return QueryEngine.load(path, bn=bn, use_pallas=use_pallas, journal=journal)


def stage_random_updates(engine: QueryEngine, mset: set, rng=None, count: int = 1) -> int:
    """Stage ``count`` random net object updates (the benchmark workload mix).

    Draws uniform vertices from the engine's *global* vertex set
    ``[0, engine.n)`` (a sharded engine is driven identically — routing by
    owner happens at flush time): a present one is staged for deletion
    (skipped while |M| <= k+1 so rows stay full through the churn), an
    absent one for insertion. ``mset`` is the caller's membership mirror and
    is kept in sync.

    ``rng`` may be a ``numpy.random.Generator``, an int seed, or None — the
    default is a fresh ``np.random.default_rng(0)``, so repeated runs that
    rely on the default draw the SAME update sequence (reproducible
    benchmarks; pass ``serve.py --seed`` / your own generator to vary it).
    Returns the number staged — possibly fewer than ``count`` when the draw
    budget runs out (e.g. every vertex is an object but |M| <= k+1, so
    nothing is stageable); the caller decides when to flush.
    """
    if rng is None or isinstance(rng, (int, np.integer)):
        rng = np.random.default_rng(0 if rng is None else int(rng))
    staged = 0
    for _ in range(max(16, 16 * count)):
        if staged >= count:
            break
        v = int(rng.integers(0, engine.n))
        if v in mset and len(mset) > engine.k + 1:
            engine.stage_delete(v)
            mset.discard(v)
        elif v not in mset:
            engine.stage_insert(v)
            mset.add(v)
        else:
            continue
        staged += 1
    return staged
