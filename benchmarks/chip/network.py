"""The benchmark's road network: its generator, and the cache of its BN-Graph.

A configuration fixes one road network (its ``network`` group and
``graph_seed``); ``--seed`` never changes it. The generator is the
benchmark's own copy of the program's perturbed-grid generator
(``repro.graph.generators.road_network``), so the plain reference reads a
graph the program did not make. The program's BN-Graph build is pure Python
and grows about as n^1.9, so the first run of a configuration in a checkout
builds it and stores it compactly (CSR, not the padded arrays) under
``.cache/``; later runs load it from there.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from pathlib import Path

import numpy as np

CACHE_DIR = Path(__file__).resolve().parent / ".cache"


@dataclasses.dataclass(frozen=True)
class Network:
    """Undirected weighted graph in CSR form, each edge stored twice."""

    n: int
    indptr: np.ndarray   # (n+1,) int64
    indices: np.ndarray  # (2m,) int32
    weights: np.ndarray  # (2m,) float64

    def neighbors(self, v: int) -> tuple[np.ndarray, np.ndarray]:
        s, e = self.indptr[v], self.indptr[v + 1]
        return self.indices[s:e], self.weights[s:e]


def _find(parent: np.ndarray, a: int) -> int:
    root = a
    while parent[root] != root:
        root = parent[root]
    while parent[a] != root:
        parent[a], a = root, parent[a]
    return root


def road_network(spec: dict) -> Network:
    """Perturbed grid city: ``grid`` x ``grid`` intersections, a random
    ``delete_frac`` of the streets removed outside a kept spanning tree,
    ``diag_frac * n`` diagonal connectors, integer lengths drawn uniformly
    from [``weight_low``, ``weight_high``]; parallel edges keep the shorter."""
    nx = ny = int(spec["grid"])
    rng = np.random.default_rng(int(spec["graph_seed"]))
    n = nx * ny
    xs, ys = np.meshgrid(np.arange(nx), np.arange(ny), indexing="ij")
    vid = (xs * ny + ys).ravel()
    right = (xs + 1 < nx).ravel()
    down = (ys + 1 < ny).ravel()
    # the grid streets in row-major order, each cell's right then down edge
    cand = np.stack([
        np.stack([vid, vid + ny], axis=1),
        np.stack([vid, vid + 1], axis=1),
    ], axis=1)
    keep = np.stack([right, down], axis=1)
    edges = cand[keep]

    perm = rng.permutation(len(edges))
    parent = np.arange(n)
    in_tree = np.zeros(len(edges), dtype=bool)
    for idx in perm.tolist():
        ru, rv = _find(parent, int(edges[idx, 0])), _find(parent, int(edges[idx, 1]))
        if ru != rv:
            parent[ru] = rv
            in_tree[idx] = True
    deletable = np.flatnonzero(~in_tree)
    n_del = min(int(spec["delete_frac"] * len(edges)), len(deletable))
    dropped = np.zeros(len(edges), dtype=bool)
    dropped[rng.choice(deletable, size=n_del, replace=False)] = True
    kept = edges[~dropped]

    n_diag = int(spec["diag_frac"] * n)
    dx = rng.integers(0, nx - 1, size=n_diag)
    dy = rng.integers(0, ny - 1, size=n_diag)
    flip = rng.random(n_diag) < 0.5
    du = np.where(flip, dx * ny + dy, (dx + 1) * ny + dy)
    dv = np.where(flip, (dx + 1) * ny + dy + 1, dx * ny + dy + 1)
    kept = np.concatenate([kept, np.stack([du, dv], axis=1)])

    ws = np.maximum(1.0, np.round(rng.uniform(spec["weight_low"], spec["weight_high"],
                                              size=len(kept))))
    return _from_edges(n, kept, ws)


def _from_edges(n: int, edges: np.ndarray, ws: np.ndarray) -> Network:
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    # parallel edges keep the shorter length
    order = np.lexsort((ws, hi, lo))
    lo, hi, ws = lo[order], hi[order], ws[order]
    first = np.ones(len(lo), dtype=bool)
    first[1:] = (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])
    lo, hi, ws = lo[first], hi[first], ws[first]
    us = np.concatenate([lo, hi])
    vs = np.concatenate([hi, lo])
    ww = np.concatenate([ws, ws])
    order = np.lexsort((vs, us))
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(us, minlength=n), out=indptr[1:])
    return Network(n=n, indptr=indptr, indices=vs[order].astype(np.int32),
                   weights=ww[order].astype(np.float64))


def is_connected(g: Network) -> bool:
    seen = np.zeros(g.n, dtype=bool)
    seen[0] = True
    frontier = np.array([0])
    while frontier.size:
        nb = np.concatenate([g.indices[g.indptr[v]:g.indptr[v + 1]] for v in frontier])
        nb = np.unique(nb[~seen[nb]])
        seen[nb] = True
        frontier = nb
    return bool(seen.all())


def spec_key(spec: dict) -> str:
    return hashlib.sha1(json.dumps(spec, sort_keys=True).encode()).hexdigest()[:16]


def _bn_to_csr(bn) -> dict:
    out = {"n": np.int64(bn.n), "rho": np.int64(bn.rho), "rank": bn.rank, "order": bn.order,
           "level_up": bn.level_up, "level_down": bn.level_down}
    for side in ("lo", "hi"):
        ids, w = getattr(bn, f"{side}_ids"), getattr(bn, f"{side}_w")
        valid = ids >= 0
        out[f"{side}_indptr"] = np.concatenate([[0], np.cumsum(valid.sum(axis=1))])
        out[f"{side}_idx"] = ids[valid]
        out[f"{side}_w"] = w[valid]
        out[f"{side}_width"] = np.int64(ids.shape[1])
    return out


def _bn_from_csr(z):
    from repro.core.bngraph import BNGraph

    n = int(z["n"])
    padded = {}
    for side in ("lo", "hi"):
        indptr = z[f"{side}_indptr"]
        width = int(z[f"{side}_width"])
        deg = np.diff(indptr)
        col = np.arange(len(z[f"{side}_idx"])) - np.repeat(indptr[:-1], deg)
        row = np.repeat(np.arange(n), deg)
        ids = np.full((n, width), -1, np.int32)
        w = np.full((n, width), np.inf, np.float64)
        ids[row, col] = z[f"{side}_idx"]
        w[row, col] = z[f"{side}_w"]
        padded[f"{side}_ids"], padded[f"{side}_w"] = ids, w
    return BNGraph(n=n, rank=z["rank"], order=z["order"], level_up=z["level_up"],
                   level_down=z["level_down"], rho=int(z["rho"]), **padded)


def load_network(spec: dict, cache_dir: Path = CACHE_DIR):
    """(Network, BNGraph) of one configuration's network, from the cache
    when a run in this checkout has built them before."""
    from repro.core.bngraph import build_bngraph
    from repro.graph.csr import Graph

    path = Path(cache_dir) / f"network-{spec_key(spec)}.npz"
    if path.exists():
        with np.load(path) as z:
            g = Network(n=int(z["n"]), indptr=z["g_indptr"], indices=z["g_indices"],
                        weights=z["g_weights"])
            return g, _bn_from_csr(z)
    g = road_network(spec)
    if not is_connected(g):
        raise ValueError(f"network {spec} is not connected")
    bn = build_bngraph(Graph(n=g.n, indptr=g.indptr, indices=g.indices, weights=g.weights))
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + ".partial.npz")
    np.savez(tmp, g_indptr=g.indptr, g_indices=g.indices, g_weights=g.weights,
             **_bn_to_csr(bn))
    os.replace(tmp, path)
    return g, bn
