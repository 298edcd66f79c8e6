"""Plain reference for the benchmark's ``correct``: Dijkstra kNN on the host.

It reads only the benchmark's own network (``network.Network``) and object
sets drawn from the seed, and imports nothing of the program. ``knn`` runs
Dijkstra from the query vertex until it has settled every object within the
k-th object's distance, so ties at the cut are all known; ``compare`` then
holds one answer row of the program to the configuration's guarantee: the
returned distances are exactly the k smallest, and every returned object is
an object of that epoch at exactly its returned distance, with no repeats.

``precision="bfloat16"`` is the control: the same search with every length
and sum rounded to bfloat16, the step below the float32 the configurations
state. At the configurations' lengths (100..1000 per arc) it must fail.
"""
from __future__ import annotations

import heapq

import numpy as np


def round_bf16(x: float) -> float:
    """Round a float to the nearest bfloat16 (ties to even)."""
    b = np.array([x], np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return float(b.view(np.float32)[0])


def knn(g, is_obj: np.ndarray, k: int, u: int, *, precision: str = "float64"):
    """(objects, dist): the objects within the k-th nearest object's distance
    from ``u`` as (id, d) sorted by (d, id), and every settled vertex's
    distance."""
    rnd = round_bf16 if precision == "bfloat16" else float
    dist = {u: 0.0}
    done: set[int] = set()
    heap = [(0.0, u)]
    found: list[tuple[int, float]] = []
    kth = None
    while heap:
        d, v = heapq.heappop(heap)
        if v in done:
            continue
        if kth is not None and d > kth:
            break
        done.add(v)
        if is_obj[v]:
            found.append((v, d))
            if len(found) == k:
                kth = d
        s, e = g.indptr[v], g.indptr[v + 1]
        for nb, w in zip(g.indices[s:e].tolist(), g.weights[s:e].tolist()):
            nd = rnd(d + rnd(w))
            if nd < dist.get(nb, np.inf):
                dist[nb] = nd
                heapq.heappush(heap, (nd, nb))
    found.sort(key=lambda t: (t[1], t[0]))
    return found, {v: dist[v] for v in done}


def answer(g, is_obj: np.ndarray, kq: int, u: int, width: int, *, precision: str = "float64"):
    """The reference's own answer row, padded like the program's: the first
    ``kq`` objects by (distance, id), then (-1, +inf) up to ``width``."""
    found, _ = knn(g, is_obj, kq, u, precision=precision)
    ids = np.full(width, -1, np.int64)
    d = np.full(width, np.inf)
    for j, (v, dv) in enumerate(found[:kq]):
        ids[j], d[j] = v, dv
    return ids, d


def compare(g, is_obj: np.ndarray, kq: int, u: int, ids, d) -> str | None:
    """None when the answer row (``ids``, ``d``) for a ``kq``-NN query at
    ``u`` meets the guarantee, else what is wrong with it."""
    found, settled = knn(g, is_obj, kq, u)
    want = min(kq, int(is_obj.sum()))
    ids = np.asarray(ids)
    d = np.asarray(d, np.float64)
    ref_d = [dv for _, dv in found[:want]]
    if not np.array_equal(d[:want], np.asarray(ref_d)):
        return f"distances {d[:want].tolist()} != {ref_d}"
    if (ids[want:] != -1).any() or np.isfinite(d[want:]).any():
        return f"entries past the {want} due: {ids[want:].tolist()}"
    got = ids[:want].tolist()
    if len(set(got)) != want:
        return f"repeated ids {got}"
    for j, v in enumerate(got):
        if not (0 <= v < len(is_obj)) or not is_obj[v]:
            return f"id {v} is not an object of this epoch"
        if settled.get(v) != d[j]:
            return f"id {v} at {d[j]} lies at {settled.get(v)}"
    return None
