"""Graph substrate: CSR invariants and generators."""
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.graph.csr import from_edges, is_connected
from repro.graph.generators import pick_objects, random_connected_graph, road_network


def test_from_edges_symmetry_and_min_parallel():
    g = from_edges(4, [(0, 1, 3.0), (1, 0, 2.0), (1, 2, 5.0), (2, 3, 1.0)])
    nbrs, ws = g.neighbors(0)
    assert list(nbrs) == [1] and list(ws) == [2.0]  # parallel edge keeps min
    nbrs1, _ = g.neighbors(1)
    assert 0 in nbrs1 and 2 in nbrs1


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 60), st.integers(0, 80), st.integers(0, 1000))
def test_random_graph_connected(n, extra, seed):
    g = random_connected_graph(n, extra_edges=extra, seed=seed)
    assert is_connected(g)
    # CSR degree bookkeeping consistent
    assert g.indptr[-1] == len(g.indices)


def test_road_network_stats():
    g = road_network(20, 20, seed=0)
    assert g.n == 400 and is_connected(g)
    deg = g.degrees()
    assert deg.mean() < 5  # road-like sparsity


def test_pick_objects_density():
    m = pick_objects(1000, 0.05, seed=0)
    assert len(m) == 50 and len(np.unique(m)) == 50
