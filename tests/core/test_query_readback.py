"""A query batch's answer leaves the device as one packed buffer, read back
once: ``query_batch`` returns host arrays, bit for bit the plain gather's
answer (the per-query k mask over the epoch's table rows), in every engine.
"""
import textwrap
from pathlib import Path

import numpy as np
import pytest

from conftest import run_devices_subprocess
from repro import knn
from repro.analysis import sanitize
from repro.core.reference import knn_index_cons_plus
from repro.core.sharded import ShardedQueryEngine
from repro.graph.generators import pick_objects, road_network
from repro.kernels import ops

HERE = Path(__file__).resolve().parent


def _plain_answer(tables, us, k, kq):
    """The gather and mask on the host: (B, width) ids and dists."""
    ids_t, d_t = tables
    if kq is None or np.ndim(kq) == 0:
        width = k if kq is None else int(kq)
        ks = np.full(len(us), width)
    else:
        width, ks = k, kq
    ids, d = ids_t[us], d_t[us].astype(np.float32)
    mask = np.arange(k)[None, :] < ks[:, None]
    ids_w = np.where(mask, ids, -1).astype(np.int32)
    d_w = np.where(mask & (ids >= 0), d, np.float32(np.inf)).astype(np.float32)
    return ids_w[:, :width], d_w[:, :width]


def _same(got, want):
    ids, d = got
    assert isinstance(ids, np.ndarray) and isinstance(d, np.ndarray)
    assert ids.dtype == np.int32 and d.dtype == np.float32
    np.testing.assert_array_equal(ids, want[0])
    np.testing.assert_array_equal(d.view(np.int32), want[1].view(np.int32))


def check_engine(kind: str) -> None:
    """Every answer form (full k, a width under k, mixed k with empty
    queries), at a small batch and at 4096 (the sharded engine's balanced
    two-phase path), on the current and a pinned older epoch: bit-equal to
    the plain answer, one readback a batch, counted in ``stats()``."""
    g = road_network(12, 12, seed=0)
    objects = pick_objects(g.n, 0.05, seed=0)  # 7 objects: fewer than k
    bn = knn.build_bngraph(g)
    k = 8
    idx = knn_index_cons_plus(bn, objects, k)
    if kind == "scalar":
        eng = knn.QueryEngine.from_index(idx, objects, bn=bn)
    else:
        eng = ShardedQueryEngine.from_index(idx, objects, bn=bn, shards=int(kind[-1]))
    rng = np.random.default_rng(1)
    old = eng._host_tables()
    # every row holds fewer than k objects: the -1/+inf pads are the tables' own
    assert (old[0] == -1).any() and np.isinf(old[1]).any()
    batches = 0
    for b in (64, 4096):
        us = rng.integers(0, g.n, size=b).astype(np.int32)
        for kq in (None, 3, rng.integers(0, k + 1, size=b).astype(np.int32)):
            with sanitize.count_transfers() as t:
                got = eng.query_batch(us, kq)
            batches += 1
            _same(got, _plain_answer(old, us, k, kq))
            # the sharded balanced path consolidates its two tiles through
            # host staging buffers (one readback a shard each)
            staged = 2 * eng.num_shards if kind == "sharded-2" and b == 4096 else 0
            assert t.d2h == 1 + staged, (kind, b, kq)
    assert eng.stats()["query_readbacks"] == batches
    # a pinned older epoch answers from its own tables
    objs = np.asarray(eng.objects)
    free = np.setdiff1d(np.arange(g.n), objs)
    for u in rng.choice(objs, 4, replace=False):
        eng.stage_delete(int(u))
    for v in rng.choice(free, 6, replace=False):
        eng.stage_insert(int(v))
    eng.flush_updates()
    new = eng._host_tables()
    assert not np.array_equal(new[0], old[0])
    us = rng.integers(0, g.n, size=64).astype(np.int32)
    kq = rng.integers(0, k + 1, size=64).astype(np.int32)
    _same(eng.query_batch(us, kq, epoch=0), _plain_answer(old, us, k, kq))
    _same(eng.query_batch(us, kq), _plain_answer(new, us, k, kq))
    batches += 2
    st = eng.stats()
    assert st["query_readbacks"] == st["query_batches"] == batches
    assert st["query_readback_bytes"] == 8 * k * (2 * 64 + 3 * (64 + 4096))
    assert st["spans"]["knn:query.readback"]["n"] == batches


_SUBPROCESS = textwrap.dedent("""
    import sys
    sys.path[:0] = [{core!r}, {tests!r}]
    from test_query_readback import check_engine
    check_engine({kind!r})
    print("ok")
""")


@pytest.mark.parametrize("kind", ["scalar", "sharded-1", "sharded-2"])
def test_query_batch_reads_back_the_plain_answer_once(kind, monkeypatch):
    # the sanitizer leg's table scans read the tables back besides
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    if kind == "sharded-2":
        code = _SUBPROCESS.format(core=str(HERE), tests=str(HERE.parent), kind=kind)
        assert run_devices_subprocess(code, n_devices=2).strip().endswith("ok")
    else:
        check_engine(kind)


def test_the_packed_answer_round_trips_bit_for_bit():
    """Pads, zeros, -0.0, subnormals and NaN bits come back as they went."""
    ids = np.array([[3, -1, 7], [0, 1, -1]], np.int32)
    d = np.array([[0.0, np.inf, -0.0], [1e-45, np.nan, 3.5]], np.float32)
    buf = np.asarray(ops.pack_answer(ids, d))
    assert buf.shape == (2 * 2 * 3,) and buf.dtype == np.int32
    for width in (3, 2):
        got_ids, got_d = ops.unpack_answer(buf, 3, width)
        np.testing.assert_array_equal(got_ids, ids[:, :width])
        np.testing.assert_array_equal(got_d.view(np.int32), d[:, :width].view(np.int32))
        assert np.shares_memory(got_ids, buf) and np.shares_memory(got_d, buf)
