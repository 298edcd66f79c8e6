"""Runtime rail: transfer guard, compile budgets, table scans, kernel poisoning.

The integration tests at the bottom pin the serving paths to the
checked-in ``tools/compile_budgets.json``: the warm counts must EQUAL the
budget (a warm compile is a recompile regression; a loose budget is
stale), the cold counts must fit under ``cold_max``.
"""
import json
import os
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import knn
from repro.analysis import sanitize
from repro.core.errors import SanitizerError
from repro.core.reference import knn_index_cons_plus
from repro.graph.generators import pick_objects, road_network


# ---------------------------------------------------------------------------
# transfer guard
# ---------------------------------------------------------------------------


def test_no_transfers_blocks_numpy_into_jit():
    f = jax.jit(lambda x: x + 1)
    host = np.arange(8, dtype=np.int32)
    f(jnp.asarray(host))  # compile outside the guard
    with pytest.raises(SanitizerError, match="transfer"):
        with sanitize.no_transfers("test"):
            f(host).block_until_ready()


def test_no_transfers_allows_explicit_put_and_readback():
    f = jax.jit(lambda x: x + 1)
    host = np.arange(8, dtype=np.int32)
    f(jax.device_put(host))
    with sanitize.no_transfers("test"):
        out = f(jax.device_put(host))
        back = np.asarray(out)  # explicit d2h stays legal
    assert back[0] == 1


def test_guard_is_noop_when_disabled(monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    f = jax.jit(lambda x: x * 2)
    with sanitize.guard("test"):
        f(np.arange(4, dtype=np.int32))  # implicit transfer, but guard is off


# ---------------------------------------------------------------------------
# compile counting + budgets
# ---------------------------------------------------------------------------


def test_count_compiles_cold_then_warm():
    def g(x):
        return x * 3 + 1

    gj = jax.jit(g)
    x = jnp.arange(97)  # shape unlikely to be cached by another test
    with sanitize.count_compiles() as cold:
        gj(x).block_until_ready()
    assert cold.count >= 1
    with sanitize.count_compiles() as warm:
        gj(x).block_until_ready()
    assert warm.count == 0


def test_assert_compiles_within(tmp_path, monkeypatch):
    budgets = tmp_path / "budgets.json"
    budgets.write_text('{"api": {"cold_max": 3, "warm": 0}}')
    monkeypatch.setenv("REPRO_COMPILE_BUDGETS", str(budgets))
    sanitize.assert_compiles_within("api", cold=3, warm=0)
    with pytest.raises(SanitizerError, match="cold"):
        sanitize.assert_compiles_within("api", cold=4)
    with pytest.raises(SanitizerError, match="warm"):
        sanitize.assert_compiles_within("api", warm=1)
    with pytest.raises(SanitizerError, match="no compile budget"):
        sanitize.assert_compiles_within("missing")


def test_count_transfers():
    with sanitize.count_transfers() as t:
        dev = jax.device_put(np.arange(8, dtype=np.int32))
        _ = np.asarray(dev)
    assert t.h2d == 1
    assert t.d2h == 1
    assert t.total == 2


# ---------------------------------------------------------------------------
# table scan
# ---------------------------------------------------------------------------


def _good_tables(n=6, k=3):
    ids = np.array([[1, 2, -1]] * n, np.int32)
    d = np.array([[0.5, 1.0, np.inf]] * n, np.float32)
    return ids, d


def test_scan_tables_accepts_valid():
    ids, d = _good_tables()
    sanitize.scan_tables(ids, d, 6)


@pytest.mark.parametrize(
    "mutate,msg",
    [
        (lambda ids, d: d.__setitem__((0, 0), np.nan), "NaN"),
        (lambda ids, d: d.__setitem__((0, 0), -1.0), "negative"),
        (lambda ids, d: ids.__setitem__((0, 0), 99), "outside"),
        (lambda ids, d: d.__setitem__((0, 2), 2.0), "pad slots"),
        (
            lambda ids, d: (
                ids.__setitem__((0, 0), -1),
                d.__setitem__((0, 0), np.inf),
            ),
            "right of pad",
        ),
        (lambda ids, d: d.__setitem__((0, 0), 1.5), "sorted"),
    ],
)
def test_scan_tables_rejects_corruption(mutate, msg):
    ids, d = _good_tables()
    mutate(ids, d)
    with pytest.raises(SanitizerError, match=msg):
        sanitize.scan_tables(ids, d, 6)


# ---------------------------------------------------------------------------
# kernel sanitizer (poisoned kernels vs oracles)
# ---------------------------------------------------------------------------


def test_kernel_aliasing_oracle_parity():
    sanitize.check_kernel_poisoning(interpret=True)


# ---------------------------------------------------------------------------
# serving-path budgets (the checked-in tools/compile_budgets.json)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_engine():
    g = road_network(8, 8, seed=3)
    objects = pick_objects(g.n, 0.2, seed=3)
    bn = knn.build_bngraph(g)
    idx = knn_index_cons_plus(bn, objects, k=4)
    return g, objects, knn.QueryEngine.from_index(idx, objects, bn=bn)


def test_query_batch_compile_budget(small_engine):
    g, objects, engine = small_engine
    us = np.arange(32, dtype=np.int32)
    with sanitize.count_compiles() as cold:
        engine.query_batch(us)
    with sanitize.count_compiles() as warm:
        engine.query_batch(us)
    sanitize.assert_compiles_within("query_batch", cold=cold.count, warm=warm.count)


def test_flush_updates_compile_budget(small_engine):
    g, objects, engine = small_engine
    obj_set = set(int(v) for v in np.asarray(objects).ravel())
    ins = [v for v in range(g.n) if v not in obj_set][:4]
    dels = sorted(obj_set)[:2]
    for v in ins:
        engine.stage_insert(v)
    for v in dels:
        engine.stage_delete(v)
    with sanitize.count_compiles() as cold:
        engine.flush_updates()
    # undo, then replay the same shapes: the warm path must not compile
    for v in ins:
        engine.stage_delete(v)
    for v in dels:
        engine.stage_insert(v)
    engine.flush_updates()
    for v in ins:
        engine.stage_insert(v)
    for v in dels:
        engine.stage_delete(v)
    with sanitize.count_compiles() as warm:
        engine.flush_updates()
    sanitize.assert_compiles_within("flush_updates", cold=cold.count, warm=warm.count)


# ---------------------------------------------------------------------------
# persistent compilation cache (cold-boot budget)
# ---------------------------------------------------------------------------

_COLD_BOOT = """
import json

import numpy as np
from repro.analysis import sanitize

# the one cache surface serve.py, knn_build.py and chip_smoke.py share:
# JAX_COMPILATION_CACHE_DIR, set by the parent test
assert str(sanitize.enable_compile_cache()) == {cache!r}

from repro import knn
from repro.core.reference import knn_index_cons_plus
from repro.graph.generators import pick_objects, road_network

g = road_network(8, 8, seed=3)
objects = pick_objects(g.n, 0.2, seed=3)
bn = knn.build_bngraph(g)
idx = knn_index_cons_plus(bn, objects, k=4)
engine = knn.QueryEngine.from_index(idx, objects, bn=bn)
obj_set = set(int(v) for v in np.asarray(objects).ravel())
ins = [v for v in range(g.n) if v not in obj_set][:4]
with sanitize.count_compiles() as c:
    engine.query_batch(np.arange(32, dtype=np.int32))
    for v in ins:
        engine.stage_insert(v)
    engine.flush_updates()
print(json.dumps({{"count": c.count, "uncached": c.uncached}}))
"""


def test_compile_cache_cold_boot_budget(tmp_path, devices_subprocess, monkeypatch):
    """A second process booting over a warm persistent cache dir must do
    no real compiles: its uncached count (backend compiles minus cache
    hits) must fit the *warm* serving budgets — a cold boot that recompiles
    is exactly the regression the cache exists to prevent."""
    cache = str(tmp_path / "xla-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    first = json.loads(
        devices_subprocess(_COLD_BOOT.format(cache=cache), n_devices=1)
    )
    # the cold process really compiled, and every program landed in the dir
    assert first["uncached"] > 0
    assert any(os.scandir(cache))
    second = json.loads(
        devices_subprocess(_COLD_BOOT.format(cache=cache), n_devices=1)
    )
    budgets = json.loads(
        (Path(__file__).parents[2] / "tools" / "compile_budgets.json").read_text()
    )
    warm_budget = (
        budgets["query_batch"]["warm"] + budgets["flush_updates"]["warm"]
    )
    assert second["uncached"] <= warm_budget, (
        f"cold boot over a warm cache recompiled "
        f"{second['uncached']} programs (budget {warm_budget})"
    )


def test_enable_compile_cache_noop_without_path(monkeypatch):
    """Without ``JAX_COMPILATION_CACHE_DIR`` the cache is no longer off: it
    lands at the fixed in-checkout ``.jax_cache`` (the same path on every
    run, so a second run hits); with the variable set, that directory wins."""
    repo = Path(__file__).resolve().parents[2]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert sanitize.compile_cache_dir() == repo / ".jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert sanitize.compile_cache_dir() == Path("/some/dir")
