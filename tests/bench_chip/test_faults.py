"""The harness drives a whole run with the timed path broken underneath,
and ``correct`` comes out false: once for each fault a cell can have."""
import functools

import numpy as np
import pytest

from bench_names import FLEET, cache_dir_fixture, run_tiny_fixture  # noqa: F401


def _answer_altered(monkeypatch):
    from repro.kernels import ops

    orig = ops.serve_gather

    def altered(vk_ids, vk_d, queries, ks):
        ids, d = orig(vk_ids, vk_d, queries, ks)
        return ids, d.at[:, 0].add(1.0)

    monkeypatch.setattr(ops, "serve_gather", altered)


def _half_batch_left_out(monkeypatch):
    from repro.kernels import ops

    orig = ops.serve_gather

    def half(vk_ids, vk_d, queries, ks):
        ids, d = orig(vk_ids, vk_d, queries, ks)
        b = ids.shape[0] // 2
        return ids.at[b:].set(-1), d.at[b:].set(np.inf)

    monkeypatch.setattr(ops, "serve_gather", half)


def _flush_unchanged(monkeypatch):
    from repro.core.engine import EngineCore

    def unchanged(self):
        # acknowledges the queue and publishes nothing new
        self._objects = set(self._pending)
        self._staged.clear()
        return {"frontier_rounds": 0, "repair_rounds": 0}

    monkeypatch.setattr(EngineCore, "flush_updates", unchanged)


def _build_unchanged(monkeypatch):
    from repro.core import construct_jax

    orig = construct_jax.build_knn_tables_jax

    @functools.wraps(orig)
    def first_only(bn, objects, k, **kw):
        if not hasattr(first_only, "tables"):
            first_only.tables = orig(bn, objects, k, **kw)
        return first_only.tables

    monkeypatch.setattr(construct_jax, "build_knn_tables_jax", first_only)


def _build_half(monkeypatch):
    from repro.core import construct_jax

    orig = construct_jax.build_knn_tables_jax
    monkeypatch.setattr(construct_jax, "build_knn_tables_jax",
                        lambda bn, objects, k, **kw: orig(bn, objects[::2], k, **kw))


def _build_altered(monkeypatch):
    from repro.core import construct_jax

    orig = construct_jax.build_knn_tables_jax

    def altered(bn, objects, k, **kw):
        ids, d = orig(bn, objects, k, **kw)
        return ids, d.at[:, 0].add(1.0)

    monkeypatch.setattr(construct_jax, "build_knn_tables_jax", altered)


READ, BUILD = "grid362-poi-k20.zipf-read", "grid362-poi-k20.rebuild"
FAULTS = [
    (READ, {}, _answer_altered),
    (READ, {}, _half_batch_left_out),
    (READ, FLEET, _answer_altered),
    (READ, FLEET, _half_batch_left_out),
    (READ, FLEET, _flush_unchanged),
    (BUILD, {}, _build_unchanged),
    (BUILD, {}, _build_half),
    (BUILD, {}, _build_altered),
]


@pytest.mark.parametrize(
    "cell,overrides,fault", FAULTS,
    ids=[f"{c}{'-fleet' if o else ''}-{f.__name__.strip('_')}" for c, o, f in FAULTS])
def test_fault_makes_the_run_incorrect(cell, overrides, fault, run_tiny, monkeypatch):
    fault(monkeypatch)
    result = run_tiny(cell, overrides=overrides)
    assert result["correct"] is False
    assert result["checks"]["wrong_answers"]["value"] > 0
