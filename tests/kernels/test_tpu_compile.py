"""The kNN Pallas kernels compile for a TPU v5e chip.

Interpret mode runs a kernel body on the CPU but does not apply the TPU
compiler's rules (block-shape tiling, lowerable primitives, fast-memory
limits). These tests compile ``sweep_merge``, ``frontier_relax`` and
``topk_merge`` through their ``ops`` wrappers, with ``interpret=False``, for
one chip of a described ``v5e:2x2`` topology at n = 2^20, k = 20, chunk 512
and B = 512 — nothing runs, so no chip is needed. The topology is described
only inside the module fixture below, never at import.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops

N, K, CHUNK, B = 1 << 20, 20, 512, 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    print(fn.__name__, compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("t", [8, 32])
def test_sweep_merge_compiles_for_tpu(one_chip, t):
    def sweep_merge(nbr, verts, w, ex_ids, ex_d, vk_ids, vk_d):
        return ops.sweep_merge(
            nbr, verts, w, ex_ids, ex_d, vk_ids, vk_d, K, use_pallas=True, interpret=False
        )

    _compile(
        sweep_merge, one_chip,
        ((CHUNK, t), jnp.int32), ((CHUNK,), jnp.int32), ((CHUNK, t), jnp.float32),
        ((N + 1, K), jnp.int32), ((N + 1, K), jnp.float32),
        ((N + 1, K), jnp.int32), ((N + 1, K), jnp.float32),
    )


@pytest.mark.parametrize("t", [8, 32])
def test_frontier_relax_compiles_for_tpu(one_chip, t):
    def frontier_relax(nbr, rows, w, dist, kth, src):
        return ops.frontier_relax(
            nbr, rows, w, dist, kth, src, use_pallas=True, interpret=False
        )

    _compile(
        frontier_relax, one_chip,
        ((CHUNK, t), jnp.int32), ((CHUNK,), jnp.int32), ((CHUNK, t), jnp.float32),
        ((N + 1, B), jnp.float32), ((N + 1,), jnp.float32), ((B,), jnp.int32),
    )


@pytest.mark.parametrize("t", [8, 32])
def test_topk_merge_compiles_for_tpu(one_chip, t):
    # the candidate width of one construction step: T neighbor k-lists + k extras
    def topk_merge(cand_ids, cand_d):
        return ops.topk_merge(cand_ids, cand_d, K, use_pallas=True, interpret=False)

    c = t * K + K
    _compile(topk_merge, one_chip, ((CHUNK, c), jnp.int32), ((CHUNK, c), jnp.float32))
