"""The kNN Pallas kernels compile for a TPU v5e chip.

Interpret mode runs a kernel body on the CPU but does not apply the TPU
compiler's rules (block-shape tiling, lowerable primitives, fast-memory
limits). These tests compile ``sweep_merge``, ``frontier_relax`` and
``topk_merge`` through their ``ops`` wrappers, with ``interpret=False``, for
one chip of a described ``v5e:2x2`` topology at n = 2^20, k = 20, chunk 512
and B = 512, and the whole construction sweep program at the same n and k —
nothing runs, so no chip is needed. The topology is described
only inside the module fixture below, never at import.
"""
from __future__ import annotations

import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.core import construct_jax
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


def test_sweep_program_loop_copies_no_table_on_tpu(one_chip):
    """The XLA-form sweep loop copies no whole array on the chip: no (n+1, k)
    table and no bucket of the schedule in any computation but the entry
    (whose copies, once per sweep, only change the tables' layout)."""
    tiers = ((4, 8), (16, 8), (16, 64), (64, 64))
    rows, chunks = 4096, 256

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    buckets = tuple((sds((rows,), jnp.int32), sds((rows * t,), jnp.int32),
                     sds((rows * t,), jnp.float32)) for t, _ in tiers)
    hlo = construct_jax._sweep_program_jit.lower(
        buckets, sds((chunks,), jnp.int32), sds((chunks,), jnp.int32),
        sds((N + 1, K), jnp.int32), sds((N + 1, K), jnp.float32),
        n=N, k=K, chunks=tuple(c for _, c in tiers),
        use_pallas=False, interpret=False,
    ).compile().as_text()
    whole = {f"{N + 1},{K}"} | {f"{rows},{t}" for t, _ in tiers} | {
        f"{rows * t}" for t, _ in tiers}
    copy = re.compile(r"\[([\d,]+)\]\S* copy(-start)?\(")
    in_entry, inner = False, []
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            in_entry = line.startswith("ENTRY")
        elif not in_entry and (m := copy.search(line)) and m.group(1) in whole:
            inner.append(line.strip())
    assert " while(" in hlo
    assert not inner
