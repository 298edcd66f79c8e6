"""The kNN Pallas kernels and the engine's own programs compile for a TPU
v5e chip.

Interpret mode runs a kernel body on the CPU but does not apply the TPU
compiler's rules (block-shape tiling, lowerable primitives, fast-memory
limits). These tests compile ``sweep_merge``, ``frontier_relax`` and
``topk_merge`` through their ``ops`` wrappers, with ``interpret=False``, for
one chip of a described ``v5e:2x2`` topology at n = 2^20, k = 20, chunk 512
and B = 512, and the whole construction sweep program at the same n and k.
They also compile every flush program a ``QueryEngine`` lists
(``_flush_signatures``) for a 40 x 40 road network, and the query program
(``ops.answer_program(ops.serve_gather)``) at the benchmark cells' shapes —
nothing runs, so no chip is needed. The topology is described only inside
the module fixture below, never at import.
"""
from __future__ import annotations

import functools
import os
import re
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import knn
from repro.core import construct_jax
from repro.core.reference import knn_index_cons_plus
from repro.graph.generators import road_network
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


@functools.cache
def _fleet_engine():
    """The QueryEngine of tests/core/test_flush_programs.py: a 40 x 40 road
    network, K = 6, an object at every 20th vertex."""
    g = road_network(40, 40, seed=1)
    bn = knn.build_bngraph(g)
    objects = np.arange(0, g.n, 20, dtype=np.int32)
    return knn.QueryEngine.from_index(knn_index_cons_plus(bn, objects, 6), objects, bn=bn)


def _on_chip(arg, one_chip):
    if isinstance(arg, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(arg.shape, arg.dtype, sharding=one_chip)
    return arg


@pytest.fixture(scope="module")
def flush_compiles(one_chip):
    """Every flush program compiled for the chip as ``_warm_flush`` compiles
    them (lowered one after another, compiled in parallel): signature ->
    None, or the error its lowering or compile raised."""
    eng = _fleet_engine()

    def lower(sig):
        fn, args, kwargs = eng._flush_program(sig)
        try:
            return fn.lower(*(_on_chip(a, one_chip) for a in args), **kwargs)
        except Exception as e:  # noqa: BLE001 — reported by the signature's case
            return e

    def compile_(low):
        if isinstance(low, Exception):
            return low
        try:
            low.compile()
        except Exception as e:  # noqa: BLE001 — reported by the signature's case
            return e
        return None

    sigs = eng._flush_signatures()
    lowered = [lower(sig) for sig in sigs]
    with ThreadPoolExecutor(os.cpu_count()) as pool:
        return dict(zip(sigs, pool.map(compile_, lowered)))


@pytest.mark.parametrize("sig", _fleet_engine()._flush_signatures(),
                         ids=lambda sig: "-".join(map(str, sig)))
def test_every_flush_signature_compiles_for_tpu(flush_compiles, sig):
    """Each program a flush dispatches, on the arguments it is dispatched
    with (``QueryEngine._flush_program``), compiles for the chip."""
    if flush_compiles[sig] is not None:
        raise flush_compiles[sig]


@pytest.mark.parametrize("b,k", [(4096, 20), (1024, 10)])
def test_query_program_compiles_for_tpu(one_chip, b, k):
    """The query batch's one program at the cells' shapes (n = 131,044 plus
    the dummy row): its single output is the packed (B*2k,) int32 answer."""
    rows = 131_044 + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = ops.answer_program(ops.serve_gather).lower(
        sds((rows, k), jnp.int32), sds((rows, k), jnp.float32),
        sds((b,), jnp.int32), sds((b,), jnp.int32),
    ).compile()
    out = compiled.out_info
    assert isinstance(out, jax.ShapeDtypeStruct)
    assert (out.shape, out.dtype) == ((b * 2 * k,), jnp.int32)
