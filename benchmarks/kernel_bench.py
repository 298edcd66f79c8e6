"""Kernel micro-benchmarks (CPU wall time is NOT the roofline — interpret
mode / XLA-CPU; these check functional throughput trends and feed §Perf with
candidate-vs-candidate ratios that carry to TPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_us
from repro.kernels import ops


def bench_topk_merge() -> None:
    rng = np.random.default_rng(0)
    for b, c, k in [(1024, 256, 20), (4096, 128, 10)]:
        ids = jnp.asarray(rng.integers(0, 5000, (b, c)), jnp.int32)
        d = jnp.asarray(rng.uniform(0, 100, (b, c)), jnp.float32)
        out = ops.topk_merge(ids, d, k, use_pallas=False)
        jax.block_until_ready(out)
        t = time_us(lambda: jax.block_until_ready(ops.topk_merge(ids, d, k, use_pallas=False)))
        row(f"kernel.topk_merge.xla.b{b}c{c}k{k}", t, f"{b * c / t:.0f}cand/us")


def bench_minplus() -> None:
    rng = np.random.default_rng(0)
    for m in (256, 512):
        a = jnp.asarray(rng.uniform(0, 10, (m, m)), jnp.float32)
        b = jnp.asarray(rng.uniform(0, 10, (m, m)), jnp.float32)
        out = ops.minplus_matmul(a, b, use_pallas=False)
        jax.block_until_ready(out)
        t = time_us(lambda: jax.block_until_ready(ops.minplus_matmul(a, b, use_pallas=False)))
        row(f"kernel.minplus.xla.m{m}", t, f"{2 * m**3 / t / 1e6:.2f}Gop/s")


ALL = [bench_topk_merge, bench_minplus]
