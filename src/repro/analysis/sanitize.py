"""Runtime sanitizer rail: transfer guards, compile budgets, table scans.

The static rail (``replint``) proves properties of the *source*; this
module checks the ones only an execution can see:

* ``no_transfers()`` / ``guard(tag)`` — ``jax.transfer_guard("disallow")``
  around the engine's query/flush paths. Under the guard, an *implicit*
  transfer (a numpy array falling into a jitted call, an eager ``jnp.full``
  materializing a Python scalar, ``int()`` on a device scalar) raises;
  explicit ``jax.device_put`` / ``np.asarray(device_array)`` stay legal —
  exactly the discipline the serving paths are written to. The engines
  enable the guard when ``REPRO_SANITIZE=1`` (the sanitizer CI leg).
* ``count_compiles()`` — counts XLA backend compiles via the jax
  monitoring events, checked against ``tools/compile_budgets.json``
  (``assert_compiles_within``): a warm serving path that compiles is a
  regression of the 28->2 win, and it fails the test, not a log line.
* ``count_transfers()`` — counts explicit h2d (``jax.device_put``) and d2h
  (``__array__`` readbacks) so benchmarks can publish ``host_transfers``
  per row.
* ``scan_tables()`` — post-flush invariant scan of the (n, k) tables:
  NaN / negative / unsorted distances, out-of-range ids, pad slots that
  carry finite distances.
* ``check_kernel_poisoning()`` — replays the gathering Pallas kernels
  (``sweep_merge``, ``frontier_relax``) against their ``kernels/ref.py``
  oracles with *poisoned* buffers: every slot the kernel must mask (pad
  neighbor slots and their weights, the dummy row) is filled with trap
  values first. A kernel that forgets a pad mask, picks the wrong row out
  of a gathered tile, or reads a row the same step already rewrote,
  diverges from the oracle here.

Everything raises ``repro.core.errors.SanitizerError`` on violation.
"""
from __future__ import annotations

import contextlib
import json
import os
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp
from jax import monitoring

from repro.core.errors import SanitizerError

_COMPILE_EVENT = "backend_compile"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enabled() -> bool:
    """Sanitizer mode: set ``REPRO_SANITIZE=1`` (the sanitizer CI leg)."""
    return os.environ.get("REPRO_SANITIZE", "").lower() in ("1", "true", "yes", "on")


@contextlib.contextmanager
def no_transfers(tag: str = ""):
    """Disallow implicit host<->device transfers inside the block."""
    try:
        with jax.transfer_guard("disallow"):
            yield
    except jax.errors.JaxRuntimeError as e:
        if "transfer" in str(e).lower():
            where = f" on the `{tag}` path" if tag else ""
            raise SanitizerError(
                f"implicit host transfer{where}: {e}\n"
                "Use jax.device_put for uploads and np.asarray(device_array) "
                "for explicit readbacks; never pass raw numpy into a jitted call."
            ) from e
        raise


def guard(tag: str = ""):
    """``no_transfers(tag)`` when sanitizer mode is on, else a no-op."""
    return no_transfers(tag) if enabled() else contextlib.nullcontext()


# ---------------------------------------------------------------------------
# compile counting + budgets
# ---------------------------------------------------------------------------


class CompileCounter:
    """Number of XLA backend compiles observed while the context was live.

    ``count`` is every backend compile — including ones served from the
    persistent compilation cache (jax still emits the backend_compile
    duration event on a cache hit, it is just ~ms instead of ~s).
    ``cache_hits`` counts the hits, so ``uncached`` (= count - cache_hits)
    is what a process actually paid to compile from scratch — the number
    the cold-boot budget pins.
    """

    def __init__(self):
        self.count = 0
        self.cache_hits = 0

    @property
    def uncached(self) -> int:
        return self.count - self.cache_hits

    def _listen(self, name: str, duration: float, **kw) -> None:
        if _COMPILE_EVENT in name:
            self.count += 1

    def _listen_event(self, name: str, **kw) -> None:
        if name == _CACHE_HIT_EVENT:
            self.cache_hits += 1


@contextlib.contextmanager
def count_compiles():
    counter = CompileCounter()
    monitoring.register_event_duration_secs_listener(counter._listen)
    monitoring.register_event_listener(counter._listen_event)
    try:
        yield counter
    finally:
        monitoring.unregister_event_duration_listener(counter._listen)
        monitoring.unregister_event_listener(counter._listen_event)


_REPO = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> Path:
    """Where the persistent compilation cache lives.

    ``JAX_COMPILATION_CACHE_DIR`` when it is set (jax itself reads it at
    import), else the fixed ``.jax_cache`` directory of this checkout. The
    path is part of the cache key, so it is never built from a temporary
    name, a process id or the time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return Path(env) if env else _REPO / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on jax's persistent compilation cache; returns its directory.

    The one place the cache is set up: ``serve.py``, ``knn_build.py`` and
    ``chip_smoke.py`` all call this before their first compile. With
    ``JAX_COMPILATION_CACHE_DIR`` set, jax already points there and no
    other directory is set. The min-compile-time/min-entry-size floors are
    zeroed so even the CPU backend's fast compiles persist — the point is
    cold-boot serving, and a second boot should pay the *warm* budget.
    """
    path = compile_cache_dir()
    path.mkdir(parents=True, exist_ok=True)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def budgets_path() -> Path:
    env = os.environ.get("REPRO_COMPILE_BUDGETS")
    if env:
        return Path(env)
    return _REPO / "tools" / "compile_budgets.json"


def load_budgets() -> dict:
    with open(budgets_path()) as f:
        return json.load(f)


def assert_compiles_within(api: str, cold: int | None = None, warm: int | None = None):
    """Check measured compile counts against the checked-in budget.

    ``warm`` must EQUAL the budget (a warm path that compiles at all is a
    regression; a budget that is too loose is stale and must be lowered).
    ``cold`` must not exceed ``cold_max``.
    """
    budget = load_budgets().get(api)
    if budget is None:
        raise SanitizerError(
            f"no compile budget for `{api}` in {budgets_path()}; add one"
        )
    if cold is not None and cold > budget["cold_max"]:
        raise SanitizerError(
            f"`{api}` cold path compiled {cold} programs, budget cold_max="
            f"{budget['cold_max']} ({budgets_path()})"
        )
    if warm is not None and warm != budget["warm"]:
        raise SanitizerError(
            f"`{api}` warm path compiled {warm} programs, budget requires "
            f"exactly {budget['warm']} ({budgets_path()}); a higher count is a "
            "recompile regression, a lower budget means the file is stale"
        )


# ---------------------------------------------------------------------------
# transfer counting (benchmark `host_transfers` column)
# ---------------------------------------------------------------------------


class TransferCounter:
    def __init__(self):
        self.h2d = 0
        self.d2h = 0

    @property
    def total(self) -> int:
        return self.h2d + self.d2h


@contextlib.contextmanager
def count_transfers():
    """Count explicit host<->device crossings inside the block.

    h2d: ``jax.device_put`` calls (after the residency fixes, ALL serving
    uploads are explicit). d2h: ``np.asarray`` / ``np.array`` calls whose
    argument is a jax array — the repo's one idiom for explicit readback
    (numpy reaches the device buffer through the buffer protocol, so the
    interposition has to happen on the numpy side). Meant to run together
    with ``no_transfers``, which rules the implicit ones out.
    """
    counter = TransferCounter()
    orig_put = jax.device_put
    orig_asarray = np.asarray
    orig_array = np.array

    def counting_put(*args, **kwargs):
        counter.h2d += 1
        return orig_put(*args, **kwargs)

    def counting_asarray(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            counter.d2h += 1
        return orig_asarray(a, *args, **kwargs)

    def counting_array(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            counter.d2h += 1
        return orig_array(a, *args, **kwargs)

    jax.device_put = counting_put
    np.asarray = counting_asarray
    np.array = counting_array
    try:
        yield counter
    finally:
        jax.device_put = orig_put
        np.asarray = orig_asarray
        np.array = orig_array


# ---------------------------------------------------------------------------
# post-flush table scan
# ---------------------------------------------------------------------------


def scan_tables(ids, dists, n: int, *, context: str = "") -> None:
    """Invariant scan of host-layout (rows, k) tables; raises on corruption.

    Checked: ids int-typed in [-1, n); no NaN; no negative distance; rows
    ascending (ties allowed); pad slots (id == -1) at +inf and packed to
    the right of every real entry.
    """
    ids = np.asarray(ids)
    d = np.asarray(dists)
    where = f" ({context})" if context else ""
    problems = []
    if np.isnan(d).any():
        problems.append(f"{int(np.isnan(d).sum())} NaN distances")
    if (d < 0).any():
        problems.append(f"{int((d < 0).sum())} negative distances")
    if ids.size:
        if int(ids.min()) < -1 or int(ids.max()) >= n:
            problems.append(
                f"ids outside [-1, {n}): min={int(ids.min())} max={int(ids.max())}"
            )
        pad = ids < 0
        if not np.isinf(np.where(pad, d, np.inf)).all():
            problems.append("pad slots (id=-1) carrying finite distances")
        # pads packed right: a real id after a pad breaks the k-list contract
        if (np.diff(pad.astype(np.int8), axis=1) < 0).any():
            problems.append("real entries to the right of pad slots")
        dd = np.where(pad, np.inf, d)
        fin = np.isfinite(dd[:, 1:]) & np.isfinite(dd[:, :-1])
        with np.errstate(invalid="ignore"):  # inf - inf on pad tails
            if (np.where(fin, np.diff(dd, axis=1), 0.0) < 0).any():
                problems.append("rows not sorted by distance")
    if problems:
        raise SanitizerError(
            f"post-flush table scan failed{where}: " + "; ".join(problems)
        )


# ---------------------------------------------------------------------------
# kernel sanitizer: poisoned kernels vs host oracles
# ---------------------------------------------------------------------------


def check_kernel_poisoning(*, k: int = 4, seed: int = 0, interpret: bool = True) -> None:
    """Replay the gathering Pallas kernels on poisoned inputs vs ref oracles.

    Poison pattern: pad neighbor slots carry huge finite garbage behind
    their -1 ids and the dummy row holds NaN-free trap values — an unmasked
    pad slot or a non-Jacobi read shows up as an exact-equality miss vs the
    oracle.
    """
    from repro.kernels import ops, ref
    from repro.kernels.frontier_relax import frontier_relax_pallas

    rng = np.random.default_rng(seed)
    trap = np.float32(7e7)  # finite, absurd, impossible to produce legally

    # --- sweep_merge: (chunk, t) gather/scatter over the live tables -------
    n, chunk, t, e = 12, 4, 3, 2
    n1 = n + 1
    # level-schedule contract: target rows and neighbor rows are disjoint
    # within a call (targets even, neighbors odd)
    nbr = (rng.integers(0, n // 2, (chunk, t)) * 2 + 1).astype(np.int32)
    nbr[0, -1] = -1  # a padded neighbor slot
    verts = np.arange(chunk, dtype=np.int32) * 2
    w = rng.uniform(0.5, 2.0, (chunk, t)).astype(np.float32)
    w[nbr < 0] = trap  # poisoned: must be masked by the id, not the weight
    ex_ids = np.full((n1, e), -1, np.int32)
    ex_ids[: n // 2] = rng.integers(0, n, (n // 2, e), dtype=np.int32)
    ex_d = np.where(ex_ids >= 0, rng.uniform(0, 3, (n1, e)), trap).astype(np.float32)
    vk_ids = rng.integers(0, n, (n1, k), dtype=np.int32)
    vk_d = np.sort(rng.uniform(0, 5, (n1, k)), axis=1).astype(np.float32)
    vk_ids[-1] = -1
    vk_d[-1] = trap  # poisoned dummy row: reads of it must be id-masked

    want = ref.sweep_merge_ref(
        jnp.asarray(nbr), jnp.asarray(verts), jnp.asarray(w),
        jnp.asarray(ex_ids), jnp.asarray(ex_d),
        jnp.asarray(vk_ids), jnp.asarray(vk_d), k=k,
    )
    got = ops.sweep_merge(
        jnp.asarray(nbr), jnp.asarray(verts), jnp.asarray(w),
        jnp.asarray(ex_ids), jnp.asarray(ex_d),
        jnp.asarray(vk_ids), jnp.asarray(vk_d),
        k, use_pallas=True, interpret=interpret,
    )
    for name, g, wnt in (("ids", got[0], want[0]), ("dists", got[1], want[1])):
        g = np.asarray(g)
        if not np.array_equal(g, wnt):
            bad = int((g != wnt).sum())
            raise SanitizerError(
                f"sweep_merge diverges from ref oracle on poisoned buffers "
                f"({name}: {bad} cells) — non-Jacobi read or pad-mask bug"
            )

    # --- frontier_relax: (n+1, B) scatter, Jacobi read discipline ----------
    r, tt, b = 5, 3, 4
    nbr2 = rng.integers(0, n, (r, tt), dtype=np.int32)
    nbr2[1, -1] = -1
    rows = rng.choice(n, r, replace=False).astype(np.int32)
    w2 = rng.uniform(0.5, 2.0, (r, tt)).astype(np.float32)
    w2[nbr2 < 0] = trap
    dist = rng.uniform(0, 4, (n1, b)).astype(np.float32)
    dist[-1] = np.inf  # dummy row
    kth = np.full(n1, 3.0, np.float32)
    kth[-1] = np.inf
    src = rng.integers(0, n, b, dtype=np.int32)

    want2 = ref.frontier_relax_ref(
        jnp.asarray(nbr2), jnp.asarray(rows), jnp.asarray(w2),
        jnp.asarray(dist), jnp.asarray(kth), jnp.asarray(src),
    )
    got2 = frontier_relax_pallas(
        jnp.asarray(nbr2), jnp.asarray(rows), jnp.asarray(w2),
        jnp.asarray(dist), jnp.asarray(kth), jnp.asarray(src),
        interpret=interpret,
    )
    got2 = np.asarray(got2)
    if not np.array_equal(got2, np.asarray(want2, np.float32)):
        bad = int((got2 != np.asarray(want2, np.float32)).sum())
        raise SanitizerError(
            f"frontier_relax diverges from ref oracle on poisoned buffers "
            f"({bad} cells) — the Jacobi read discipline is broken"
        )
