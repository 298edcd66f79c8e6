"""Correctness tooling for the repo's device-residency invariants.

Two rails:

* **Static** — ``repro.analysis.replint`` (stdlib-only, importable without
  jax): an AST rule engine over the source tree that mechanizes the
  invariants six PRs of performance work rely on. Run it as

      python -m repro.analysis.replint src/

  Rules (see ``repro.analysis.rules``): REP001 host materialization inside
  jit-reachable code, REP002 Pallas input/output-aliasing hazards, REP003
  recompile risks, REP004 the int32/float32 kernel-boundary dtype contract,
  REP005 module-level ``jnp`` computation. Violations are suppressed only by
  a justified pragma: ``# replint: disable=REPxxx(reason)`` — the reason
  string is mandatory and its absence is itself an error.

* **Runtime** — ``repro.analysis.sanitize`` (imports jax): transfer-guard
  context managers the engines run their query/flush paths under in
  sanitizer mode (``REPRO_SANITIZE=1``), a compile counter checked against
  ``tools/compile_budgets.json``, a post-flush table invariant scanner, and
  a kernel sanitizer that replays each gathering Pallas kernel on poisoned
  pad/dummy slots against its ``kernels/ref.py`` oracle.

``sanitize`` is deliberately NOT imported here: the static rail must stay
importable in a bare-stdlib environment (the blocking ``analyze`` CI job
runs it without installing the jax stack).
"""
