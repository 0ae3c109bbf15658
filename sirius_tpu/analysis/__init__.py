"""sirius-lint: JAX-aware static analysis for the sirius_tpu tree.

Six rule families keep the invariants the test suite cannot check
mechanically:

- **JAX rules** (analysis/jaxrules.py), scoped to *jit-reachable*
  functions (the transitive closure of every ``jax.jit``/``jax.pmap``
  seed and ``jax.lax`` higher-order body over the project call graph):
  tracer-hostile Python control flow, ``np.*`` calls and Python-float
  accumulation inside compiled code, implicit host syncs, donated-buffer
  reuse, dtype-less array creation (the fp64-path drift groundwork for
  the mixed-precision ladder), and non-hashable static arguments.
- **Concurrency rules** (analysis/lockrules.py) for the threaded
  ``serve/`` modules: a static lock-acquisition graph built from
  ``with self._lock:`` nesting and called-method edges (Condition
  aliasing resolved), cycle detection (potential deadlock), unlocked
  shared-attribute writes reachable from two threads, and the
  ``*_locked``-naming contract.
- **Registry-consistency rules** (analysis/registryrules.py): every
  ``control.*`` read must name a ``config/schema.py`` field, every
  fault-site literal must be in ``utils/faults.KNOWN_SITES``, every
  ``scf.*``/``md.*`` span must have an ``obs/costs.scf_stage_costs``
  key or an ``UNCOSTED_SPANS`` exemption, every ``emit(kind, ...)``
  literal must be in ``obs/events.KNOWN_EVENT_KINDS``, and every
  production ``REGISTRY.counter/gauge/histogram`` name must be in
  ``obs/metrics.KNOWN_METRIC_NAMES``.
- **Recompile-hazard rules** (analysis/compilerules.py), built on the
  interprocedural device-dataflow model in analysis/dataflow.py:
  ``jax.jit`` wrappers constructed inside loop bodies, per-call-varying
  values (loop indices, ``time.*``/``random.*``) at
  ``static_argnums``/``static_argnames`` positions, and the
  serve/cache.py cross-check — any ``self.<attr>`` a cache-shared
  jitted impl reads but its ``_trace_signature()`` omits, and any value
  a ``partial`` binds into a jitted impl that the key of the table it
  is kept in omits (dft/fused.py ``step_program``).
- **Transfer-budget rules** (analysis/transferrules.py): device→host
  crossings statically enumerated from the dataflow model and checked
  against the checked-in ``TRANSFER_BUDGET.json`` manifest — the fused
  SCF loop's one-readback-per-iteration contract is *proved* at the
  AST level, attributable to source lines.
- **Sharding-consistency rules** (analysis/shardrules.py): a static
  mesh/axis model (every ``Mesh(...)`` construction and producer),
  collective ``axis_name``s checked against declared axes,
  NamedSharding/shard_map spec-vs-mesh mismatches,
  ``with_sharding_constraint`` in jit-reachable loop bodies, and the
  per-driver sharding inventory (``sirius-lint --report sharding``).

Findings are suppressed per line with ``# sirius-lint: disable=RULE``
(or ``disable=*``), per file with ``# sirius-lint: disable-file=RULE``,
and per tree with the checked-in ``LINT_BASELINE.json`` — CI fails only
on *new* violations (``sirius-lint --baseline LINT_BASELINE.json``).
Baseline fingerprints are rename-stable: keyed on (rule, normalized
finding text, enclosing qualname), never on path or line. Stale
suppressions are audited by ``sirius-lint --check-suppressions``
(``--strict`` fails on them) and SARIF 2.1.0 output for review UIs
comes from ``--sarif PATH``.
"""

from sirius_tpu.analysis.core import (  # noqa: F401
    Finding,
    LintEngine,
    ProjectIndex,
    all_rules,
    load_baseline,
    write_baseline,
)
