"""sirius-lint: JAX rules on jit-reachable code, serve lock-order
analysis, registry-consistency checks, suppression comments, the findings
baseline, and the live-tree gate (repo must lint clean modulo the checked-in
LINT_BASELINE.json, with zero lock cycles in serve/).

The v2 families (interprocedural jit-dataflow): recompile hazards
(compilerules), transfer budgets against TRANSFER_BUDGET.json
(transferrules — including the live proof of the fused SCF
one-readback-per-iteration contract), sharding consistency and the
per-driver inventory (shardrules), event/metric registry cross-checks,
rename-stable fingerprints, the stale-suppression audit, SARIF output,
and the <60 s lint-runtime budget."""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from sirius_tpu.analysis import (
    compilerules,
    jaxrules,
    lockrules,
    registryrules,
    shardrules,
    transferrules,
)
from sirius_tpu.analysis.core import (
    DEFAULT_SCAN,
    LintEngine,
    collect_files,
    load_baseline,
    new_findings,
    write_baseline,
)
from sirius_tpu.analysis.registryrules import RegistryConfig
from sirius_tpu.analysis.sarif import to_sarif

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(tmp_path, files, rules=None, registry=None):
    """Materialise a fixture tree under tmp_path and lint it."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    eng = LintEngine(str(tmp_path), rules=rules, registry=registry)
    return eng, eng.run()


def names(findings):
    return sorted(f.rule for f in findings)


JIT_HEADER = """\
    import jax
    import jax.numpy as jnp
    import numpy as np
"""


# ------------------------------------------------------------- JAX rules


def test_traced_control_flow_positive_and_negative(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def bad(x):
        y = jnp.sin(x)
        if y > 0:
            return y
        return -y

    def not_jitted(x):
        y = jnp.sin(x)
        if y > 0:  # same shape, but never traced
            return y
        return -y

    @jax.jit
    def static_ok(x, aux):
        y = jnp.cos(x)
        if aux is None:  # identity check: static at trace time
            return y
        return y + aux
    """}, rules=[jaxrules.JitTracedControlFlow])
    assert names(found) == ["jit-traced-control-flow"]
    assert found[0].line == 8  # the `if y > 0` inside bad()


def test_traced_control_flow_python_bool_untainted_ok(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def f(x, polarized: bool):
        if polarized:  # plain Python flag, static under jit
            return jnp.sin(x)
        return jnp.cos(x)
    """}, rules=[jaxrules.JitTracedControlFlow])
    assert found == []


def test_numpy_call_in_jit(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def bad(x):
        return np.sum(x)

    def host_side(x):
        return np.sum(x)  # fine: not jit-reachable
    """}, rules=[jaxrules.JitNumpyCall])
    assert names(found) == ["jit-numpy-call"]


def test_host_sync_in_jit(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def bad(x):
        y = jnp.sum(x)
        return float(y)

    @jax.jit
    def ok(n):
        return float(3)  # untainted literal: no device sync
    """}, rules=[jaxrules.JitHostSync])
    assert names(found) == ["jit-host-sync"]


def test_jit_reachability_through_helpers(tmp_path):
    """The np.* call is in a helper two hops below the jit boundary."""
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    def leaf(x):
        return np.dot(x, x)

    def middle(x):
        return leaf(x) + 1

    @jax.jit
    def entry(x):
        return middle(x)
    """}, rules=[jaxrules.JitNumpyCall])
    assert names(found) == ["jit-numpy-call"]


def test_dtype_literal_keyword_and_positional(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def f(n):
        a = jnp.zeros((3,))                    # flagged
        b = jnp.zeros((3,), dtype=jnp.float64)  # keyword dtype ok
        c = jnp.zeros((), bool)                # positional dtype ok
        d = jnp.full((2,), 1.0, jnp.float32)   # positional dtype ok
        return a, b, c, d
    """}, rules=[jaxrules.JitDtypeLiteral])
    assert names(found) == ["jit-dtype-literal"]
    assert "jnp.zeros((3,))" in found[0].text


def test_python_float_accumulation(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def bad(xs):
        acc = 0.0
        for i in range(3):
            acc += jnp.sum(xs)
        return acc
    """}, rules=[jaxrules.JitPythonFloatAccum])
    assert names(found) == ["jit-python-float-accum"]


def test_nonhashable_static_arg(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    def kernel(x, shape):
        return jnp.zeros(shape, jnp.float64) + x

    def caller(x):
        g = jax.jit(kernel, static_argnums=(1,))
        g(x, (4, 4))   # tuple: hashable, fine
        return g(x, [4, 4])  # list literal at static position
    """}, rules=[jaxrules.JitNonHashableStatic])
    assert names(found) == ["jit-nonhashable-static"]


def test_donated_buffer_reuse(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    def step(state, dx):
        return state + dx

    def drive(state, dx):
        g = jax.jit(step, donate_argnums=(0,))
        out = g(state, dx)
        return out + state  # state was donated above
    """}, rules=[jaxrules.JitDonatedReuse])
    assert names(found) == ["jit-donated-reuse"]


def test_jit_expression_seed_and_partial_unwrap(tmp_path):
    """jax.jit(partial(f, ...)) must seed f's closure too."""
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    from functools import partial

    def kern(x, n):
        return np.ones(n) + x

    def build():
        return jax.jit(partial(kern, n=4))
    """}, rules=[jaxrules.JitNumpyCall])
    assert names(found) == ["jit-numpy-call"]


# ----------------------------------------------------------- suppression


def test_inline_suppression(tmp_path):
    eng, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def f(x):
        return np.sum(x)  # sirius-lint: disable=jit-numpy-call
    """}, rules=[jaxrules.JitNumpyCall])
    assert found == []
    assert eng.suppressed_count == 1


def test_file_suppression_and_star(tmp_path):
    eng, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    # sirius-lint: disable-file=jit-numpy-call
    @jax.jit
    def f(x):
        a = np.sum(x)          # silenced file-wide
        b = jnp.zeros((3,))  # sirius-lint: disable=*
        return a, b
    """}, rules=[jaxrules.JitNumpyCall, jaxrules.JitDtypeLiteral])
    assert found == []
    assert eng.suppressed_count == 2


def test_suppression_is_per_rule(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def f(x):
        return np.sum(x)  # sirius-lint: disable=jit-host-sync
    """}, rules=[jaxrules.JitNumpyCall])
    assert names(found) == ["jit-numpy-call"]  # wrong rule name: no effect


# ------------------------------------------------------------ lock rules

LOCK_HEADER = """\
    import threading
"""


def test_lock_order_cycle_detected(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/serve/locky.py": LOCK_HEADER + """
    class S:
        def __init__(self):
            self._la = threading.Lock()
            self._lb = threading.Lock()

        def one(self):
            with self._la:
                with self._lb:
                    pass

        def two(self):
            with self._lb:
                with self._la:
                    pass
    """}, rules=[lockrules.LockOrderCycle])
    assert "lock-order-cycle" in names(found)


def test_lock_order_consistent_is_clean(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/serve/locky.py": LOCK_HEADER + """
    class S:
        def __init__(self):
            self._la = threading.Lock()
            self._lb = threading.Lock()

        def one(self):
            with self._la:
                with self._lb:
                    pass

        def two(self):
            with self._la:
                self.one_inner()

        def one_inner(self):
            with self._lb:
                pass
    """}, rules=[lockrules.LockOrderCycle])
    assert found == []


def test_lock_cycle_through_called_method(tmp_path):
    """Cycle only visible once `with lb: self.grab_a()` edges are added."""
    _, found = lint(tmp_path, {"sirius_tpu/serve/locky.py": LOCK_HEADER + """
    class S:
        def __init__(self):
            self._la = threading.Lock()
            self._lb = threading.Lock()

        def fwd(self):
            with self._la:
                with self._lb:
                    pass

        def rev(self):
            with self._lb:
                self.grab_a()

        def grab_a(self):
            with self._la:
                pass
    """}, rules=[lockrules.LockOrderCycle])
    assert "lock-order-cycle" in names(found)


def test_nonreentrant_reacquire_is_self_deadlock(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/serve/locky.py": LOCK_HEADER + """
    class S:
        def __init__(self):
            self._lock = threading.Lock()

        def outer(self):
            with self._lock:
                self.inner()

        def inner(self):
            with self._lock:
                pass
    """}, rules=[lockrules.LockOrderCycle])
    assert "lock-order-cycle" in names(found)


def test_rlock_reentry_is_fine(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/serve/locky.py": LOCK_HEADER + """
    class S:
        def __init__(self):
            self._lock = threading.RLock()

        def outer(self):
            with self._lock:
                self.inner()

        def inner(self):
            with self._lock:
                pass
    """}, rules=[lockrules.LockOrderCycle])
    assert found == []


def test_unlocked_shared_write(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/serve/shared.py": LOCK_HEADER + """
    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._worker)

        def _worker(self):
            with self._lock:
                self.count += 1

        def bump(self):
            self.count += 1
    """}, rules=[lockrules.UnlockedSharedWrite])
    assert names(found) == ["unlocked-shared-write"]
    assert "self.count" in found[0].message


def test_locked_write_is_clean(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/serve/shared.py": LOCK_HEADER + """
    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._worker)

        def _worker(self):
            with self._lock:
                self.count += 1

        def bump(self):
            with self._lock:
                self.count += 1
    """}, rules=[lockrules.UnlockedSharedWrite])
    assert found == []


def test_locked_suffix_call_without_lock(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/serve/sfx.py": LOCK_HEADER + """
    class S:
        def __init__(self):
            self._lock = threading.Lock()

        def _spawn_locked(self):
            pass

        def good(self):
            with self._lock:
                self._spawn_locked()

        def bad(self):
            self._spawn_locked()
    """}, rules=[lockrules.LockedSuffixCall])
    assert names(found) == ["locked-suffix-call"]


# -------------------------------------------------------- registry rules

REGISTRY = RegistryConfig(
    control_keys=frozenset({"device_scf", "ngk_pad_quantum"}),
    fault_sites=frozenset({"scf.density"}),
    span_keys=frozenset({"scf.iter"}),
)


def test_unknown_control_key(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": """
    def f(cfg):
        a = cfg.control.device_scf      # known
        b = cfg.control.device_scff     # typo
        c = getattr(cfg.control, "ngk_pad_quantum", 16)
        d = getattr(cfg.control, "bogus", None)
        return a, b, c, d
    """}, rules=[registryrules.UnknownControlKey], registry=REGISTRY)
    assert names(found) == ["unknown-control-key"] * 2


def test_unknown_fault_site(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": """
    from sirius_tpu.utils import faults

    def f():
        faults.check("scf.density")   # known
        faults.check("scf.densety")   # typo
    """}, rules=[registryrules.UnknownFaultSite], registry=REGISTRY)
    assert names(found) == ["unknown-fault-site"]
    assert "scf.densety" in found[0].message


def test_uncosted_span(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": """
    def f(rec, dt):
        rec.record("scf.iter", dt)       # costed
        rec.record("scf.mystery", dt)    # neither costed nor exempt
        rec.record("not-a-span", dt)     # not span-shaped: ignored
    """}, rules=[registryrules.UncostedSpan], registry=REGISTRY)
    assert names(found) == ["uncosted-span"]
    assert "scf.mystery" in found[0].message


# --------------------------------------------------------------- baseline


def test_baseline_suppresses_known_flags_new(tmp_path):
    files = {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def f(x):
        return np.sum(x)
    """}
    _, found = lint(tmp_path, files, rules=[jaxrules.JitNumpyCall])
    assert len(found) == 1
    bp = str(tmp_path / "baseline.json")
    write_baseline(bp, found, old=None)
    base = load_baseline(bp)
    assert new_findings(found, base) == []

    # a second, distinct violation is NOT covered by the baseline
    # (same indentation as the original literal: lint() dedents the whole)
    files["sirius_tpu/mod.py"] += """
    @jax.jit
    def g(x):
        return np.prod(x)
    """
    _, found2 = lint(tmp_path, files, rules=[jaxrules.JitNumpyCall])
    fresh = new_findings(found2, base)
    assert len(found2) == 2 and len(fresh) == 1
    assert "np.prod" in fresh[0].text


def test_baseline_rewrite_preserves_justifications(tmp_path):
    files = {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def f(x):
        return np.sum(x)
    """}
    _, found = lint(tmp_path, files, rules=[jaxrules.JitNumpyCall])
    bp = str(tmp_path / "baseline.json")
    write_baseline(bp, found, old=None)
    base = load_baseline(bp)
    next(iter(base.values()))["justification"] = "deliberate: host fallback"
    json.dump({"version": 1, "findings": list(base.values())},
              open(bp, "w"))
    write_baseline(bp, found, old=load_baseline(bp))
    kept = load_baseline(bp)
    assert next(iter(kept.values()))["justification"] == (
        "deliberate: host fallback")


# -------------------------------------------------------------------- CLI


def test_cli_exit_codes(tmp_path):
    (tmp_path / "sirius_tpu").mkdir()
    (tmp_path / "sirius_tpu" / "mod.py").write_text(textwrap.dedent(
        JIT_HEADER + """
    @jax.jit
    def f(x):
        return np.sum(x)
    """))
    env = dict(os.environ, PYTHONPATH=REPO)

    def cli(*argv):
        return subprocess.run(
            [sys.executable, "-m", "sirius_tpu.analysis.cli",
             "--root", str(tmp_path), *argv],
            capture_output=True, text=True, env=env, cwd=str(tmp_path))

    r = cli()
    assert r.returncode == 1, r.stdout + r.stderr
    r = cli("--write-baseline", "b.json")
    assert r.returncode == 0
    r = cli("--baseline", "b.json", "--report", "rep.json")
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.load(open(tmp_path / "rep.json"))
    assert rep["new_findings"] == [] and rep["baselined"] == 1
    r = cli("--rules", "no-such-rule")
    assert r.returncode == 2


# -------------------------------------------------------------- live tree


@pytest.fixture(scope="module")
def live_engine():
    t0 = time.perf_counter()
    eng = LintEngine(REPO, paths=collect_files(REPO, DEFAULT_SCAN))
    eng.findings = eng.run()
    eng.wall_seconds = time.perf_counter() - t0
    return eng


@pytest.fixture(scope="module")
def live_run(live_engine):
    return live_engine.findings


def test_live_tree_clean_modulo_baseline(live_run):
    """The acceptance gate: the repo lints clean except for the
    checked-in, justified baseline."""
    base = load_baseline(os.path.join(REPO, "LINT_BASELINE.json"))
    fresh = new_findings(live_run, base)
    assert fresh == [], "new lint findings:\n" + "\n".join(map(str, fresh))


def test_live_tree_baseline_is_justified():
    base = load_baseline(os.path.join(REPO, "LINT_BASELINE.json"))
    for entry in base.values():
        assert entry.get("justification", "").strip(), (
            f"baseline entry {entry['fingerprint']} "
            f"({entry['rule']} in {entry['path']}) lacks a justification")


def test_live_tree_has_no_lock_cycles(live_run):
    """Zero lock-order cycles in serve/ — not even baselined ones."""
    assert [f for f in live_run if f.rule == "lock-order-cycle"] == []


def test_live_tree_fault_sites_consistent(live_run):
    """KNOWN_SITES covers every site the tree arms/checks."""
    assert [f for f in live_run if f.rule == "unknown-fault-site"] == []


# ----------------------------------------------- recompile-hazard rules


def test_recompile_jit_in_loop(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    def hot(xs):
        for x in xs:
            f = jax.jit(lambda v: v * 2)  # rebuilt every iteration
            f(x)

    def cached(cache, sig, fn, xs):
        for x in xs:
            g = cache.get(sig, lambda: jax.jit(fn))  # miss-only builder
            g(x)
    """}, rules=[compilerules.RecompileJitInLoop])
    assert names(found) == ["recompile-jit-in-loop"]
    assert "hot" in found[0].message


def test_recompile_unstable_static(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    def drive(xs):
        step = jax.jit(lambda x, n: x * n, static_argnums=(1,))
        for i, x in enumerate(xs):
            step(x, i)   # loop index at a static position
            step(x, 16)  # literal: compiles once, fine
    """}, rules=[compilerules.RecompileUnstableStatic])
    assert names(found) == ["recompile-unstable-static"]
    assert "loop variable `i`" in found[0].message


def test_cache_key_trace_constant(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/pipe.py": JIT_HEADER + """
    class Pipeline:
        def __init__(self, cache, nb, dtype):
            self.nb = nb
            self.dtype = dtype
            self.scale = 2.0
            self.run = cache.get(self._trace_signature(),
                                 lambda: jax.jit(self._impl))

        def _trace_signature(self):
            return ("pipeline", self.nb, self.dtype)

        def _impl(self, x):
            return x.astype(self.dtype) * self.nb * self.scale
    """}, rules=[compilerules.CacheKeyTraceConstant])
    assert names(found) == ["cache-key-trace-constant"]
    assert "self.scale" in found[0].message
    assert "_trace_signature" in found[0].message


def test_cache_key_trace_constant_complete_signature_ok(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/pipe.py": JIT_HEADER + """
    class Pipeline:
        def __init__(self, cache, nb):
            self.nb = nb
            self.run = cache.get(self._trace_signature(),
                                 lambda: jax.jit(self._impl))

        def _trace_signature(self):
            return ("pipeline", self.nb)

        def _impl(self, x):
            return x * self.nb
    """}, rules=[compilerules.CacheKeyTraceConstant])
    assert found == []


KEYED_PARTIAL = JIT_HEADER + """
    from functools import partial

    _programs = {{}}

    def _impl(rec, flavor, x):
        return x * rec.scale + flavor

    def program(rec, flavor):
        step = _programs.get({key})
        if step is None:
            bound = partial(_impl, rec, flavor)
            step = jax.jit(bound)
            _programs[{key}] = step
        return step
    """


@pytest.mark.parametrize("key, missing", [
    ("rec", ["flavor"]),
    ("(rec, flavor)", []),
])
def test_cache_key_trace_constant_partial_bound(tmp_path, key, missing):
    """The rule's other subject since the fused step reads no self: what a
    partial binds into a jitted impl has to be in the key of the table the
    wrapper is kept in (dft/fused.step_program keys its one record)."""
    eng, found = lint(
        tmp_path, {"sirius_tpu/tab.py": KEYED_PARTIAL.format(key=key)},
        rules=[compilerules.CacheKeyTraceConstant])
    assert names(found) == ["cache-key-trace-constant"] * len(missing)
    for f, m in zip(found, missing):
        assert f"`{m}` bound into jitted `_impl`" in f.message
    # the impl behind the local partial is a jit seed all the same
    assert ("sirius_tpu.tab", "_impl") in eng.project.jit_reachable()


def test_step_program_is_seen_through(tmp_path):
    """The tree's own table of fused steps: the analysis finds _step_impl
    behind the named partial, takes FusedScf._step for the jit binding it
    is (so step() returns device values and its donated carry is watched),
    and the table's key holds everything the partial binds."""
    from sirius_tpu.analysis.dataflow import DeviceModel

    path = os.path.join(REPO, "sirius_tpu", "dft", "fused.py")
    eng = LintEngine(REPO, [path], rules=[compilerules.CacheKeyTraceConstant])
    assert eng.run() == []
    project = eng.project
    assert ("sirius_tpu.dft.fused", "_step_impl") in project.jit_reachable()
    (place, kwargs), = [
        v for k, v in project.jit_factories().items()
        if k == ("sirius_tpu.dft.fused", "step_program")]
    assert place == 0 and "donate_argnums" in kwargs
    model = DeviceModel.of(project)
    assert model.jit_attrs[("sirius_tpu.dft.fused", "FusedScf")] == {"_step"}
    step = project.modules["sirius_tpu.dft.fused"].functions["FusedScf.step"]
    assert model.return_origins[step.key] == frozenset({"dev"})


# ------------------------------------------------- transfer-budget rules


def test_transfer_budget_exceeded(tmp_path):
    manifest = json.dumps({"version": 1, "regions": [
        {"path": "sirius_tpu/mod.py", "function": "drive",
         "kind": "loops", "budget": 1}]})
    _, found = lint(tmp_path, {
        "TRANSFER_BUDGET.json": manifest,
        "sirius_tpu/mod.py": JIT_HEADER + """
    def drive(xs):
        tot = 0.0
        for x in xs:
            y = jnp.dot(x, x)
            a = np.asarray(y)   # readback 1: within budget
            tot += float(y)     # readback 2: over budget
        return a, tot
    """}, rules=[transferrules.TransferBudget])
    assert names(found) == ["transfer-budget"]
    assert "budget of 1" in found[0].message
    assert "float()" in found[0].message


def test_transfer_budget_allowed_and_stale(tmp_path):
    manifest = json.dumps({"version": 1, "regions": [
        {"path": "sirius_tpu/mod.py", "function": "drive",
         "kind": "loops", "budget": 0,
         "allowed": ["np.asarray", "never-matches"]},
        {"path": "sirius_tpu/mod.py", "function": "gone",
         "kind": "body", "budget": 0}]})
    _, found = lint(tmp_path, {
        "TRANSFER_BUDGET.json": manifest,
        "sirius_tpu/mod.py": JIT_HEADER + """
    def drive(xs):
        for x in xs:
            y = jnp.dot(x, x)
            a = np.asarray(y)  # exempted by the allowed pattern
        return a
    """})
    assert names(found) == ["transfer-stale-allowance",
                            "transfer-stale-region"]
    msgs = " | ".join(f.message for f in found)
    assert "never-matches" in msgs and "gone" in msgs


def test_transfer_if_region_excludes_else_branch(tmp_path):
    manifest = json.dumps({"version": 1, "regions": [
        {"path": "sirius_tpu/mod.py", "function": "drive",
         "kind": "loop-if:fast", "budget": 0}]})
    _, found = lint(tmp_path, {
        "TRANSFER_BUDGET.json": manifest,
        "sirius_tpu/mod.py": JIT_HEADER + """
    def drive(xs, fast):
        for x in xs:
            y = jnp.dot(x, x)
            if fast:
                z = y + 1
            else:
                z = np.asarray(y)  # host fallback: not the guard's debt
        return z
    """}, rules=[transferrules.TransferBudget])
    assert found == []


def test_transfer_param_crossing_interprocedural(tmp_path):
    """A helper that moves its parameter to host taints its call sites:
    the crossing lands at the caller's line, where the device value is."""
    manifest = json.dumps({"version": 1, "regions": [
        {"path": "sirius_tpu/mod.py", "function": "drive",
         "kind": "loops", "budget": 0}]})
    _, found = lint(tmp_path, {
        "TRANSFER_BUDGET.json": manifest,
        "sirius_tpu/mod.py": JIT_HEADER + """
    def to_host(v):
        return np.asarray(v)

    def drive(xs):
        for x in xs:
            y = jnp.dot(x, x)
            h = to_host(y)  # the transfer happens here, one hop down
        return h
    """}, rules=[transferrules.TransferBudget])
    assert names(found) == ["transfer-budget"]
    assert "to_host" in found[0].message


def test_live_fused_one_readback_contract(live_engine):
    """The static proof of the fused-SCF transfer contract: exactly one
    scalar readback per fused iteration, an allowed supervised snapshot,
    a transfer-free profile span, and a sync-free jitted step."""
    rows = transferrules.budget_report(live_engine.project)
    assert rows, "TRANSFER_BUDGET.json missing or empty"
    for r in rows:
        assert not r["stale"], f"stale manifest region: {r}"
        assert r["count"] <= r["budget"], f"budget exceeded: {r}"
    fused_iter = next(r for r in rows
                      if r["kind"] == "loop-if:fused is not None")
    assert fused_iter["count"] == 1
    assert fused_iter["crossings"][0]["kind"] == "asarray"
    assert fused_iter["allowed_hits"] == {"fused.fetch_state": 1}
    span = next(r for r in rows if r["kind"] == "with:scf::fused_step")
    assert span["count"] == 0 and span["budget"] == 0
    step = next(r for r in rows if r["function"] == "FusedScf.step")
    assert step["count"] == 0 and step["budget"] == 0


# ------------------------------------------- sharding-consistency rules

SHARD_HEADER = """\
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
"""


def test_shard_unknown_axis(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": SHARD_HEADER + """
    def make(devs):
        return Mesh(np.array(devs), ("k", "b"))

    def good():
        return P("k", None)

    def bad():
        return P("q")  # no mesh anywhere declares "q"
    """}, rules=[shardrules.ShardUnknownAxis])
    assert names(found) == ["shard-unknown-axis"]
    assert '"q"' in found[0].message


def test_shard_ctor_alias_resolution(tmp_path):
    """`Mesh as _Mesh` / `PartitionSpec as _P` resolve through the
    import map — the scf.py FFT-mesh idiom must not false-positive."""
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": """
    import numpy as np
    from jax.sharding import Mesh as _Mesh, PartitionSpec as _P

    def make(devs):
        return _Mesh(np.array(devs), ("g",))

    def spec():
        return _P("g")
    """}, rules=[shardrules.ShardUnknownAxis])
    assert found == []


def test_shard_axis_mismatch(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": SHARD_HEADER + """
    def put(devs):
        gmesh = Mesh(np.array(devs), ("g",))
        kmesh = Mesh(np.array(devs), ("k",))
        ok = NamedSharding(gmesh, P("g"))
        bad = NamedSharding(gmesh, P("k"))  # "k" exists, not on gmesh
        return ok, bad, kmesh
    """}, rules=[shardrules.ShardAxisMismatch])
    assert names(found) == ["shard-axis-mismatch"]
    assert '"k"' in found[0].message


def test_shard_constraint_in_loop(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    from jax.lax import with_sharding_constraint

    @jax.jit
    def hot(xs, spec):
        out = xs
        for _ in range(3):
            out = with_sharding_constraint(out, spec)
        return out

    def host(xs, spec):
        for _ in range(3):
            xs = with_sharding_constraint(xs, spec)  # not jit-reachable
        return xs
    """}, rules=[shardrules.ShardConstraintInLoop])
    assert names(found) == ["shard-constraint-in-loop"]
    assert "hot" in found[0].message


def test_live_sharding_inventory_schema(live_engine):
    """Schema-pinning for `sirius-lint --report sharding`: the five
    driver rows, the row shape, and the load-bearing live facts."""
    inv = shardrules.sharding_inventory(live_engine.project)
    assert inv["version"] == 1
    assert inv["declared_axes"] == ["b", "g", "k"]
    assert sorted(inv["drivers"]) == [
        "campaigns", "md", "relax", "scf", "serve"]
    row = inv["drivers"]["scf"]
    assert sorted(row) == [
        "axes_used", "collectives", "donate_argnums", "indexed",
        "jit_sites", "meshes", "named_shardings", "partition_specs",
        "path", "sharding_constraints"]
    assert row["indexed"], "scf driver must be indexed"
    assert any(m["axes"] == ["g"] for m in row["meshes"]), (
        "scf's distributed-FFT mesh (axis g) missing from the inventory")
    # the delegation diff signal: serve/md/relax construct no meshes of
    # their own — all sharding flows through scf/parallel helpers
    for name in ("serve", "md", "relax"):
        assert inv["drivers"][name]["meshes"] == [], name
    assert any(inv["parallel"].values()), "parallel/ rows missing"


# ------------------------------------- event/metric registry cross-check

REGISTRY_V2 = RegistryConfig(
    event_kinds=frozenset({"scf_iteration"}),
    metric_names=frozenset({"scf_iterations_total"}),
)


def test_unknown_event_kind(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": """
    from sirius_tpu.obs import events

    def f(mode):
        events.emit("scf_iteration", it=1)
        events.emit("scf_iterration", it=2)  # typo
        events.emit("drain" if mode else "scf_iteration")  # one bad arm
    """}, rules=[registryrules.UnknownEventKind], registry=REGISTRY_V2)
    assert names(found) == ["unknown-event-kind"] * 2
    msgs = " | ".join(f.message for f in found)
    assert "scf_iterration" in msgs and "drain" in msgs


def test_unknown_metric_name(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": """
    from sirius_tpu.obs.metrics import REGISTRY, MetricsRegistry

    def f():
        REGISTRY.counter("scf_iterations_total").inc()
        REGISTRY.counter("scf_itertions_total").inc()  # typo
        private = MetricsRegistry()
        private.counter("throwaway_total").inc()  # private registry: exempt
    """}, rules=[registryrules.UnknownMetricName], registry=REGISTRY_V2)
    assert names(found) == ["unknown-metric-name"]
    assert "scf_itertions_total" in found[0].message


def test_live_tree_event_and_metric_registries(live_run):
    """KNOWN_EVENT_KINDS / KNOWN_METRIC_NAMES cover the live tree."""
    assert [f for f in live_run
            if f.rule in ("unknown-event-kind",
                          "unknown-metric-name")] == []


# --------------------------------------- fingerprints, suppressions, SARIF


def test_fingerprint_rename_stable(tmp_path):
    """Fingerprints key on (rule, normalized text, enclosing qualname):
    moving the file and shifting its lines must not churn the baseline,
    but a different enclosing function is a different finding."""
    body = """
    @jax.jit
    def f(x):
        return np.sum(x)
    """
    a, b, c = tmp_path / "a", tmp_path / "b", tmp_path / "c"
    _, fa = lint(a, {"sirius_tpu/alpha.py": JIT_HEADER + body},
                 rules=[jaxrules.JitNumpyCall])
    _, fb = lint(b, {"sirius_tpu/renamed/beta.py":
                     JIT_HEADER + "\n\n\n" + body},
                 rules=[jaxrules.JitNumpyCall])
    assert fa[0].fingerprint == fb[0].fingerprint
    assert fa[0].line != fb[0].line  # the shift the fingerprint ignores
    _, fc = lint(c, {"sirius_tpu/alpha.py": JIT_HEADER + """
    @jax.jit
    def g(x):
        return np.sum(x)
    """}, rules=[jaxrules.JitNumpyCall])
    assert fc[0].fingerprint != fa[0].fingerprint


def test_stale_suppression_audit(tmp_path):
    eng, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def f(x):
        return np.sum(x)  # sirius-lint: disable=jit-numpy-call

    def g(x):
        return x  # sirius-lint: disable=jit-numpy-call

    def h(x):
        return x  # sirius-lint: disable=no-such-rule
    """}, rules=[jaxrules.JitNumpyCall])
    assert found == []  # the one real violation is suppressed
    stale = eng.stale_suppressions()
    assert [(s["rule"], s["reason"]) for s in stale] == [
        ("jit-numpy-call", "never fired"),
        ("no-such-rule", "unknown rule")]


def test_sarif_output(tmp_path):
    _, found = lint(tmp_path, {"sirius_tpu/mod.py": JIT_HEADER + """
    @jax.jit
    def f(x):
        return np.sum(x)
    """}, rules=[jaxrules.JitNumpyCall])
    doc = to_sarif(found, [jaxrules.JitNumpyCall], new=[],
                   root=str(tmp_path))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert [r["id"] for r in run["tool"]["driver"]["rules"]] == [
        "jit-numpy-call"]
    res = run["results"][0]
    assert res["ruleId"] == "jit-numpy-call"
    assert res["baselineState"] == "unchanged"  # new=[]: all baselined
    assert res["partialFingerprints"]["siriusLint/v2"] == (
        found[0].fingerprint)
    loc = res["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] == found[0].line
    assert loc["artifactLocation"]["uri"] == "sirius_tpu/mod.py"


def test_cli_sarif_suppressions_and_sharding(tmp_path, capsys):
    # in-process cli.main() — subprocess spawns would re-pay the jax
    # import for every flag combination
    from sirius_tpu.analysis import cli as lint_cli

    (tmp_path / "sirius_tpu").mkdir()
    (tmp_path / "sirius_tpu" / "mod.py").write_text(textwrap.dedent(
        JIT_HEADER + """
    def f(x):
        return x  # sirius-lint: disable=jit-numpy-call
    """))

    def cli(*argv):
        rc = lint_cli.main(["--root", str(tmp_path), *argv])
        out = capsys.readouterr()
        return rc, out.out, out.err

    # stale suppression: advisory by default, fatal under --strict;
    # SARIF rides along in the same invocation
    sarif_path = tmp_path / "out.sarif"
    rc, out, err = cli("--check-suppressions", "--sarif", str(sarif_path))
    assert rc == 0 and "stale suppression" in out
    doc = json.load(open(sarif_path))
    assert doc["version"] == "2.1.0" and doc["runs"][0]["results"] == []
    rc, out, err = cli("--check-suppressions", "--strict")
    assert rc == 1, out + err
    # the audit needs the full catalog
    rc, out, err = cli("--check-suppressions", "--rules", "jit-numpy-call")
    assert rc == 2
    # sharding inventory on stdout
    rc, out, err = cli("--report", "sharding")
    assert rc == 0, out + err
    inv = json.loads(out)
    assert inv["version"] == 1 and "drivers" in inv


# ------------------------------------------------- self-scan and budget


def test_default_scan_includes_tests(live_engine):
    """Satellite: the lint indexes its own test tree, so cross-package
    call resolution covers tests/ fixtures too."""
    assert "tests" in DEFAULT_SCAN
    assert any(f.relpath == "tests/test_lint.py"
               for f in live_engine.project.files)


def test_live_lint_runtime_budget(live_engine):
    """The whole-tree lint (index + all six families) must stay under
    the 60 s CI budget."""
    assert live_engine.wall_seconds < 60.0
