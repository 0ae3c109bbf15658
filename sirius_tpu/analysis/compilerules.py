"""Recompile-hazard rules: trace signatures that vary per call.

A retrace storm is the quietest way to lose a TPU: nothing is wrong,
the answers are right, and every step pays a fresh trace+lower+compile
(SERVE_BENCH.json's 220 backend compiles at a 0.90 cache hit rate is
what that smells like). These rules flag the static patterns that
*must* retrace:

- ``recompile-jit-in-loop`` — a ``jax.jit``/``pmap`` wrapper built
  inside a loop body discards jit's compile cache every iteration.
  Builders that only run on a cache miss (the
  ``exec_cache.get(sig, lambda: jax.jit(...))`` idiom from
  serve/cache.py) are exempt: the lambda body is not loop-executed.
- ``recompile-unstable-static`` — a value that provably varies per
  call (an enclosing loop variable, ``time.*``/``random.*``/``uuid.*``
  results) passed at a ``static_argnums``/``static_argnames`` position:
  every distinct value is a distinct executable.
- ``cache-key-trace-constant`` — the cross-check with serve/cache.py's
  executable keys: for a class that routes a jitted ``self.<impl>``
  through ``ExecutableCache`` (``self.X = cache.get(sig, lambda:
  jax.jit(self.<impl>))``) and declares its key via a
  ``_trace_signature()`` method, every ``self.<attr>`` the impl reads
  is baked into the traced program as a constant — so any read attr
  missing from the signature means two instances that differ only in
  that attr would *share an executable and silently compute with the
  wrong constant*. The analysis and the cache share one definition of
  "same executable": the signature tuple. The same rule reads the other
  way of baking a constant in, ``jax.jit(partial(impl, rec))`` kept in a
  table of programs (dft/fused.py ``step_program``: the fused step reads
  no ``self``, its constants are one frozen record bound by the partial):
  every value the partial binds must be in the key the wrapper is stored
  under, or two calls that differ in it would share a program.
"""

from __future__ import annotations

import ast

from sirius_tpu.analysis.core import (
    FunctionInfo,
    ProjectIndex,
    _JIT_WRAPPERS,
    _PARTIAL,
    call_name,
    dotted_name,
)
from sirius_tpu.analysis.dataflow import DeviceModel
from sirius_tpu.analysis.jaxrules import (
    _int_elements,
    _local_jit_bindings,
    _str_elements,
)

_VARYING_CALL_PREFIXES = ("time.", "random.", "uuid.", "np.random.",
                          "numpy.random.", "secrets.", "os.urandom")


def _loops_containing(fn_node: ast.AST):
    """(loop_node, set of descendant nodes excluding lambda/def bodies)."""
    out = []
    for node in ast.walk(fn_node):
        if not isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
            continue
        inside: set[int] = set()
        stack = list(node.body) + list(node.orelse)
        while stack:
            n = stack.pop()
            inside.add(id(n))
            if isinstance(n, (ast.Lambda, ast.FunctionDef,
                              ast.AsyncFunctionDef)):
                continue  # deferred bodies don't execute per iteration
            stack.extend(ast.iter_child_nodes(n))
        out.append((node, inside))
    return out


def _loop_vars(fn_node: ast.AST) -> set[str]:
    """Names bound as loop targets anywhere in the function."""
    out: set[str] = set()
    for node in ast.walk(fn_node):
        if isinstance(node, (ast.For, ast.AsyncFor)):
            for n in ast.walk(node.target):
                if isinstance(n, ast.Name):
                    out.add(n.id)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            for gen in node.generators:
                for n in ast.walk(gen.target):
                    if isinstance(n, ast.Name):
                        out.add(n.id)
    return out


class RecompileJitInLoop:
    """``jax.jit(...)`` evaluated inside a loop body: the fresh wrapper
    has an empty compile cache, so every iteration retraces and
    recompiles. Hoist the jit out of the loop (or route it through an
    ExecutableCache builder lambda, which this rule exempts)."""

    name = "recompile-jit-in-loop"

    def run(self, project: ProjectIndex):
        for fi in project.iter_functions():
            loops = _loops_containing(fi.node)
            if not loops:
                continue
            for node in ast.walk(fi.node):
                if not (isinstance(node, ast.Call)
                        and call_name(node) in _JIT_WRAPPERS):
                    continue
                if any(id(node) in inside for _, inside in loops):
                    yield project.finding(
                        self.name, fi, node,
                        f"`{call_name(node)}(...)` built inside a loop "
                        f"in `{fi.qualname}` retraces every iteration; "
                        f"hoist it (or build it in a cache-miss lambda)")


class RecompileUnstableStatic:
    """A per-call-varying value at a static position: jit hashes static
    args into the executable key, so a loop index or timestamp there
    means one fresh compile per call — a retrace storm by construction."""

    name = "recompile-unstable-static"

    def _varying_reason(self, expr: ast.AST,
                        loop_vars: set[str]) -> str | None:
        for n in ast.walk(expr):
            if (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
                    and n.id in loop_vars):
                return f"loop variable `{n.id}`"
            if isinstance(n, ast.Call):
                d = call_name(n)
                if d and (d.startswith(_VARYING_CALL_PREFIXES)
                          or d in ("id",)):
                    return f"per-call-varying `{d}()`"
        return None

    def _static_positions(self, project: ProjectIndex,
                          fi: FunctionInfo):
        """callable-name -> (static positions, static names) visible
        from ``fi``: local jit bindings plus resolved jit seeds."""
        local: dict[str, tuple[list[int], list[str]]] = {}
        for tgt, kwargs, _ in _local_jit_bindings(fi.node):
            p = _int_elements(kwargs.get("static_argnums",
                                         ast.Constant(value=None)))
            n = _str_elements(kwargs.get("static_argnames",
                                         ast.Constant(value=None)))
            if p or n:
                local[tgt] = (p, n)
        return local

    def run(self, project: ProjectIndex):
        project.jit_reachable()  # populate jit_kwargs on seeds
        seeded: dict[tuple, tuple[list[int], list[str]]] = {}
        for fi in project.iter_functions():
            if fi.jit_kwargs:
                p = _int_elements(fi.jit_kwargs.get(
                    "static_argnums", ast.Constant(value=None)))
                n = _str_elements(fi.jit_kwargs.get(
                    "static_argnames", ast.Constant(value=None)))
                if p or n:
                    seeded[fi.key] = (p, n)
        for fi in project.iter_functions():
            loop_vars = _loop_vars(fi.node)
            local = self._static_positions(project, fi)
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = call_name(node)
                if not d:
                    continue
                info = local.get(d)
                if info is None:
                    for tgt in project._resolve_call(fi.module, fi.cls, d):
                        info = seeded.get(tgt.key)
                        if info:
                            break
                if not info:
                    continue
                pos, names = info
                for i, a in enumerate(node.args):
                    if i not in pos:
                        continue
                    why = self._varying_reason(a, loop_vars)
                    if why:
                        yield project.finding(
                            self.name, fi, a,
                            f"{why} at static position {i} of `{d}` in "
                            f"`{fi.qualname}`: one recompile per call")
                for k in node.keywords:
                    if k.arg not in names:
                        continue
                    why = self._varying_reason(k.value, loop_vars)
                    if why:
                        yield project.finding(
                            self.name, fi, k.value,
                            f"{why} for static arg `{k.arg}` of `{d}` in "
                            f"`{fi.qualname}`: one recompile per call")


class CacheKeyTraceConstant:
    """A ``self.<attr>`` read by a cache-shared jitted impl but missing
    from the class's ``_trace_signature()``: the attr is baked into the
    executable as a constant, yet two instances differing only in it
    produce equal cache keys — the second silently reuses the first's
    program with the wrong constant."""

    name = "cache-key-trace-constant"

    def _self_attr_reads(self, mi, cls: str, method: str,
                         seen: set[str]) -> set[str]:
        """self.<attr> Loads in ``cls.method``, transitively through
        same-class method calls; attribute names used as call targets
        (``self.m(...)``) recurse instead of counting as reads."""
        out: set[str] = set()
        fi = mi.functions.get(f"{cls}.{method}")
        if fi is None or method in seen:
            return out
        seen.add(method)
        call_funcs = {id(n.func) for n in ast.walk(fi.node)
                      if isinstance(n, ast.Call)}
        for node in ast.walk(fi.node):
            if not (isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, ast.Load)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"):
                continue
            if id(node) in call_funcs:
                if f"{cls}.{node.attr}" in mi.functions:
                    out |= self._self_attr_reads(mi, cls, node.attr, seen)
                continue
            out.add(node.attr)
        return out

    def _bound_partials(self, fn_node: ast.AST):
        """jit wrappers over a partial in one function: yields (the
        wrapper's local name or None, the jit call, the partial call), the
        partial written in place or bound to a local first."""
        partials = {}  # local name -> partial call
        for node in ast.walk(fn_node):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)
                    and call_name(node.value) in _PARTIAL):
                partials[node.targets[0].id] = node.value
        named = {id(assign.value): tgt  # jit call -> the local it is bound to
                 for tgt, _, assign in _local_jit_bindings(fn_node)}
        for node in ast.walk(fn_node):
            if not (isinstance(node, ast.Call)
                    and call_name(node) in _JIT_WRAPPERS and node.args):
                continue
            a = node.args[0]
            part = (a if isinstance(a, ast.Call)
                    and call_name(a) in _PARTIAL
                    else partials.get(getattr(a, "id", None)))
            if part is not None:
                yield named.get(id(node)), node, part

    def _keys_of(self, fn_node: ast.AST, name: str | None,
                 jit_call: ast.Call):
        """Key expressions the wrapper is stored under in this function
        (``table[key] = wrapper``)."""
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Assign) and (
                    node.value is jit_call or (
                        isinstance(node.value, ast.Name)
                        and node.value.id == name)):
                for t in node.targets:
                    if isinstance(t, ast.Subscript):
                        yield t.slice

    def _unkeyed_partial_args(self, project: ProjectIndex):
        for fi in project.iter_functions():
            for name, jit_call, part in self._bound_partials(fi.node):
                bound = [(dotted_name(a), a) for a in (
                    *part.args[1:], *(k.value for k in part.keywords))]
                for key in self._keys_of(fi.node, name, jit_call):
                    in_key = {dotted_name(n) for n in ast.walk(key)
                              if isinstance(n, (ast.Name, ast.Attribute))}
                    for d, a in bound:
                        if d and d not in in_key:
                            yield project.finding(
                                self.name, fi, a,
                                f"`{d}` bound into jitted "
                                f"`{dotted_name(part.args[0])}` by partial "
                                f"in `{fi.qualname}` but absent from the "
                                f"key the wrapper is stored under: equal "
                                f"keys would reuse an executable with the "
                                f"wrong baked-in value")

    def run(self, project: ProjectIndex):
        yield from self._unkeyed_partial_args(project)
        model = DeviceModel.of(project)
        for (mod, cls, attr), impl in sorted(model.jit_attr_impl.items()):
            mi = project.modules.get(mod)
            if mi is None:
                continue
            sig_fi = mi.functions.get(f"{cls}._trace_signature")
            impl_fi = mi.functions.get(f"{cls}.{impl}")
            if sig_fi is None or impl_fi is None:
                continue
            sig_attrs = self._self_attr_reads(
                mi, cls, "_trace_signature", set())
            reads = self._self_attr_reads(mi, cls, impl, set())
            jit_attrs = model.jit_attrs.get((mod, cls), set())
            for a in sorted(reads - sig_attrs - jit_attrs):
                node = None
                for n in ast.walk(impl_fi.node):
                    if (isinstance(n, ast.Attribute) and n.attr == a
                            and isinstance(n.value, ast.Name)
                            and n.value.id == "self"):
                        node = n
                        break
                yield project.finding(
                    self.name, impl_fi, node,
                    f"`self.{a}` read by jitted `{cls}.{impl}` (bound to "
                    f"`self.{attr}`) but absent from "
                    f"`{cls}._trace_signature()`: equal cache keys would "
                    f"reuse an executable with the wrong baked-in value")


RULES = (RecompileJitInLoop, RecompileUnstableStatic,
         CacheKeyTraceConstant)
