"""JAX rules: invariants of jit-reachable (traced) code.

Every rule here is scoped to the jit-reachability closure computed by
:meth:`ProjectIndex.jit_reachable` — host-path code is free to use
numpy, Python control flow, and ``float()`` readbacks, so flagging it
would drown the signal. Taint is intra-function and deliberately
shallow: a value is "tracer-ish" iff it flows (through assignments and
expressions) from a ``jnp.*`` / ``jax.lax.*`` call, which keeps
Python-bool conditionals like ``if polarized:`` inside device code
clean while still catching ``if jnp.max(r) > tol:``.
"""

from __future__ import annotations

import ast

from sirius_tpu.analysis.core import (
    Finding,
    FunctionInfo,
    ProjectIndex,
    assigned_names,
    call_name,
    dotted_name,
)

_ARRAY_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "lax.")
_NUMPY_PREFIXES = ("np.", "numpy.", "scipy.", "sp.")
_DTYPELESS_CTORS = {"zeros", "ones", "empty", "full", "arange",
                    "linspace", "eye", "zeros_like_none"}


def _is_array_call(d: str) -> bool:
    return d.startswith(_ARRAY_PREFIXES)


def tainted_names(fn_node: ast.AST) -> set[str]:
    """Names that (transitively) hold results of jnp/lax calls."""
    tainted: set[str] = set()

    def expr_tainted(e: ast.AST) -> bool:
        for n in ast.walk(e):
            if isinstance(n, ast.Call):
                d = call_name(n)
                if d and _is_array_call(d):
                    return True
            elif (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
                  and n.id in tainted):
                return True
        return False

    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn_node):
            targets: list[ast.AST] = []
            value = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets, value = [node.target], node.value
            if value is None or not expr_tainted(value):
                continue
            for t in targets:
                for nm in assigned_names(t):
                    if nm not in tainted:
                        tainted.add(nm)
                        changed = True
    return tainted


def _expr_is_tainted(e: ast.AST, tainted: set[str]) -> bool:
    for n in ast.walk(e):
        if isinstance(n, ast.Call):
            d = call_name(n)
            if d and _is_array_call(d):
                return True
        elif (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
              and n.id in tainted):
            return True
    return False


def _jit_functions(project: ProjectIndex):
    reach = project.jit_reachable()
    for fi in project.iter_functions():
        if fi.key in reach:
            yield fi


class JitTracedControlFlow:
    """Python ``if``/``while`` branching on a traced array value —
    resolved at trace time, so it either crashes (ConcretizationError)
    or silently bakes in one branch and recompiles per shape."""

    name = "jit-traced-control-flow"

    def run(self, project: ProjectIndex):
        for fi in _jit_functions(project):
            tainted = tainted_names(fi.node)
            for node in ast.walk(fi.node):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                test = node.test
                if (isinstance(test, ast.Compare) and all(
                        isinstance(op, (ast.Is, ast.IsNot))
                        for op in test.ops)):
                    continue  # `x is None`: identity, static at trace time
                if _expr_is_tainted(test, tainted):
                    kw = "while" if isinstance(node, ast.While) else "if"
                    yield project.finding(
                        self.name, fi, node,
                        f"Python `{kw}` on a traced array value in "
                        f"jit-reachable `{fi.qualname}`; use jnp.where / "
                        f"lax.cond / lax.while_loop")


class JitNumpyCall:
    """``np.*``/``scipy.*`` calls inside jit-reachable code run on host
    at trace time — a silent device→host sync plus a constant baked
    into the executable."""

    name = "jit-numpy-call"

    def run(self, project: ProjectIndex):
        for fi in _jit_functions(project):
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = call_name(node)
                if d and d.startswith(_NUMPY_PREFIXES):
                    yield project.finding(
                        self.name, fi, node,
                        f"host numpy call `{d}` in jit-reachable "
                        f"`{fi.qualname}`; use the jnp equivalent")


class JitHostSync:
    """Implicit device→host syncs (``float()``/``.item()``/
    ``np.asarray()`` on traced values) — each one stalls the dispatch
    pipeline. Sanctioned readback sites carry an inline suppression."""

    name = "jit-host-sync"
    _CASTS = {"float", "int", "bool", "complex"}
    _SYNC_METHODS = {"item", "tolist", "block_until_ready"}

    def _in_scope(self, project, fi: FunctionInfo, reach) -> bool:
        if fi.key in reach:
            return True
        tail = fi.qualname.rsplit(".", 1)[-1]
        return tail.endswith("_device") or tail.startswith("device_")

    def run(self, project: ProjectIndex):
        reach = project.jit_reachable()
        for fi in project.iter_functions():
            if not self._in_scope(project, fi, reach):
                continue
            tainted = tainted_names(fi.node)
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = call_name(node)
                if d in self._CASTS and node.args and _expr_is_tainted(
                        node.args[0], tainted):
                    yield project.finding(
                        self.name, fi, node,
                        f"`{d}()` on a traced value in `{fi.qualname}` "
                        f"forces a device->host sync")
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in self._SYNC_METHODS):
                    yield project.finding(
                        self.name, fi, node,
                        f"`.{node.func.attr}()` in jit-scope "
                        f"`{fi.qualname}` forces a device->host sync")
                elif (d in ("np.asarray", "np.array", "numpy.asarray",
                            "numpy.array") and node.args
                      and _expr_is_tainted(node.args[0], tainted)):
                    yield project.finding(
                        self.name, fi, node,
                        f"`{d}()` on a traced value in `{fi.qualname}` "
                        f"copies the buffer to host")


def _int_elements(node: ast.AST) -> list[int]:
    """Literal ints from an int or tuple-of-ints AST node."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    out = []
    if isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
    return out


def _str_elements(node: ast.AST) -> list[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    out = []
    if isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
    return out


def _local_jit_bindings(fn_node: ast.AST):
    """``name = jax.jit(f, ...)`` / ``self.attr = jax.jit(f, ...)``
    bindings inside one function: yields (binding, kwargs, assign)."""
    from sirius_tpu.analysis.core import _JIT_WRAPPERS

    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        v = node.value
        if not (isinstance(v, ast.Call) and call_name(v) in _JIT_WRAPPERS):
            continue
        tgt = dotted_name(node.targets[0])
        if tgt:
            yield tgt, {k.arg: k.value for k in v.keywords if k.arg}, node


class JitDonatedReuse:
    """Reading an argument after passing it at a ``donate_argnums``
    position — the buffer has been handed to XLA and may alias the
    output; reuse is undefined behaviour."""

    name = "jit-donated-reuse"

    def _donated_map(self, project):
        """(module, owner-name) -> donated positions, from both local
        ``g = jax.jit(f, donate_argnums=...)`` bindings and
        ``self.X = jax.jit(...)`` class-level bindings, the jit call in
        place or in a function that hands the wrapper out."""
        out: dict[tuple[str, str, str], list[int]] = {}
        for fi in project.iter_functions():
            for tgt, kwargs, _ in (*_local_jit_bindings(fi.node),
                                   *project.factory_jit_bindings(fi)):
                if "donate_argnums" not in kwargs:
                    continue
                pos = _int_elements(kwargs["donate_argnums"])
                if not pos:
                    continue
                if tgt.startswith("self.") and fi.cls:
                    out[(fi.module.name, fi.cls, tgt)] = pos
                else:
                    # local binding: scoped to this function only
                    out[(fi.module.name, fi.qualname, tgt)] = pos
        return out

    def run(self, project: ProjectIndex):
        donated = self._donated_map(project)
        if not donated:
            return
        for fi in project.iter_functions():
            scopes = [(fi.module.name, fi.qualname),
                      (fi.module.name, fi.cls or "")]
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = call_name(node)
                if not d:
                    continue
                pos = None
                for sm, so in scopes:
                    pos = donated.get((sm, so, d))
                    if pos:
                        break
                if not pos:
                    continue
                donated_args = {
                    a.id for i, a in enumerate(node.args)
                    if i in pos and isinstance(a, ast.Name)}
                if not donated_args:
                    continue
                for later in ast.walk(fi.node):
                    if (isinstance(later, ast.Name)
                            and isinstance(later.ctx, ast.Load)
                            and later.id in donated_args
                            and later.lineno > node.lineno):
                        yield project.finding(
                            self.name, fi, later,
                            f"`{later.id}` read after being donated to "
                            f"`{d}` (line {node.lineno}); the buffer may "
                            f"alias the output")
                        donated_args.discard(later.id)
                        if not donated_args:
                            break


class JitDtypeLiteral:
    """Array constructors without an explicit ``dtype=`` in
    jit-reachable code default to the ambient x64 setting — a silent
    precision fork once the mixed-precision ladder lands."""

    name = "jit-dtype-literal"

    def run(self, project: ProjectIndex):
        for fi in _jit_functions(project):
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = call_name(node)
                if not d or not d.startswith(("jnp.", "jax.numpy.")):
                    continue
                ctor = d.rsplit(".", 1)[-1]
                if ctor not in {"zeros", "ones", "empty", "full",
                                "arange", "linspace", "eye"}:
                    continue
                if any(k.arg == "dtype" for k in node.keywords):
                    continue
                # positional dtype: zeros(shape, dtype) / full(sh, v, dtype)
                min_args = {"zeros": 1, "ones": 1, "empty": 1, "full": 2}
                if ctor in min_args and len(node.args) > min_args[ctor]:
                    continue
                yield project.finding(
                    self.name, fi, node,
                    f"`{d}(...)` without dtype= in jit-reachable "
                    f"`{fi.qualname}`; pin the precision explicitly")


class JitPythonFloatAccum:
    """A Python scalar initialised from a literal and then accumulated
    with traced values — every trace re-materialises it as a fresh
    constant, defeating donation and promoting dtype weakly."""

    name = "jit-python-float-accum"

    def run(self, project: ProjectIndex):
        for fi in _jit_functions(project):
            tainted = tainted_names(fi.node)
            literal_inits: set[str] = set()
            for node in ast.walk(fi.node):
                if (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Constant)
                        and isinstance(node.value.value, (int, float))):
                    literal_inits.update(
                        nm for t in node.targets for nm in
                        assigned_names(t))
            if not literal_inits:
                continue
            for node in ast.walk(fi.node):
                if (isinstance(node, ast.AugAssign)
                        and isinstance(node.target, ast.Name)
                        and node.target.id in literal_inits
                        and _expr_is_tainted(node.value, tainted)):
                    yield project.finding(
                        self.name, fi, node,
                        f"Python scalar `{node.target.id}` accumulated "
                        f"with traced values in `{fi.qualname}`; "
                        f"initialise it as a jnp array")


class JitNonHashableStatic:
    """A list/dict/set passed at a ``static_argnums`` position — jit
    hashes static args for the compile cache, so this raises (or worse,
    with custom __hash__, caches wrongly)."""

    name = "jit-nonhashable-static"
    _BAD = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
            ast.SetComp)

    def _static_info(self, fi: FunctionInfo):
        pos = _int_elements(fi.jit_kwargs.get("static_argnums",
                                              ast.Constant(value=None)))
        names = _str_elements(fi.jit_kwargs.get("static_argnames",
                                                ast.Constant(value=None)))
        return pos, names

    def run(self, project: ProjectIndex):
        project.jit_reachable()  # populates jit_kwargs on seeds
        static: dict[tuple[str, str], tuple[list[int], list[str]]] = {}
        for fi in project.iter_functions():
            if fi.jit_kwargs:
                p, n = self._static_info(fi)
                if p or n:
                    static[fi.key] = (p, n)
        # local bindings: g = jax.jit(f, static_argnums=(1,)) then g([..])
        for fi in project.iter_functions():
            local: dict[str, tuple[list[int], list[str]]] = {}
            for tgt, kwargs, _ in _local_jit_bindings(fi.node):
                p = _int_elements(kwargs.get("static_argnums",
                                             ast.Constant(value=None)))
                n = _str_elements(kwargs.get("static_argnames",
                                             ast.Constant(value=None)))
                if p or n:
                    local[tgt] = (p, n)
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = call_name(node)
                if not d:
                    continue
                info = local.get(d)
                if info is None:
                    for tgt in project._resolve_call(fi.module, fi.cls, d):
                        info = static.get(tgt.key)
                        if info:
                            break
                if not info:
                    continue
                pos, names = info
                for i, a in enumerate(node.args):
                    if i in pos and isinstance(a, self._BAD):
                        yield project.finding(
                            self.name, fi, a,
                            f"non-hashable literal at static position "
                            f"{i} of `{d}`; use a tuple")
                for k in node.keywords:
                    if k.arg in names and isinstance(k.value, self._BAD):
                        yield project.finding(
                            self.name, fi, k.value,
                            f"non-hashable literal for static arg "
                            f"`{k.arg}` of `{d}`; use a tuple")


RULES = (
    JitTracedControlFlow,
    JitNumpyCall,
    JitHostSync,
    JitDonatedReuse,
    JitDtypeLiteral,
    JitPythonFloatAccum,
    JitNonHashableStatic,
)
