"""Interprocedural jit-boundary dataflow shared by the v2 rule families.

Everything here is still pure ``ast`` — no imports of analysed code —
but unlike the per-function taint in jaxrules.py the model is
*summary-based and interprocedural*:

- **Origin sets.** An expression evaluates to a set of origin tokens:
  ``"dev"`` (flows from a device producer — ``jnp.*``/``lax.*`` calls,
  a jitted binding, an attribute spelled ``*_dev``) and/or ``"p<i>"``
  (flows from the function's i-th parameter). Empty set = host value.
- **Function summaries.** A fixpoint over the resolved project call
  graph computes, per function, its *return origins* (does it return a
  device value; which parameters flow through to the return) and its
  *crossed params* (which parameters it moves to host internally). Call
  sites substitute actual-argument origins into the summary, so
  ``fused_out = fused.step(carry)`` is device-tainted because
  ``FusedScf.step`` returns the output of a ``self._step`` jit binding
  three modules away.
- **Instance typing.** ``x = ClassName(...)`` (locals) and
  ``self.a = ClassName(...)`` (attrs) resolve through the import map so
  ``x.method(...)`` calls bind to ``ClassName.method`` cross-module.
- **Crossings.** A device→host crossing is recorded where a tainted
  value meets ``float()``/``int()``/``bool()``, ``.item()``/
  ``.tolist()``, ``np.asarray``/``np.array``, ``jax.device_get``,
  implicit bool coercion (``if``/``while``/``not``/``and``/``or``), a
  Python ``for`` over a device array, or a call whose summary says the
  callee crosses that parameter. ``.block_until_ready()`` is a *fence*,
  not a transfer: it keeps its origins and records nothing — matching
  the runtime ``jax.transfer_guard`` contract the budget rule mirrors.

The evaluator makes two passes per function: pass one only grows the
local environment (so loop-carried assignments converge), pass two
records crossings. Precision is deliberately modest — no path
sensitivity, no container element tracking — but it is *sound enough in
practice* to prove the fused-SCF one-readback contract and cheap enough
to stay inside the lint runtime budget.
"""

from __future__ import annotations

import ast
import dataclasses

from sirius_tpu.analysis.core import (
    FunctionInfo,
    ModuleInfo,
    ProjectIndex,
    _JIT_WRAPPERS,
    call_name,
    dotted_name,
)

DEV = "dev"

_DEVICE_PREFIXES = ("jnp.", "jax.numpy.", "lax.", "jax.lax.",
                    "jax.scipy.", "jsp.", "jax.nn.")
_DEVICE_CALLS = {"jax.device_put", "device_put"}
_CAST_FNS = {"float", "int", "bool", "complex"}
_NP_CROSSERS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                "np.copy", "numpy.copy"}
_DEVICE_GET = {"jax.device_get", "device_get"}
_SYNC_METHODS = {"item", "tolist"}
_FENCE_METHODS = {"block_until_ready"}
# host-returning builtins: pass device values without moving them
# (len/shape are metadata; str/repr only appear on host paths)
_HOST_FNS = {"len", "range", "print", "str", "repr", "format",
             "isinstance", "hasattr", "getattr", "type", "id",
             "enumerate", "zip", "list", "tuple", "dict", "set",
             "sorted", "reversed"}


@dataclasses.dataclass
class Crossing:
    """One device→host movement, attributable to a source line."""

    node: ast.AST
    kind: str    # cast | asarray | item | device_get | bool | iter | call
    detail: str  # the call/expression text that moves the data
    origins: frozenset


def _param_names(node: ast.AST) -> list[str]:
    a = getattr(node, "args", None)
    if a is None:
        return []
    names = [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
    return names


class DeviceModel:
    """Project-wide device/host dataflow summaries (built lazily once
    per ProjectIndex; the three rule families share one instance)."""

    _CACHE_ATTR = "_dataflow_device_model"

    @classmethod
    def of(cls, project: ProjectIndex) -> "DeviceModel":
        model = getattr(project, cls._CACHE_ATTR, None)
        if model is None:
            model = cls(project)
            setattr(project, cls._CACHE_ATTR, model)
        return model

    def __init__(self, project: ProjectIndex):
        self.project = project
        project.jit_reachable()  # populate seeds/jit_kwargs
        # (module, class) -> attrs bound to jitted callables
        # (``self.X = ... jax.jit(...) ...`` anywhere in the class)
        self.jit_attrs: dict[tuple[str, str], set[str]] = {}
        # (module, class, attr) -> impl method name it wraps, when the
        # binding's jit call wraps ``self.<impl>`` (compilerules keys
        # the trace-signature cross-check on this)
        self.jit_attr_impl: dict[tuple[str, str, str], str] = {}
        self._scan_jit_attrs()
        # per-function summaries, keyed by FunctionInfo.key
        self.return_origins: dict[tuple, frozenset] = {}
        self.crossed_params: dict[tuple, frozenset] = {}
        self._inst_types: dict[tuple, dict[str, tuple[ModuleInfo, str]]] = {}
        self._attr_types: dict[tuple[str, str, str],
                               tuple[ModuleInfo, str]] = {}
        self._scan_instance_attrs()
        self._fixpoint()
        self._crossings: dict[tuple, list[Crossing]] = {}

    # -- structural scans --------------------------------------------------

    def _scan_jit_attrs(self) -> None:
        for fi in self.project.iter_functions():
            if not fi.cls:
                continue
            for node in ast.walk(fi.node):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1):
                    continue
                tgt = dotted_name(node.targets[0])
                if not tgt or not tgt.startswith("self."):
                    continue
                for sub in ast.walk(node.value):
                    if (isinstance(sub, ast.Call)
                            and call_name(sub) in _JIT_WRAPPERS):
                        attr = tgt[5:]
                        self.jit_attrs.setdefault(
                            (fi.module.name, fi.cls), set()).add(attr)
                        if sub.args:
                            d = dotted_name(sub.args[0])
                            if d and d.startswith("self."):
                                self.jit_attr_impl[
                                    (fi.module.name, fi.cls, attr)
                                ] = d[5:]
                        break
            # ... or handed out by a function that built it
            for tgt, _, _ in self.project.factory_jit_bindings(fi):
                if tgt.startswith("self."):
                    self.jit_attrs.setdefault(
                        (fi.module.name, fi.cls), set()).add(tgt[5:])

    def _resolve_class(self, mi: ModuleInfo,
                       name: str) -> tuple[ModuleInfo, str] | None:
        """``ClassName`` / ``mod.ClassName`` -> defining (module, class)."""
        if "." not in name:
            if name in mi.classes:
                return (mi, name)
            tgt = mi.imports.get(name)
            if tgt and "." in tgt:
                m, c = tgt.rsplit(".", 1)
                if m in self.project.modules and (
                        c in self.project.modules[m].classes):
                    return (self.project.modules[m], c)
            return None
        head, rest = name.split(".", 1)
        base = mi.imports.get(head, head)
        parts = f"{base}.{rest}".split(".")
        for i in range(len(parts) - 1, 0, -1):
            m = ".".join(parts[:i])
            if m in self.project.modules:
                c = ".".join(parts[i:])
                if c in self.project.modules[m].classes:
                    return (self.project.modules[m], c)
                break
        return None

    def _scan_instance_attrs(self) -> None:
        """``self.a = ClassName(...)`` -> (module, cls, a) instance type."""
        for fi in self.project.iter_functions():
            if not fi.cls:
                continue
            for node in ast.walk(fi.node):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.value, ast.Call)):
                    continue
                tgt = dotted_name(node.targets[0])
                cn = call_name(node.value)
                if not tgt or not tgt.startswith("self.") or not cn:
                    continue
                hit = self._resolve_class(fi.module, cn)
                if hit:
                    self._attr_types[
                        (fi.module.name, fi.cls, tgt[5:])] = hit

    def instance_types(self, fi: FunctionInfo) -> dict:
        """Local-variable -> (module, class) bindings from
        ``x = ClassName(...)`` assignments inside ``fi``."""
        cached = self._inst_types.get(fi.key)
        if cached is not None:
            return cached
        out: dict[str, tuple[ModuleInfo, str]] = {}
        for node in ast.walk(fi.node):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)):
                continue
            cn = call_name(node.value)
            if not cn:
                continue
            hit = self._resolve_class(fi.module, cn)
            if hit:
                out[node.targets[0].id] = hit
        self._inst_types[fi.key] = out
        return out

    # -- call resolution with instance typing ------------------------------

    def resolve_call(self, fi: FunctionInfo,
                     name: str) -> list[FunctionInfo]:
        out = self.project._resolve_call(fi.module, fi.cls, name)
        if out or "." not in name:
            return out
        head, rest = name.split(".", 1)
        hit = self.instance_types(fi).get(head)
        if hit is None and head == "self" and fi.cls and "." in rest:
            # self.a.method() through a typed instance attribute
            a, rest2 = rest.split(".", 1)
            hit2 = self._attr_types.get((fi.module.name, fi.cls, a))
            if hit2:
                m, c = hit2
                q = f"{c}.{rest2}"
                if q in m.functions:
                    return [m.functions[q]]
            return []
        if hit is None:
            return []
        m, c = hit
        q = f"{c}.{rest}"
        return [m.functions[q]] if q in m.functions else []

    def is_jit_binding_call(self, fi: FunctionInfo, name: str) -> bool:
        """``self.X(...)`` where X is a jit attr of fi's class, or a
        local ``g = jax.jit(...)`` binding name."""
        if name.startswith("self.") and fi.cls:
            attr = name[5:].split(".")[0]
            return attr in self.jit_attrs.get(
                (fi.module.name, fi.cls), ())
        return name in self._local_jit_names(fi)

    def _local_jit_names(self, fi: FunctionInfo) -> set[str]:
        names = getattr(fi, "_local_jit_names", None)
        if names is None:
            names = set()
            for node in ast.walk(fi.node):
                if (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and isinstance(node.value, ast.Call)
                        and call_name(node.value) in _JIT_WRAPPERS):
                    names.add(node.targets[0].id)
            names.update(t for t, _, _ in
                         self.project.factory_jit_bindings(fi)
                         if "." not in t)
            fi._local_jit_names = names
        return names

    # -- summary fixpoint --------------------------------------------------

    def _fixpoint(self) -> None:
        fns = list(self.project.iter_functions())
        for fi in fns:
            self.return_origins[fi.key] = frozenset()
            self.crossed_params[fi.key] = frozenset()
        for _ in range(4):  # summaries stabilise in 2-3 rounds
            changed = False
            for fi in fns:
                scan = _FunctionScan(self, fi)
                scan.run()
                ret = frozenset(scan.return_origins)
                crossed = frozenset(
                    o for c in scan.crossings for o in c.origins
                    if o != DEV)
                if ret != self.return_origins[fi.key]:
                    self.return_origins[fi.key] = ret
                    changed = True
                if crossed != self.crossed_params[fi.key]:
                    self.crossed_params[fi.key] = crossed
                    changed = True
            if not changed:
                break

    # -- per-function results ----------------------------------------------

    def crossings(self, fi: FunctionInfo) -> list[Crossing]:
        cached = self._crossings.get(fi.key)
        if cached is None:
            scan = _FunctionScan(self, fi)
            scan.run()
            cached = scan.crossings
            self._crossings[fi.key] = cached
        return cached


class _FunctionScan:
    """Two-pass forward evaluation of one function body: pass one only
    grows the environment (loop-carried assignments), pass two records
    crossings and return origins."""

    def __init__(self, model: DeviceModel, fi: FunctionInfo):
        self.model = model
        self.fi = fi
        self.env: dict[str, frozenset] = {}
        self.crossings: list[Crossing] = []
        self.return_origins: set[str] = set()
        self._emitting = False
        for i, p in enumerate(_param_names(fi.node)):
            self.env[p] = frozenset({f"p{i}"})

    def run(self) -> None:
        node = self.fi.node
        if isinstance(node, ast.Lambda):
            self._emitting = True
            self.return_origins |= self._eval(node.body)
            return
        self._emitting = False
        self._visit_block(node.body)
        self._emitting = True
        self.crossings = []
        self.return_origins = set()
        self._visit_block(node.body)

    # -- statements --------------------------------------------------------

    def _visit_block(self, stmts) -> None:
        for s in stmts:
            self._visit(s)

    def _visit(self, node: ast.stmt) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested scopes analysed as their own functions
        if isinstance(node, ast.Assign):
            o = self._eval(node.value)
            for t in node.targets:
                self._bind(t, o)
        elif isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._bind(node.target, self._eval(node.value))
        elif isinstance(node, ast.AugAssign):
            o = self._eval(node.value)
            if isinstance(node.target, ast.Name):
                prev = self.env.get(node.target.id, frozenset())
                self.env[node.target.id] = prev | o
        elif isinstance(node, ast.Return):
            if node.value is not None:
                self.return_origins |= self._eval(node.value)
        elif isinstance(node, ast.Expr):
            self._eval(node.value)
        elif isinstance(node, (ast.If, ast.While)):
            self._test(node.test)
            self._visit_block(node.body)
            self._visit_block(node.orelse)
        elif isinstance(node, ast.For):
            o = self._eval(node.iter)
            if o and self._emitting:
                self._cross(node.iter, "iter",
                            "Python for over a device value", o)
            self._bind(node.target, o)
            self._visit_block(node.body)
            self._visit_block(node.orelse)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                self._eval(item.context_expr)
                if item.optional_vars is not None:
                    self._bind(item.optional_vars, frozenset())
            self._visit_block(node.body)
        elif isinstance(node, ast.Try):
            self._visit_block(node.body)
            for h in node.handlers:
                self._visit_block(h.body)
            self._visit_block(node.orelse)
            self._visit_block(node.finalbody)
        elif isinstance(node, ast.Assert):
            self._test(node.test)
        elif isinstance(node, (ast.Raise, ast.Delete, ast.Global,
                               ast.Nonlocal, ast.Pass, ast.Break,
                               ast.Continue, ast.Import, ast.ImportFrom)):
            if isinstance(node, ast.Raise) and node.exc is not None:
                self._eval(node.exc)

    def _bind(self, target: ast.AST, origins: frozenset) -> None:
        if isinstance(target, ast.Name):
            self.env[target.id] = origins
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, origins)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, origins)
        # attribute/subscript stores: drop (no heap model)

    def _test(self, test: ast.AST) -> None:
        """Implicit bool coercion: a tainted branch condition is a
        device->host sync. ``x is None`` identity tests are static."""
        if (isinstance(test, ast.Compare)
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in test.ops)):
            return
        o = self._eval(test)
        if o and self._emitting:
            self._cross(test, "bool",
                        "implicit bool() on a device value", o)

    # -- expressions -------------------------------------------------------

    def _cross(self, node: ast.AST, kind: str, detail: str,
               origins: frozenset) -> None:
        self.crossings.append(Crossing(node, kind, detail,
                                       frozenset(origins)))

    def _eval(self, e: ast.AST) -> frozenset:
        empty = frozenset()
        if e is None or isinstance(e, ast.Constant):
            return empty
        if isinstance(e, ast.Name):
            return self.env.get(e.id, empty)
        if isinstance(e, ast.Attribute):
            if e.attr.endswith(("_dev", "_device")):
                return frozenset({DEV})
            if e.attr in ("dtype", "shape", "ndim", "size"):
                return empty  # array metadata: host-side, no transfer
            return self._eval(e.value)
        if isinstance(e, ast.Subscript):
            return self._eval(e.value) | self._eval(e.slice)
        if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
            o = empty
            for x in e.elts:
                o |= self._eval(x)
            return o
        if isinstance(e, ast.Dict):
            o = empty
            for k, v in zip(e.keys, e.values):
                if k is not None:
                    o |= self._eval(k)
                o |= self._eval(v)
            return o
        if isinstance(e, ast.BinOp):
            return self._eval(e.left) | self._eval(e.right)
        if isinstance(e, ast.UnaryOp):
            o = self._eval(e.operand)
            if isinstance(e.op, ast.Not) and o and self._emitting:
                self._cross(e, "bool",
                            "`not` on a device value", o)
                return empty
            return o
        if isinstance(e, ast.BoolOp):
            # short-circuiting coerces each operand to bool; record per
            # tainted operand and return host (the enclosing test must
            # not double-count)
            for v in e.values:
                vo = self._eval(v)
                if vo and self._emitting:
                    self._cross(v, "bool",
                                "and/or on a device value", vo)
            return empty
        if isinstance(e, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in e.ops):
                return empty  # identity: static at trace/host time
            o = self._eval(e.left)
            for c in e.comparators:
                o |= self._eval(c)
            return o
        if isinstance(e, ast.IfExp):
            self._test(e.test)
            return self._eval(e.body) | self._eval(e.orelse)
        if isinstance(e, ast.Starred):
            return self._eval(e.value)
        if isinstance(e, (ast.JoinedStr, ast.FormattedValue)):
            for sub in ast.iter_child_nodes(e):
                self._eval(sub)
            return empty
        if isinstance(e, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                          ast.DictComp)):
            o = empty
            for gen in e.generators:
                go = self._eval(gen.iter)
                self._bind(gen.target, go)
                o |= go
            if isinstance(e, ast.DictComp):
                o |= self._eval(e.key) | self._eval(e.value)
            else:
                o |= self._eval(e.elt)
            return o
        if isinstance(e, ast.Lambda):
            return empty
        if isinstance(e, ast.NamedExpr):
            o = self._eval(e.value)
            self._bind(e.target, o)
            return o
        if isinstance(e, ast.Await):
            return self._eval(e.value)
        if isinstance(e, ast.Call):
            return self._eval_call(e)
        return empty

    def _eval_call(self, call: ast.Call) -> frozenset:
        empty = frozenset()
        name = call_name(call) or ""
        arg_origins = [self._eval(a) for a in call.args]
        kw_origins = {k.arg: self._eval(k.value) for k in call.keywords}
        all_in = empty
        for o in arg_origins:
            all_in |= o
        for o in kw_origins.values():
            all_in |= o

        # explicit crossings --------------------------------------------
        if name in _CAST_FNS:
            if call.args and arg_origins[0] and self._emitting:
                self._cross(call, "cast", f"{name}()", arg_origins[0])
            return empty
        if name in _NP_CROSSERS:
            if call.args and arg_origins[0] and self._emitting:
                self._cross(call, "asarray", f"{name}()", arg_origins[0])
            return empty
        if name in _DEVICE_GET:
            if all_in and self._emitting:
                self._cross(call, "device_get", f"{name}()", all_in)
            return empty
        if isinstance(call.func, ast.Attribute):
            base_o = self._eval(call.func.value)
            if call.func.attr in _SYNC_METHODS:
                if base_o and self._emitting:
                    self._cross(call, "item",
                                f".{call.func.attr}()", base_o)
                return empty
            if call.func.attr in _FENCE_METHODS:
                return base_o  # fence: synchronises, moves nothing

        # device producers ----------------------------------------------
        if (name.startswith(_DEVICE_PREFIXES) or name in _DEVICE_CALLS
                or self.model.is_jit_binding_call(self.fi, name)):
            return frozenset({DEV})

        if name in _HOST_FNS:
            return empty

        # project-resolved calls: substitute summaries ---------------------
        cands = self.model.resolve_call(self.fi, name) if name else []
        if cands:
            out: set[str] = set()
            for cand in cands:
                pnames = _param_names(cand.node)
                is_method = bool(cand.cls) and pnames[:1] == ["self"]
                off = 1 if is_method and "." in name else 0

                def actual(idx: int) -> frozenset:
                    j = idx - off
                    if 0 <= j < len(arg_origins):
                        return arg_origins[j]
                    if 0 <= idx < len(pnames):
                        return kw_origins.get(pnames[idx], empty)
                    return empty

                for tok in self.model.return_origins.get(cand.key, ()):
                    if tok == DEV:
                        out.add(DEV)
                    elif tok.startswith("p"):
                        out |= actual(int(tok[1:]))
                for tok in self.model.crossed_params.get(cand.key, ()):
                    idx = int(tok[1:])
                    o = actual(idx)
                    if o and self._emitting:
                        pn = pnames[idx] if idx < len(pnames) else tok
                        self._cross(
                            call, "call",
                            f"{name}() moves its `{pn}` argument to "
                            f"host", o)
            return frozenset(out)

        # unresolved: conservative pass-through
        return all_in
