"""Sharding-consistency rules and the per-driver sharding inventory.

A static mesh/axis model built from every ``Mesh(...)``,
``NamedSharding``, ``PartitionSpec``/``P``, ``shard_map``,
``with_sharding_constraint`` and named-axis collective in the tree:

- ``shard-unknown-axis`` — an axis name used in a PartitionSpec or as a
  collective's ``axis_name`` that no ``Mesh(...)`` in the project
  declares. GSPMD raises at trace time *if* the code path runs; decks
  that never take the path ship the typo silently.
- ``shard-axis-mismatch`` — a ``NamedSharding(mesh, P(...))`` or
  ``shard_map(..., mesh=mesh, ...)`` whose spec names an axis that the
  *specific* mesh bound to that variable does not declare (the axis may
  exist on some other mesh — that is exactly the hazard: a "k" spec on
  the "g" mesh).
- ``shard-constraint-in-loop`` — ``with_sharding_constraint`` inside a
  loop body of jit-reachable code: every iteration forces GSPMD to
  materialise the constraint, i.e. a potential all-to-all reshard in
  the hot loop.

``sharding_inventory()`` renders the pre-flight artifact the
ExecutionPlan refactor needs (`sirius-lint --report sharding`): one row
per driver — scf, serve, md, relax, campaigns — listing the meshes it
constructs, the axes/specs/constraints/collectives it uses, and its
jit/donation sites, so the five independently-maintained sharding sites
can be diffed at review time instead of in a post-mortem.
"""

from __future__ import annotations

import ast

from sirius_tpu.analysis.core import (
    FunctionInfo,
    ProjectIndex,
    _JIT_WRAPPERS,
    call_name,
    dotted_name,
)

_MESH_CTORS = {"Mesh", "make_mesh"}
_SPEC_CTORS = {"PartitionSpec", "P"}
_COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "all_gather",
                "all_to_all", "ppermute", "pshuffle", "axis_index",
                "psum_scatter"}
_CONSTRAINT = {"with_sharding_constraint"}

DRIVERS = (
    # the SCF driver's meshes and shardings are decided behind
    # band_solve.choose; dft/scf.py only replicates onto band.mesh
    ("scf", "sirius_tpu/dft/band_solve.py"),
    ("serve", "sirius_tpu/serve/scheduler.py"),
    ("md", "sirius_tpu/md/driver.py"),
    ("relax", "sirius_tpu/dft/relax.py"),
    ("campaigns", "sirius_tpu/campaigns/runner.py"),
)


def _axis_strings(node: ast.AST) -> list[str]:
    """Axis-name string literals inside a spec/axes expression."""
    out = []
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            out.append(n.value)
    return out


def _mesh_axes_from_call(call: ast.Call) -> list[str]:
    """Declared axis names of a ``Mesh(devs, ("k", "b"))`` /
    ``axis_names=...`` construction (empty when non-literal)."""
    for k in call.keywords:
        if k.arg == "axis_names":
            return _axis_strings(k.value)
    if len(call.args) >= 2:
        return _axis_strings(call.args[1])
    return []


def _is_ctor(mi, name: str | None, ctors: set[str]) -> bool:
    """True when a dotted call name denotes one of ``ctors``, resolving
    local aliases (``Mesh as _Mesh``, ``PartitionSpec as _P``) through
    the module's import map."""
    if not name:
        return False
    if name.split(".")[-1] in ctors:
        return True
    tgt = mi.imports.get(name) or mi.imports.get(name.split(".")[0])
    return bool(tgt) and tgt.split(".")[-1] in ctors


class MeshModel:
    """Project-wide mesh declarations + per-function mesh variables."""

    _CACHE_ATTR = "_shard_mesh_model"

    @classmethod
    def of(cls, project: ProjectIndex) -> "MeshModel":
        model = getattr(project, cls._CACHE_ATTR, None)
        if model is None:
            model = cls(project)
            setattr(project, cls._CACHE_ATTR, model)
        return model

    def __init__(self, project: ProjectIndex):
        self.project = project
        # every Mesh construction: (fctx, node, axes tuple)
        self.meshes: list[tuple] = []
        # function key -> axes it returns (mesh-producing helpers like
        # make_mesh / production_mesh, incl. (mesh, spec) tuple returns)
        self.producer_axes: dict[tuple, tuple] = {}
        for mi in project.modules.values():
            for node in ast.walk(mi.fctx.tree):
                if (isinstance(node, ast.Call)
                        and _is_ctor(mi, call_name(node), {"Mesh"})):
                    axes = tuple(_mesh_axes_from_call(node))
                    if axes:
                        self.meshes.append((mi.fctx, node, axes))
        for fi in project.iter_functions():
            axes = set()
            for node in ast.walk(fi.node):
                if (isinstance(node, ast.Call)
                        and _is_ctor(fi.module, call_name(node),
                                     {"Mesh"})):
                    axes.update(_mesh_axes_from_call(node))
            if axes:
                self.producer_axes[fi.key] = tuple(sorted(axes))
        # one propagation round: helpers that return another helper's
        # mesh (production_mesh -> make_mesh)
        for _ in range(2):
            changed = False
            for fi in project.iter_functions():
                if fi.key in self.producer_axes:
                    continue
                axes = set()
                for node in ast.walk(fi.node):
                    if not isinstance(node, ast.Call):
                        continue
                    d = call_name(node)
                    if not d:
                        continue
                    for cand in project._resolve_call(
                            fi.module, fi.cls, d):
                        axes.update(self.producer_axes.get(cand.key, ()))
                if axes and any(
                        isinstance(n, ast.Return)
                        for n in ast.walk(fi.node)):
                    self.producer_axes[fi.key] = tuple(sorted(axes))
                    changed = True
            if not changed:
                break
        self.declared_axes = frozenset(
            a for _, _, axes in self.meshes for a in axes) | frozenset(
            a for axes in self.producer_axes.values() for a in axes)

    def local_mesh_vars(self, fi: FunctionInfo) -> dict[str, tuple]:
        """var name -> axes for meshes bound inside ``fi``:
        ``m = Mesh(..., axes)``, ``m = make_mesh(...)`` and the
        ``mesh, spec = production_mesh(...)`` tuple-unpack idiom."""
        out: dict[str, tuple] = {}
        for node in ast.walk(fi.node):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.value, ast.Call)):
                continue
            call, tgt = node.value, node.targets[0]
            d = call_name(call)
            axes: tuple = ()
            if _is_ctor(fi.module, d, {"Mesh"}):
                axes = tuple(_mesh_axes_from_call(call))
            elif d:
                for cand in self.project._resolve_call(
                        fi.module, fi.cls, d):
                    axes = self.producer_axes.get(cand.key, ())
                    if axes:
                        break
            if not axes:
                continue
            if isinstance(tgt, ast.Name):
                out[tgt.id] = axes
            elif (isinstance(tgt, ast.Tuple) and tgt.elts
                  and isinstance(tgt.elts[0], ast.Name)):
                out[tgt.elts[0].id] = axes  # (mesh, spec) unpack
        return out


def _axis_name_args(call: ast.Call) -> list[ast.AST]:
    """The axis-name expression(s) of a collective call."""
    out = [k.value for k in call.keywords if k.arg == "axis_name"]
    d = call_name(call) or ""
    tail = d.split(".")[-1]
    if not out and tail in _COLLECTIVES and len(call.args) >= 2:
        out.append(call.args[1])
    if not out and tail == "axis_index" and call.args:
        out.append(call.args[0])
    return out


class ShardUnknownAxis:
    """An axis name in a PartitionSpec or collective that no Mesh in
    the project declares — a trace-time crash on the paths that run,
    a latent typo on the ones that don't."""

    name = "shard-unknown-axis"

    def run(self, project: ProjectIndex):
        model = MeshModel.of(project)
        if not model.declared_axes:
            return  # no meshes anywhere: nothing to check against
        for fi in project.iter_functions():
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = call_name(node)
                if _is_ctor(fi.module, d, _SPEC_CTORS):
                    for a in _axis_strings(node):
                        if a not in model.declared_axes:
                            yield project.finding(
                                self.name, fi, node,
                                f"axis \"{a}\" in PartitionSpec is not "
                                f"declared by any Mesh (declared: "
                                f"{sorted(model.declared_axes)})")
                elif d and d.split(".")[-1] in _COLLECTIVES:
                    for arg in _axis_name_args(node):
                        for a in _axis_strings(arg):
                            if a not in model.declared_axes:
                                yield project.finding(
                                    self.name, fi, node,
                                    f"collective axis_name \"{a}\" is "
                                    f"not declared by any Mesh")


class ShardAxisMismatch:
    """A spec bound to a *specific* mesh variable names an axis that
    mesh does not declare — e.g. a ("k", "b") spec device_put onto the
    "g" FFT mesh. The axis exists somewhere, which is why the global
    unknown-axis check cannot catch it."""

    name = "shard-axis-mismatch"

    def _check(self, project, fi, mesh_axes, call, spec_node):
        for a in _axis_strings(spec_node):
            if a not in mesh_axes:
                yield project.finding(
                    self.name, fi, call,
                    f"axis \"{a}\" not on this mesh (axes: "
                    f"{list(mesh_axes)}); the spec would be rejected "
                    f"at trace time")

    def run(self, project: ProjectIndex):
        model = MeshModel.of(project)
        for fi in project.iter_functions():
            mesh_vars = model.local_mesh_vars(fi)
            if not mesh_vars:
                continue
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = call_name(node)
                if _is_ctor(fi.module, d,
                            {"NamedSharding"}) and len(node.args) >= 2:
                    axes = mesh_vars.get(dotted_name(node.args[0]) or "")
                    if axes:
                        yield from self._check(
                            project, fi, axes, node, node.args[1])
                elif d and d.split(".")[-1] in ("shard_map",
                                                "_shard_map"):
                    mesh_kw = next(
                        (k.value for k in node.keywords
                         if k.arg == "mesh"), None)
                    if mesh_kw is None:
                        continue
                    axes = mesh_vars.get(dotted_name(mesh_kw) or "")
                    if not axes:
                        continue
                    for k in node.keywords:
                        if k.arg in ("in_specs", "out_specs"):
                            yield from self._check(
                                project, fi, axes, node, k.value)


class ShardConstraintInLoop:
    """``with_sharding_constraint`` inside a loop of jit-reachable code
    — each iteration pins a layout the compiler must materialise,
    i.e. a standing invitation for a per-iteration reshard."""

    name = "shard-constraint-in-loop"

    def run(self, project: ProjectIndex):
        reach = project.jit_reachable()
        for fi in project.iter_functions():
            if fi.key not in reach:
                continue
            loop_spans = [
                (n.lineno, n.end_lineno)
                for n in ast.walk(fi.node)
                if isinstance(n, (ast.For, ast.While, ast.AsyncFor))]
            if not loop_spans:
                continue
            for node in ast.walk(fi.node):
                if not (isinstance(node, ast.Call)
                        and _is_ctor(fi.module, call_name(node),
                                     _CONSTRAINT)):
                    continue
                line = node.lineno
                if any(lo < line <= hi for lo, hi in loop_spans):
                    yield project.finding(
                        self.name, fi, node,
                        f"with_sharding_constraint inside a loop of "
                        f"jit-reachable `{fi.qualname}`; hoist the "
                        f"constraint or fold it into the carry's "
                        f"sharding")


# ---------------------------------------------------------------------------
# inventory report


def _file_inventory(project: ProjectIndex, relpath: str) -> dict:
    mi = project.by_relpath.get(relpath)
    row: dict = {
        "path": relpath,
        "indexed": mi is not None,
        "meshes": [],
        "partition_specs": [],
        "named_shardings": 0,
        "sharding_constraints": 0,
        "collectives": [],
        "jit_sites": 0,
        "donate_argnums": [],
        "axes_used": [],
    }
    if mi is None:
        return row
    axes_used: set[str] = set()
    specs: set[tuple] = set()
    for node in ast.walk(mi.fctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = call_name(node)
        if _is_ctor(mi, d, {"Mesh"}):
            axes = _mesh_axes_from_call(node)
            row["meshes"].append({"line": node.lineno, "axes": axes})
            axes_used.update(axes)
        elif _is_ctor(mi, d, _SPEC_CTORS):
            s = tuple(_axis_strings(node))
            specs.add(s)
            axes_used.update(s)
        elif _is_ctor(mi, d, {"NamedSharding"}):
            row["named_shardings"] += 1
        elif _is_ctor(mi, d, _CONSTRAINT):
            row["sharding_constraints"] += 1
        elif d and d.split(".")[-1] in _COLLECTIVES:
            names = [a for arg in _axis_name_args(node)
                     for a in _axis_strings(arg)]
            row["collectives"].append({
                "op": d.split(".")[-1], "line": node.lineno,
                "axes": names})
            axes_used.update(names)
        if d in _JIT_WRAPPERS:
            row["jit_sites"] += 1
            for k in node.keywords:
                if k.arg == "donate_argnums":
                    lits = [n.value for n in ast.walk(k.value)
                            if isinstance(n, ast.Constant)
                            and isinstance(n.value, int)]
                    row["donate_argnums"].append(
                        {"line": node.lineno, "argnums": lits})
    row["partition_specs"] = sorted(list(s) for s in specs)
    row["axes_used"] = sorted(axes_used)
    return row


def sharding_inventory(project: ProjectIndex) -> dict:
    """The five-driver sharding inventory (``--report sharding``)."""
    model = MeshModel.of(project)
    return {
        "version": 1,
        "declared_axes": sorted(model.declared_axes),
        "drivers": {name: _file_inventory(project, rel)
                    for name, rel in DRIVERS},
        "parallel": {
            rel: _file_inventory(project, rel)
            for rel in sorted(
                f.relpath for f in project.files
                if f.relpath.startswith("sirius_tpu/parallel/"))},
    }


RULES = (ShardUnknownAxis, ShardAxisMismatch, ShardConstraintInLoop)
