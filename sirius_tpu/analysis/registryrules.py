"""Registry-consistency rules.

Five registries keep names honest across subsystem boundaries:
``config/schema.py``'s ``ControlConfig`` fields (every ``control.*``
read), ``utils/faults.py``'s ``KNOWN_SITES`` (every fault-injection
site literal), ``obs/costs.py``'s ``scf_stage_costs`` keys plus
``UNCOSTED_SPANS`` (every ``scf.*``/``md.*``/``serve.*``/``campaign.*``
span name), ``obs/events.py``'s ``KNOWN_EVENT_KINDS`` (every
``emit(kind, ...)`` literal), and ``obs/metrics.py``'s
``KNOWN_METRIC_NAMES`` (every ``REGISTRY.counter/gauge/histogram``
name literal in production code — tests register throwaway names on
private registries and are exempt).
Each registry is parsed *by AST* from the live source — never imported
— so the lint works in any environment and the registries cannot drift
from what the rule checks.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re

from sirius_tpu.analysis.core import (
    ProjectIndex,
    call_name,
    dotted_name,
)

_SPAN_RE = re.compile(
    r"^(scf|md|serve|campaign|trace|collective)\.[a-z_][a-z0-9_.]*$")


@dataclasses.dataclass
class RegistryConfig:
    """Override any field in tests; ``None`` disables that family."""

    control_keys: frozenset | None = None
    fault_sites: frozenset | None = None
    span_keys: frozenset | None = None
    event_kinds: frozenset | None = None
    metric_names: frozenset | None = None


def _module_tree(project: ProjectIndex, suffix: str,
                 relsrc: str) -> ast.AST | None:
    for mi in project.modules.values():
        if mi.name.endswith(suffix):
            return mi.fctx.tree
    path = os.path.join(project.root, relsrc)
    if os.path.exists(path):
        try:
            with open(path, encoding="utf-8") as fh:
                return ast.parse(fh.read(), filename=path)
        except (OSError, SyntaxError):
            return None
    return None


def _control_keys(tree: ast.AST) -> frozenset | None:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "ControlConfig":
            keys = set()
            for sub in node.body:
                if isinstance(sub, ast.AnnAssign) and isinstance(
                        sub.target, ast.Name):
                    keys.add(sub.target.id)
            return frozenset(keys)
    return None


def _tuple_of_strings(tree: ast.AST, name: str) -> frozenset | None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == name
                   for t in node.targets):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List, ast.Set)):
            out = {e.value for e in node.value.elts
                   if isinstance(e, ast.Constant)
                   and isinstance(e.value, str)}
            return frozenset(out)
    return None


def _span_keys(tree: ast.AST) -> frozenset | None:
    keys: set[str] = set()
    found = False
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == (
                "scf_stage_costs"):
            found = True
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Subscript)
                        and isinstance(sub.slice, ast.Constant)
                        and isinstance(sub.slice.value, str)):
                    keys.add(sub.slice.value)
    uncosted = _tuple_of_strings(tree, "UNCOSTED_SPANS")
    if uncosted:
        found = True
        keys |= uncosted
    return frozenset(keys) if found else None


def load_registry(project: ProjectIndex) -> RegistryConfig:
    schema = _module_tree(project, "config.schema",
                          "sirius_tpu/config/schema.py")
    faults = _module_tree(project, "utils.faults",
                          "sirius_tpu/utils/faults.py")
    costs = _module_tree(project, "obs.costs", "sirius_tpu/obs/costs.py")
    events = _module_tree(project, "obs.events", "sirius_tpu/obs/events.py")
    metrics = _module_tree(project, "obs.metrics",
                           "sirius_tpu/obs/metrics.py")
    return RegistryConfig(
        control_keys=_control_keys(schema) if schema else None,
        fault_sites=(_tuple_of_strings(faults, "KNOWN_SITES")
                     if faults else None),
        span_keys=_span_keys(costs) if costs else None,
        event_kinds=(_tuple_of_strings(events, "KNOWN_EVENT_KINDS")
                     if events else None),
        metric_names=(_tuple_of_strings(metrics, "KNOWN_METRIC_NAMES")
                      if metrics else None),
    )


_CONTROL_BASES = {"control", "ctl", "ctrl"}
_NOT_FIELDS = {"get", "items", "keys", "values", "replace", "copy",
               "asdict"}


class UnknownControlKey:
    """A ``*.control.<key>`` read for a key that is not a
    ``ControlConfig`` field — it would raise AttributeError at runtime
    (or, via getattr default, silently never fire)."""

    name = "unknown-control-key"
    wants_registry = True

    def run(self, project: ProjectIndex, registry=None):
        reg = registry or load_registry(project)
        keys = reg.control_keys
        if keys is None:
            return
        for mi in project.modules.values():
            if mi.name.endswith("config.schema"):
                continue
            fctx = mi.fctx
            for node in ast.walk(fctx.tree):
                key = None
                if isinstance(node, ast.Attribute):
                    base = node.value
                    if (isinstance(base, ast.Attribute)
                            and base.attr == "control"):
                        key = node.attr
                    elif (isinstance(base, ast.Name)
                          and base.id in _CONTROL_BASES):
                        key = node.attr
                elif isinstance(node, ast.Call) and call_name(
                        node) == "getattr" and len(node.args) >= 2:
                    tgt = node.args[0]
                    d = dotted_name(tgt)
                    if d and (d.endswith(".control")
                              or d in _CONTROL_BASES):
                        a = node.args[1]
                        if isinstance(a, ast.Constant) and isinstance(
                                a.value, str):
                            key = a.value
                if (key is None or key in keys or key.startswith("_")
                        or key in _NOT_FIELDS):
                    continue
                yield project.finding(
                    self.name, fctx, node,
                    f"`control.{key}` is not a ControlConfig field in "
                    f"config/schema.py")


class UnknownFaultSite:
    """A fault-injection call naming a site that is not in
    ``utils/faults.KNOWN_SITES`` — the spec grammar would accept it and
    the fault would silently never fire."""

    name = "unknown-fault-site"
    wants_registry = True
    _FNS = {"armed", "check", "corrupt", "fire"}

    def run(self, project: ProjectIndex, registry=None):
        reg = registry or load_registry(project)
        sites = reg.fault_sites
        if sites is None:
            return
        for fctx in project.files:
            for node in ast.walk(fctx.tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in self._FNS):
                    continue
                base = dotted_name(node.func.value)
                if not base or not base.split(".")[-1] == "faults":
                    continue
                if not node.args:
                    continue
                a = node.args[0]
                if not (isinstance(a, ast.Constant)
                        and isinstance(a.value, str)):
                    continue
                if a.value in sites:
                    continue
                yield project.finding(
                    self.name, fctx, node,
                    f"fault site \"{a.value}\" is not in "
                    f"utils/faults.KNOWN_SITES")


class UncostedSpan:
    """A span name wired into the observability layer with neither a
    ``scf_stage_costs()`` flop model nor an ``UNCOSTED_SPANS``
    exemption — the attribution report would show it with 0 FLOPs and
    skew MFU percentages."""

    name = "uncosted-span"
    wants_registry = True
    _FNS = {"record", "span", "open_span", "_stage"}

    def run(self, project: ProjectIndex, registry=None):
        reg = registry or load_registry(project)
        spans = reg.span_keys
        if spans is None:
            return
        for fctx in project.files:
            if fctx.relpath.endswith(("obs/costs.py", "utils/faults.py")):
                continue
            for node in ast.walk(fctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                d = call_name(node)
                if not d or d.split(".")[-1] not in self._FNS:
                    continue
                if not node.args:
                    continue
                a = node.args[0]
                if not (isinstance(a, ast.Constant)
                        and isinstance(a.value, str)
                        and _SPAN_RE.match(a.value)):
                    continue
                if a.value in spans:
                    continue
                yield project.finding(
                    self.name, fctx, node,
                    f"span \"{a.value}\" has no scf_stage_costs() key "
                    f"and no UNCOSTED_SPANS exemption in obs/costs.py")


def _literal_strings(node: ast.AST) -> list[str]:
    """String literal(s) an argument expression evaluates to: plain
    constants plus both arms of a conditional expression
    (``emit("drain" if mode == "drain" else "abort", ...)``)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.IfExp):
        return _literal_strings(node.body) + _literal_strings(node.orelse)
    return []


class UnknownEventKind:
    """An ``obs.events.emit(kind, ...)`` literal not registered in
    ``obs/events.KNOWN_EVENT_KINDS`` — the event would be written but
    no consumer (trace exporter, replayer, dashboards) knows the kind
    exists, so it silently vanishes from every downstream view."""

    name = "unknown-event-kind"
    wants_registry = True
    _BASES = {"events", "obs", "obs_events", "_events"}

    def run(self, project: ProjectIndex, registry=None):
        reg = registry or load_registry(project)
        kinds = reg.event_kinds
        if kinds is None:
            return
        for fctx in project.files:
            if (fctx.relpath.startswith("tests/")
                    or fctx.relpath.endswith("obs/events.py")):
                continue
            for node in ast.walk(fctx.tree):
                if not (isinstance(node, ast.Call) and node.args):
                    continue
                if isinstance(node.func, ast.Name):
                    if node.func.id != "emit":
                        continue
                elif isinstance(node.func, ast.Attribute):
                    if node.func.attr != "emit":
                        continue
                    base = dotted_name(node.func.value)
                    if not base or base.split(".")[-1] not in self._BASES:
                        continue
                else:
                    continue
                for kind in _literal_strings(node.args[0]):
                    if kind in kinds:
                        continue
                    yield project.finding(
                        self.name, fctx, node,
                        f"event kind \"{kind}\" is not in "
                        f"obs/events.KNOWN_EVENT_KINDS")


class UnknownMetricName:
    """A ``REGISTRY.counter/gauge/histogram(name, ...)`` literal not
    registered in ``obs/metrics.KNOWN_METRIC_NAMES`` — the series would
    be exported under a name no dashboard query or CI smoke assertion
    knows about. Private per-test registries (any base other than the
    module-level ``REGISTRY``) are exempt."""

    name = "unknown-metric-name"
    wants_registry = True
    _KINDS = {"counter", "gauge", "histogram"}

    def run(self, project: ProjectIndex, registry=None):
        reg = registry or load_registry(project)
        names = reg.metric_names
        if names is None:
            return
        for fctx in project.files:
            if fctx.relpath.startswith("tests/"):
                continue
            for node in ast.walk(fctx.tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in self._KINDS
                        and node.args):
                    continue
                base = dotted_name(node.func.value)
                if not base or base.split(".")[-1] != "REGISTRY":
                    continue
                for mname in _literal_strings(node.args[0]):
                    if mname in names:
                        continue
                    yield project.finding(
                        self.name, fctx, node,
                        f"metric \"{mname}\" is not in "
                        f"obs/metrics.KNOWN_METRIC_NAMES")


RULES = (UnknownControlKey, UnknownFaultSite, UncostedSpan,
         UnknownEventKind, UnknownMetricName)
