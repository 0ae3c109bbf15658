"""Lint engine: file/project indexing, jit-reachability, suppression
comments, findings, and the checked-in baseline.

The engine is pure ``ast``/``tokenize`` — it never imports the modules it
analyses (linting must work without jax installed and must not trigger
backend initialization). Rules are project-scoped: each rule class gets
the whole :class:`ProjectIndex` so cross-file analyses (the lock graph,
the jit-reachability closure, registry lookups) are first-class rather
than bolted on.

Suppression grammar (comments anywhere on the offending line)::

    x = np.asarray(y)  # sirius-lint: disable=jit-numpy-call
    # sirius-lint: disable-file=lock-order-cycle   (anywhere in the file)
    y = bad()          # sirius-lint: disable=*    (every rule, this line)

Baseline: findings are fingerprinted by ``(rule, enclosing qualname,
whitespace-normalized source-line text)`` — stable across unrelated
edits that shift line numbers AND across file renames (the path is
advisory metadata on the baseline entry, not part of the key) — and
compared as multisets, so CI fails only when a fingerprint's count
*grows* (a genuinely new violation), never on pre-existing, justified
ones. ``write_baseline`` migrates pre-rename baselines in place:
justifications are carried over by fingerprint first, then by
``(rule, normalized text)`` for entries whose fingerprint scheme (or
enclosing file) changed.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import io
import json
import os
import re
import tokenize

_SUPPRESS_RE = re.compile(
    r"#\s*sirius-lint:\s*(disable(?:-file)?)\s*=\s*([A-Za-z0-9_\-*,\s]+)")


# ---------------------------------------------------------------------------
# findings


def normalize_text(text: str) -> str:
    """Whitespace-collapsed source line: the fingerprint's text key."""
    return " ".join(text.split())


@dataclasses.dataclass
class Finding:
    rule: str
    path: str  # posix relpath from the scan root (advisory, not keyed)
    line: int
    col: int
    message: str
    text: str = ""  # stripped source line (fingerprint input)
    qualname: str = "<module>"  # enclosing function/method qualname

    @property
    def fingerprint(self) -> str:
        """Keyed on (rule, enclosing qualname, normalized text) so a file
        rename — or a pure reformat — does not orphan baseline entries;
        the path rides along as advisory metadata only."""
        h = hashlib.sha1(
            f"{self.rule}|{self.qualname}|{normalize_text(self.text)}"
            .encode()).hexdigest()
        return h[:16]

    def to_dict(self) -> dict:
        return {
            "rule": self.rule, "path": self.path, "line": self.line,
            "col": self.col, "message": self.message, "text": self.text,
            "qualname": self.qualname, "fingerprint": self.fingerprint,
        }

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"[{self.rule}] {self.message}")


# ---------------------------------------------------------------------------
# AST helpers shared by the rule modules


def dotted_name(e: ast.AST) -> str | None:
    """'a.b.c' for Name/Attribute chains, None for anything else."""
    parts: list[str] = []
    while isinstance(e, ast.Attribute):
        parts.append(e.attr)
        e = e.value
    if isinstance(e, ast.Name):
        parts.append(e.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call) -> str | None:
    return dotted_name(call.func)


def assigned_names(target: ast.AST) -> list[str]:
    """Plain Name identifiers bound by an assignment target."""
    out: list[str] = []
    for n in ast.walk(target):
        if isinstance(n, ast.Name) and isinstance(
                n.ctx, (ast.Store, ast.Del)):
            out.append(n.id)
    return out


# ---------------------------------------------------------------------------
# file / project indexing


class FileContext:
    """One parsed source file plus its suppression map."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.line_suppressions: dict[int, set[str]] = {}
        self.file_suppressions: set[str] = set()
        # every suppression token as written: (comment line, rule, file?)
        # — the stale-suppression audit diffs this against what fired
        self.suppression_records: list[tuple[int, str, bool]] = []
        self._scan_suppressions()

    def _scan_suppressions(self) -> None:
        try:
            toks = tokenize.generate_tokens(io.StringIO(self.source).readline)
            for tok in toks:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _SUPPRESS_RE.search(tok.string)
                if not m:
                    continue
                rules = {r.strip() for r in m.group(2).split(",") if r.strip()}
                file_level = m.group(1) == "disable-file"
                for r in sorted(rules):
                    self.suppression_records.append(
                        (tok.start[0], r, file_level))
                if file_level:
                    self.file_suppressions |= rules
                else:
                    self.line_suppressions.setdefault(
                        tok.start[0], set()).update(rules)
        except tokenize.TokenError:
            pass  # truncated file: lint what parsed, skip comment scan

    def suppressed(self, rule: str, line: int) -> bool:
        if rule in self.file_suppressions or "*" in self.file_suppressions:
            return True
        on_line = self.line_suppressions.get(line, ())
        return rule in on_line or "*" in on_line

    def matching_suppressions(self, rule: str, line: int):
        """The suppression records a (rule, line) finding is silenced by,
        as (comment_line, rule_token, file_level) keys."""
        out = []
        for tok in (rule, "*"):
            if tok in self.file_suppressions:
                out.extend(r for r in self.suppression_records
                           if r[1] == tok and r[2])
            if tok in self.line_suppressions.get(line, ()):
                out.append((line, tok, False))
        return out

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""


class FunctionInfo:
    """One function/method (or seeded lambda) in the project index."""

    def __init__(self, module: "ModuleInfo", qualname: str, node: ast.AST,
                 cls: str | None = None):
        self.module = module
        self.qualname = qualname  # "func" | "Class.method" | "<lambda@N>"
        self.node = node
        self.cls = cls
        self.jit_seed = False
        self.jit_kwargs: dict[str, ast.AST] = {}  # static/donate argnums

    @property
    def key(self) -> tuple[str, str]:
        return (self.module.name, self.qualname)

    def __repr__(self) -> str:
        return f"<fn {self.module.name}:{self.qualname}>"


class ModuleInfo:
    def __init__(self, name: str, fctx: FileContext):
        self.name = name
        self.fctx = fctx
        self.functions: dict[str, FunctionInfo] = {}
        self.imports: dict[str, str] = {}  # local alias -> dotted target
        self.classes: dict[str, ast.ClassDef] = {}


_JIT_WRAPPERS = {
    "jax.jit", "jit", "jax.pmap", "pmap",
    "eqx.filter_jit", "equinox.filter_jit", "filter_jit",
}
_PARTIAL = {"partial", "functools.partial"}
# higher-order ops that trace their function-valued arguments even when
# called outside an enclosing jit
_TRACING_HOFS = {
    "jax.lax.scan", "lax.scan", "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop", "jax.lax.cond", "lax.cond",
    "jax.lax.switch", "lax.switch", "jax.lax.map", "lax.map",
    "jax.lax.associative_scan", "lax.associative_scan",
    "jax.checkpoint", "jax.remat", "jax.vmap", "jax.grad",
    "jax.value_and_grad",
}


class ProjectIndex:
    """Modules, functions, imports, and the jit-reachability closure."""

    def __init__(self, root: str, paths: list[str]):
        self.root = os.path.abspath(root)
        self.modules: dict[str, ModuleInfo] = {}
        self.by_relpath: dict[str, ModuleInfo] = {}
        self.files: list[FileContext] = []
        self.errors: list[str] = []
        for p in paths:
            self._index_file(p)
        self._jit_reachable: set[tuple[str, str]] | None = None
        self._jit_factories: dict | None = None
        self._lambda_counter = 0

    # -- indexing ----------------------------------------------------------

    def _module_name(self, relpath: str) -> str:
        mod = relpath.replace(os.sep, "/")
        if mod.endswith(".py"):
            mod = mod[:-3]
        if mod.endswith("/__init__"):
            mod = mod[: -len("/__init__")]
        return mod.replace("/", ".")

    def _index_file(self, path: str) -> None:
        relpath = os.path.relpath(os.path.abspath(path), self.root)
        try:
            with open(path, encoding="utf-8") as fh:
                source = fh.read()
            fctx = FileContext(path, relpath, source)
        except (OSError, SyntaxError, ValueError) as e:
            self.errors.append(f"{relpath}: {type(e).__name__}: {e}")
            return
        mi = ModuleInfo(self._module_name(relpath), fctx)
        self.modules[mi.name] = mi
        self.by_relpath[fctx.relpath] = mi
        self.files.append(fctx)
        pkg = mi.name.rsplit(".", 1)[0] if "." in mi.name else ""
        for node in ast.walk(fctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    mi.imports[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:
                    parts = pkg.split(".") if pkg else []
                    parts = parts[: len(parts) - (node.level - 1)]
                    base = ".".join(parts + ([node.module]
                                             if node.module else []))
                for a in node.names:
                    if a.name == "*":
                        continue
                    mi.imports[a.asname or a.name] = (
                        f"{base}.{a.name}" if base else a.name)
        for node in fctx.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                mi.functions[node.name] = FunctionInfo(mi, node.name, node)
            elif isinstance(node, ast.ClassDef):
                mi.classes[node.name] = node
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        q = f"{node.name}.{sub.name}"
                        mi.functions[q] = FunctionInfo(
                            mi, q, sub, cls=node.name)

    # -- call/seed resolution ---------------------------------------------

    def _resolve_call(self, mi: ModuleInfo, cls: str | None,
                      name: str) -> list[FunctionInfo]:
        """FunctionInfo candidates a dotted call name may refer to."""
        out: list[FunctionInfo] = []
        if name.startswith("self.") and cls:
            q = f"{cls}.{name[5:]}"
            if q in mi.functions:
                out.append(mi.functions[q])
            return out
        if "." not in name:
            if name in mi.functions:
                out.append(mi.functions[name])
            elif name in mi.imports:
                tgt = mi.imports[name]
                if "." in tgt:
                    m, f = tgt.rsplit(".", 1)
                    if m in self.modules and f in self.modules[m].functions:
                        out.append(self.modules[m].functions[f])
            return out
        head, rest = name.split(".", 1)
        base = mi.imports.get(head, head)
        full = f"{base}.{rest}"
        # longest module prefix wins: "pkg.mod.Class.method" or "pkg.mod.fn"
        parts = full.split(".")
        for i in range(len(parts) - 1, 0, -1):
            m = ".".join(parts[:i])
            if m in self.modules:
                f = ".".join(parts[i:])
                if f in self.modules[m].functions:
                    out.append(self.modules[m].functions[f])
                break
        return out

    def _seed_target(self, mi: ModuleInfo, cls: str | None, arg: ast.AST,
                     enclosing: "FunctionInfo | None" = None,
                     ) -> list[FunctionInfo]:
        if isinstance(arg, ast.Lambda):
            self._lambda_counter += 1
            q = f"<lambda@{arg.lineno}#{self._lambda_counter}>"
            fi = FunctionInfo(mi, q, arg, cls=cls)
            mi.functions[q] = fi
            return [fi]
        if isinstance(arg, ast.Call):
            # unwrap jit(partial(f, ...)) / jit(shard_map(f, ...)) /
            # jit(checkpoint(f)) down to the function they wrap
            cn = call_name(arg) or ""
            if (cn in _PARTIAL or cn.split(".")[-1] in (
                    "shard_map", "checkpoint", "remat", "vmap", "pmap")
                    ) and arg.args:
                return self._seed_target(mi, cls, arg.args[0], enclosing)
            return []
        d = dotted_name(arg)
        if not d:
            return []
        out = self._resolve_call(mi, cls, d)
        if out or enclosing is None or "." in d:
            return out
        # a local bound to a partial: bound = partial(f, rec), then
        # jax.jit(bound)
        for node in ast.walk(enclosing.node):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == d
                    and isinstance(node.value, ast.Call)
                    and call_name(node.value) in _PARTIAL
                    and node.value.args
                    and dotted_name(node.value.args[0]) != d):
                return self._seed_target(mi, cls, node.value.args[0],
                                         enclosing)
        # a nested def: jax.jit(run) where run is local to `enclosing`
        for node in ast.walk(enclosing.node):
            if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name == d and node is not enclosing.node):
                q = f"{enclosing.qualname}.<locals>.{d}@{node.lineno}"
                fi = mi.functions.get(q)
                if fi is None:
                    fi = FunctionInfo(mi, q, node, cls=cls)
                    mi.functions[q] = fi
                return [fi]
        return out

    def _mark_seeds(self) -> None:
        for mi in self.modules.values():
            for fi in list(mi.functions.values()):
                node = fi.node
                for dec in getattr(node, "decorator_list", []):
                    d = dotted_name(dec)
                    if d in _JIT_WRAPPERS:
                        fi.jit_seed = True
                    elif isinstance(dec, ast.Call):
                        dc = call_name(dec)
                        if dc in _JIT_WRAPPERS:
                            fi.jit_seed = True
                            fi.jit_kwargs = {
                                k.arg: k.value for k in dec.keywords if k.arg}
                        elif dc in _PARTIAL and dec.args and dotted_name(
                                dec.args[0]) in _JIT_WRAPPERS:
                            fi.jit_seed = True
                            fi.jit_kwargs = {
                                k.arg: k.value for k in dec.keywords if k.arg}
            # expression-form seeds: jax.jit(f, ...) / lax.scan(body, ...)
            for fi in list(mi.functions.values()):
                for call in [n for n in ast.walk(fi.node)
                             if isinstance(n, ast.Call)]:
                    cn = call_name(call)
                    if cn in _JIT_WRAPPERS and call.args:
                        for tgt in self._seed_target(mi, fi.cls,
                                                     call.args[0], fi):
                            tgt.jit_seed = True
                            tgt.jit_kwargs.update({
                                k.arg: k.value
                                for k in call.keywords if k.arg})
                    elif cn in _TRACING_HOFS:
                        for a in call.args:
                            for tgt in self._seed_target(mi, fi.cls, a, fi):
                                tgt.jit_seed = True

    def function_calls(self, fi: FunctionInfo) -> list[FunctionInfo]:
        out: list[FunctionInfo] = []
        for call in [n for n in ast.walk(fi.node)
                     if isinstance(n, ast.Call)]:
            d = call_name(call)
            if d:
                out.extend(self._resolve_call(fi.module, fi.cls, d))
        return out

    def jit_reachable(self) -> set[tuple[str, str]]:
        """Keys of every function in the transitive closure of the jit
        seeds over the resolved project call graph."""
        if self._jit_reachable is not None:
            return self._jit_reachable
        self._mark_seeds()
        seen: set[tuple[str, str]] = set()
        frontier = [fi for mi in self.modules.values()
                    for fi in mi.functions.values() if fi.jit_seed]
        while frontier:
            fi = frontier.pop()
            if fi.key in seen:
                continue
            seen.add(fi.key)
            frontier.extend(self.function_calls(fi))
        self._jit_reachable = seen
        return seen

    def iter_functions(self):
        for mi in self.modules.values():
            yield from mi.functions.values()

    # -- jit wrappers handed out by a function -----------------------------

    def jit_factories(self) -> dict[tuple[str, str], tuple]:
        """Functions that return a jit wrapper they built (a process-level
        table of programs, dft/fused.step_program): key -> (the wrapper's
        place in a returned tuple, None where it is the whole value; the
        jit call's keyword arguments)."""
        if self._jit_factories is not None:
            return self._jit_factories
        from sirius_tpu.analysis.jaxrules import _local_jit_bindings

        out: dict[tuple[str, str], tuple] = {}
        for fi in self.iter_functions():
            built = {tgt: kwargs  # local name -> the jit call's kwargs
                     for tgt, kwargs, _ in _local_jit_bindings(fi.node)
                     if "." not in tgt}
            if not built:
                continue
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Return) or node.value is None:
                    continue
                in_tuple = isinstance(node.value, ast.Tuple)
                elts = node.value.elts if in_tuple else [node.value]
                for i, e in enumerate(elts):
                    if isinstance(e, ast.Name) and e.id in built:
                        out[fi.key] = (i if in_tuple else None, built[e.id])
        self._jit_factories = out
        return out

    def factory_jit_bindings(self, fi: FunctionInfo):
        """``name = factory(...)`` / ``self.attr, flag = factory(...)``
        inside ``fi``, factory one of `jit_factories`: yields (binding,
        the jit call's kwargs, assign), as jaxrules._local_jit_bindings
        does for a jit call written in place."""
        factories = self.jit_factories()
        if not factories:
            return
        short = {q.rsplit(".", 1)[-1] for _, q in factories}
        for node in ast.walk(fi.node):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.value, ast.Call)):
                continue
            d = call_name(node.value)
            if not d or d.rsplit(".", 1)[-1] not in short:
                continue
            for callee in self._resolve_call(fi.module, fi.cls, d):
                if callee.key not in factories:
                    continue
                place, kwargs = factories[callee.key]
                tgt = node.targets[0]
                if place is not None:
                    if not (isinstance(tgt, ast.Tuple)
                            and place < len(tgt.elts)):
                        continue
                    tgt = tgt.elts[place]
                name = dotted_name(tgt)
                if name:
                    yield name, kwargs, node
                break

    # -- findings ----------------------------------------------------------

    def qualname_at(self, fctx: FileContext, line: int) -> str:
        """Qualname of the innermost indexed function enclosing ``line``
        (``<module>`` for top-level code) — the rename-stable fingerprint
        anchor."""
        mi = self.by_relpath.get(fctx.relpath)
        if mi is None:
            return "<module>"
        best = None
        for fi in mi.functions.values():
            lo = getattr(fi.node, "lineno", None)
            hi = getattr(fi.node, "end_lineno", None)
            if lo is None or hi is None or not (lo <= line <= hi):
                continue
            if best is None or lo > best[0]:
                best = (lo, fi.qualname)
        return best[1] if best else "<module>"

    def finding(self, rule: str, fi_or_fctx, node: ast.AST | None,
                message: str) -> Finding:
        fctx = (fi_or_fctx.module.fctx
                if isinstance(fi_or_fctx, FunctionInfo) else fi_or_fctx)
        line = getattr(node, "lineno", 1) if node is not None else 1
        col = getattr(node, "col_offset", 0) if node is not None else 0
        if isinstance(fi_or_fctx, FunctionInfo):
            qualname = fi_or_fctx.qualname
        else:
            qualname = self.qualname_at(fctx, line)
        return Finding(rule=rule, path=fctx.relpath, line=line, col=col,
                       message=message, text=fctx.line_text(line),
                       qualname=qualname)


# ---------------------------------------------------------------------------
# engine


def all_rules() -> list:
    from sirius_tpu.analysis import (
        compilerules,
        jaxrules,
        lockrules,
        registryrules,
        shardrules,
        transferrules,
    )

    return (list(jaxrules.RULES) + list(lockrules.RULES)
            + list(registryrules.RULES) + list(compilerules.RULES)
            + list(transferrules.RULES) + list(shardrules.RULES))


DEFAULT_SCAN = ("sirius_tpu", "tools", "tests")
_SKIP_DIRS = {"__pycache__", ".git", "csrc", ".github"}


def collect_files(root: str, targets=DEFAULT_SCAN) -> list[str]:
    out: list[str] = []
    for t in targets:
        p = t if os.path.isabs(t) else os.path.join(root, t)
        if os.path.isfile(p) and p.endswith(".py"):
            out.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(d for d in dirnames if d not in _SKIP_DIRS)
            for f in sorted(filenames):
                if f.endswith(".py"):
                    out.append(os.path.join(dirpath, f))
    return out


class LintEngine:
    def __init__(self, root: str, paths: list[str] | None = None,
                 rules=None, registry=None):
        self.root = os.path.abspath(root)
        self.paths = paths if paths is not None else collect_files(self.root)
        self.project = ProjectIndex(self.root, self.paths)
        self.rules = rules if rules is not None else all_rules()
        self.registry = registry  # RegistryConfig override (tests)
        self.suppressed_count = 0
        # (relpath, comment_line, rule_token, file_level) records that
        # actually silenced a finding in the last run()
        self.used_suppressions: set[tuple] = set()
        self._ran = False

    def run(self) -> list[Finding]:
        findings: list[Finding] = []
        by_path = {f.relpath: f for f in self.project.files}
        seen: set[tuple] = set()  # lambdas re-walk their parent's lines
        for rule in self.rules:
            kwargs = {}
            if self.registry is not None and getattr(
                    rule, "wants_registry", False):
                kwargs["registry"] = self.registry
            for f in rule().run(self.project, **kwargs):
                key = (f.rule, f.path, f.line, f.col, f.message)
                if key in seen:
                    continue
                seen.add(key)
                fctx = by_path.get(f.path)
                if fctx is not None and fctx.suppressed(f.rule, f.line):
                    self.suppressed_count += 1
                    for rec in fctx.matching_suppressions(f.rule, f.line):
                        self.used_suppressions.add((fctx.relpath, *rec))
                    continue
                findings.append(f)
        findings.sort(key=lambda f: (f.path, f.line, f.rule))
        self._ran = True
        return findings

    def stale_suppressions(self) -> list[dict]:
        """Suppression comments that silenced nothing in the last run():
        either the violation was fixed (the comment is dead weight hiding
        future regressions) or the rule name is a typo and the comment
        never worked. Only meaningful after run() with the full rule set —
        the CLI guards the partial --rules case."""
        assert self._ran, "run() first"
        known = {r.name for r in self.rules}
        out = []
        for fctx in self.project.files:
            for line, rule, file_level in fctx.suppression_records:
                key = (fctx.relpath, line, rule, file_level)
                if key in self.used_suppressions:
                    continue
                reason = ("never fired" if rule == "*" or rule in known
                          else "unknown rule")
                out.append({
                    "path": fctx.relpath, "line": line, "rule": rule,
                    "file_level": file_level, "reason": reason,
                    "text": fctx.line_text(line),
                })
        out.sort(key=lambda e: (e["path"], e["line"], e["rule"]))
        return out


# ---------------------------------------------------------------------------
# baseline


def load_baseline(path: str) -> dict:
    """fingerprint -> {count, rule, path, text, justification}."""
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as fh:
        data = json.load(fh)
    return {e["fingerprint"]: e for e in data.get("findings", [])}


def write_baseline(path: str, findings: list[Finding],
                   old: dict | None = None) -> dict:
    """Aggregate findings into a baseline file, preserving justifications
    from the previous baseline for fingerprints that persist. Entries
    whose fingerprint changed (scheme migration, function rename) fall
    back to a (rule, normalized text) match so justifications survive."""
    old = old or {}
    by_text = {(e.get("rule"), normalize_text(e.get("text", ""))): e
               for e in old.values() if e.get("justification")}

    def _justification(f: Finding) -> str:
        hit = old.get(f.fingerprint)
        if hit and hit.get("justification"):
            return hit["justification"]
        hit = by_text.get((f.rule, normalize_text(f.text)))
        return hit["justification"] if hit else ""

    agg: dict[str, dict] = {}
    for f in findings:
        e = agg.setdefault(f.fingerprint, {
            "fingerprint": f.fingerprint, "rule": f.rule, "path": f.path,
            "qualname": f.qualname, "text": f.text, "count": 0,
            "justification": _justification(f),
        })
        e["count"] += 1
    data = {
        "version": 1,
        "comment": ("sirius-lint baseline: pre-existing, justified findings."
                    " CI fails only when a fingerprint's count grows."),
        "findings": sorted(agg.values(),
                           key=lambda e: (e["path"], e["rule"], e["text"])),
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=1, sort_keys=False)
        fh.write("\n")
    return agg


def new_findings(findings: list[Finding], baseline: dict) -> list[Finding]:
    """Findings whose fingerprint count exceeds the baselined count."""
    budget = {fp: e.get("count", 0) for fp, e in baseline.items()}
    out: list[Finding] = []
    for f in findings:  # engine output is sorted: excess = later lines
        if budget.get(f.fingerprint, 0) > 0:
            budget[f.fingerprint] -= 1
        else:
            out.append(f)
    return out
