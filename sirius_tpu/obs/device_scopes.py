"""Where the device's time went, in the program's own names.

A profiler capture (obs/trace.py) names a device operation by the compiler's
label (``fusion.1038``, ``while.29``). The program's own names for what it
runs are the ``jax.named_scope`` blocks of its device programs; they reach the
executable as the ``op_name`` metadata of every HLO instruction
(``jit(davidson_kset)/.../while/body/closed_call/davidson_rr/jit(eigh)/eigh``).
``table`` joins the two: every operation of the capture is put to the scope
path of its instruction, and the seconds are summed per path as unions of
intervals, per device.

The optimised module that ran comes by one of two routes. The capture itself
may hold it: one ``Hlo Proto`` bytes stat per jitted module on the xplane's
``/host:metadata`` plane (the CPU backend; a TPU program compiled with the
persistent compile cache off). With the cache on, as every entry point has
it, the TPU's capture holds none of the job's programs, compiled in the
process or loaded (PERF.md section 6, PR 36); then the executables the
process's backends hold are asked for theirs (``loaded_modules``): no second
compile either way.

Three steps, each testable alone:

* ``hlo_modules`` / ``loaded_modules``: a protobuf wire reader (no schema, no
  tensorflow) from the serialized XSpace (XSpace.planes ->
  XPlane.event_metadata -> XEventMetadata.stats -> HloProto.hlo_module) or an
  executable's serialized HloModuleProto to {instruction: Instr}, through
  computations -> instructions, of which it keeps the name, the opcode, the
  ``op_name``, the called computations and a custom call's target;
* ``operations``: the events of the "XLA Ops" line of every ``/device:``
  plane, each with the module whose "XLA Modules" event contains it on the
  same plane (instruction names repeat across modules). Where there is no
  device plane (the CPU backend) the host events that carry ``hlo_op`` /
  ``hlo_module`` stats stand in, by ``device_ordinal``;
* ``reduce``: the union arithmetic on plain columns.

An operation's scope path is the chain of ``SCOPES`` names in its own
``op_name``, outermost first (``davidson_hpsi/local_op``). Where that holds
none (a fusion keeps its root's metadata) it is the path most instructions of
the computations it calls share; else the operation is unscoped. A ``while``,
``conditional`` or ``call`` contains its body's events and is no leaf: it is
never counted as an operation or as unscoped time; its interval counts
towards ``busy_s`` and, where it lies under a scope itself (the reduction's
loop under ``eigh_reduce``, the QDWH program's conditionals under
``eigh_kernel``), towards that scope's union, so that the gaps between its
body's operations are the scope's too.

``SCOPES`` is the registry: every ``jax.named_scope`` the tree emits, and
nothing else (tests/test_device_scopes.py holds both directions). Nothing here
is imported until a capture stops (or `sirius-trace export` merges one).
"""

from __future__ import annotations

import bisect
import re
import time
from typing import NamedTuple

SCOPES = (
    # solvers/davidson.py: the start's orthonormalisation, then the stages
    # of a Davidson step (residual and preconditioning of the new block, H
    # and S applied to it, the subspace matrices, Rayleigh-Ritz, rotation)
    "davidson_ortho", "davidson_residual",
    "davidson_hpsi", "davidson_inner", "davidson_rr", "davidson_rotate",
    # inside davidson_hpsi: ops/local.py, ops/gamma.py, ops/hamiltonian.py
    "local_op", "beta_proj",
    # inside davidson_rr: solvers/subspace_eigh.py
    "eigh_reduce", "eigh_kernel",
    # dft/fused.py::_step_impl and dft/potential.generate_potential_device
    "step_density", "step_mixing", "step_hartree", "step_xc", "xc_gga",
    "xc_spin",
    "step_vloc", "step_d_matrix", "step_ledger",
    # core/fftgrid.g_to_r_gather
    "box_fill",
    # dft/density.py: the symmetrisers of a deck with use_symmetry, under
    # step_density (the density matrix, the new density), step_vloc (v_eff)
    # and step_ledger (the idempotency invariant)
    "sym_pw", "sym_dm",
    # the programs between band solve and step: parallel/batched.py
    # density_kset and density_matrix_kset, ops/gamma.density_gamma
    "density_kset", "density_gamma", "density_matrix",
    # parallel/dist_fft.py (the G-sharded path)
    "collective.all_to_all_x2y", "collective.all_to_all_y2x",
    "collective.psum_beta",
)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
CONTAINERS = ("while", "conditional", "call")
SOURCE_XPLANE = "xplane_hlo_proto"
SOURCE_LOADED = "loaded_executables"
SOURCE_NONE = "none"


# ---- protobuf wire reader ---------------------------------------------------

def _varint(buf, i: int):
    b = buf[i]
    if b < 0x80:  # most keys, lengths and ids are one byte
        return b, i + 1
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of one message in buf[start:end]: an int for a
    varint, (start, end) for a length-delimited field; fixed-width fields are
    skipped (none is read here)."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """(key, value span) of one map<int64, message> entry."""
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


class Instr(NamedTuple):
    opcode: str
    op_name: str
    calls: tuple      # ids of the computations it calls
    target: str       # custom_call_target


def _instruction(buf, span):
    name = opcode = op_name = target = ""
    calls = []
    for f, v in _fields(buf, *span):
        if f == 1:
            name = _text(buf, v)
        elif f == 2:
            opcode = _text(buf, v)
        elif f == 7:  # OpMetadata
            for g, w in _fields(buf, *v):
                if g == 2:
                    op_name = _text(buf, w)
        elif f == 28:
            target = _text(buf, v)
        elif f == 38:  # called_computation_ids, packed or not
            if isinstance(v, tuple):
                i = v[0]
                while i < v[1]:
                    c, i = _varint(buf, i)
                    calls.append(c)
            else:
                calls.append(v)
    return name, Instr(opcode, op_name, tuple(calls), target)


class Module(NamedTuple):
    name: str
    instrs: dict          # instruction name -> Instr (names are module-wide)
    computations: dict    # computation id -> (name, [instruction names])


def _module(buf, span) -> Module:
    """HloModuleProto: name = 1, computations = 3 (name = 1, instructions = 2,
    id = 5)."""
    name, instrs, comps = "", {}, {}
    for f, v in _fields(buf, *span):
        if f == 1:
            name = _text(buf, v)
        elif f == 3:
            cname, cid, members = "", 0, []
            for g, w in _fields(buf, *v):
                if g == 1:
                    cname = _text(buf, w)
                elif g == 2:
                    iname, instr = _instruction(buf, w)
                    instrs[iname] = instr
                    members.append(iname)
                elif g == 5:
                    cid = w
            comps[cid] = (cname, members)
    return Module(name, instrs, comps)


def _planes(buf):
    """(name, span) of every XPlane of a serialized XSpace."""
    for f, v in _fields(buf, 0, len(buf)):
        if f == 1:
            name = ""
            for g, w in _fields(buf, *v):
                if g == 2:
                    name = _text(buf, w)
                    break
            yield name, v


def hlo_modules(xspace: bytes) -> dict:
    """{"jit_f(5)": Module} from the ``Hlo Proto`` stats of the capture's
    ``/host:metadata`` plane: the key is the event metadata's name, the
    module's name and its program id as the "XLA Modules" line writes them.
    Empty where the capture holds none."""
    buf = memoryview(xspace)
    out = {}
    for pname, span in _planes(buf):
        if pname != METADATA_PLANE:
            continue
        events, stat_names = [], {}
        for f, v in _fields(buf, *span):
            if f == 4:
                events.append(_map_entry(buf, v)[1])
            elif f == 5:
                key, value = _map_entry(buf, v)
                for g, w in _fields(buf, *value):
                    if g == 2:
                        stat_names[key] = _text(buf, w)
        hlo_ids = {k for k, n in stat_names.items() if n == HLO_STAT}
        for ev in events:
            name, proto = "", None
            for f, v in _fields(buf, *ev):
                if f == 2:
                    name = _text(buf, v)
                elif f == 5:  # XStat: metadata_id = 1, bytes_value = 6
                    sid, blob = None, None
                    for g, w in _fields(buf, *v):
                        if g == 1:
                            sid = w
                        elif g == 6:
                            blob = w
                    if sid in hlo_ids and blob is not None:
                        proto = blob
            if proto is None:
                continue
            for f, v in _fields(buf, *proto):  # HloProto.hlo_module = 1
                if f == 1:
                    out[name] = _module(buf, v)
    return out


# ---- from an instruction to its scope ---------------------------------------

# vmap(name), jvp(vmap(name)): a transform's wrapper around a scope's name;
# jit(name) is a function's frame, whatever the function is called
_WRAPPED = re.compile(r"^(?:(?!p?jit\()\w+\()*([\w.\-]+)\)*$")
_IS_SCOPE = frozenset(SCOPES)


def scope_path(op_name: str) -> str:
    """The chain of the program's scope names in an ``op_name``, outermost
    first, "/"-joined; a transform's wrapper (``vmap(davidson_hpsi)``) does
    not hide a name, and a name repeated on the way down (a nested jit's
    frame under its caller's scope) counts once. "" where there is none."""
    found = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in _IS_SCOPE and found[-1:] != [m.group(1)]:
            found.append(m.group(1))
    return "/".join(found)


def short_name(name: str, instr: Instr | None, module: Module | None) -> str:
    """What benchmark/harness/trace_reduce.short_name makes of the TPU's
    event text, from the instruction itself: its name and, where it calls a
    computation or a custom kernel, that one's."""
    hint = ""
    if instr is not None:
        if instr.calls and module is not None:
            hint = module.computations.get(instr.calls[0], ("",))[0]
        hint = hint or instr.target
    return (name + " " + hint).strip()[:120]


def assign(module: Module) -> dict:
    """{instruction name: scope path} for one module: the instruction's own
    ``op_name`` first; else, for a fusion or another leaf, the path most
    instructions of the computations it calls share (through nested calls; a
    loop or conditional goes by its own name only: its body may hold several
    stages); else, in a module whose named instructions all lie under one
    top-level scope (density_kset: a program of one stage), that scope: the
    copies and reshapes the compiler put between them are the stage's; ""
    where none of the three names one."""
    own = {n: scope_path(i.op_name) for n, i in module.instrs.items()}
    votes_of: dict = {}

    def votes(cid):
        got = votes_of.get(cid)
        if got is None:
            got = votes_of[cid] = {}
            for n in module.computations.get(cid, ("", ()))[1]:
                if own[n]:
                    got[own[n]] = got.get(own[n], 0) + 1
                else:
                    for c in module.instrs[n].calls:
                        for p, k in votes(c).items():
                            got[p] = got.get(p, 0) + k
        return got

    out = {}
    for n, instr in module.instrs.items():
        path = own[n]
        if not path and instr.calls and instr.opcode not in CONTAINERS:
            tally: dict = {}
            for c in instr.calls:
                for p, k in votes(c).items():
                    tally[p] = tally.get(p, 0) + k
            if tally:
                path = max(sorted(tally), key=tally.get)
        out[n] = path
    tops = {p.split("/")[0] for p in out.values() if p}
    if len(tops) == 1:
        (only,) = tops
        out = {n: p or only for n, p in out.items()}
    return out


# ---- the capture's operations -----------------------------------------------

class Op(NamedTuple):
    plane: str       # the profiler's plane the event is on
    device: str      # the device it ran on: the plane, or host:<ordinal>
    module: str      # "jit_f(5)", "" where no module event contains it
    instr: str       # the HLO instruction's name
    start_ns: float
    dur_ns: float


def instruction_name(event_name: str) -> str:
    """The TPU names an event by its instruction's whole text
    (``%fusion.3 = f32[...] fusion(...), calls=...``); the CPU backend by
    the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def operations(xspace: bytes):
    """(ops, module_seconds): the device operations of a serialized XSpace as
    `Op`s, and {device: {module: seconds}} from the "XLA Modules" line."""
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    planes = list(pd.planes)
    have_device = any(p.name.startswith("/device:") for p in planes)
    ops, module_s = [], {}
    for plane in planes:
        is_dev = plane.name.startswith("/device:")
        if have_device != is_dev:
            continue
        lines = {ln.name: ln for ln in plane.lines} if is_dev else {}
        if is_dev:
            mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                           e.name) for e in lines[MODULES_LINE].events
                          ) if MODULES_LINE in lines else []
            starts = [m[0] for m in mods]
            if OPS_LINE not in lines:
                continue
            per = module_s.setdefault(plane.name, {})
            for s, e, name in mods:
                per[name] = per.get(name, 0.0) + (e - s) * 1e-9
            for ev in lines[OPS_LINE].events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                k = bisect.bisect_right(starts, s) - 1
                inside = k >= 0 and s < mods[k][1]
                ops.append(Op(plane.name, plane.name,
                              mods[k][2] if inside else "",
                              instruction_name(ev.name), s, d))
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_op" not in stats:
                    continue
                module = stats.get("hlo_module", "")
                if "program_id" in stats:
                    module = f"{module}({stats['program_id']})"
                ops.append(Op(plane.name,
                              f"host:{stats.get('device_ordinal', 0)}",
                              module, str(stats["hlo_op"]),
                              float(ev.start_ns), float(ev.duration_ns)))
    if not have_device:  # no "XLA Modules" line: the union of each module's ops
        spans: dict = {}
        for op in ops:
            spans.setdefault(op.device, {}).setdefault(op.module, []).append(
                (op.start_ns, op.start_ns + op.dur_ns))
        module_s = {p: {m: union_ns(iv) * 1e-9 for m, iv in per.items()}
                    for p, per in spans.items()}
    return ops, module_s


def loaded_modules(names) -> dict:
    """{module name: [Module, ...]} of the executables this process's
    backends hold whose module is named in ``names``: the second route to an
    operation's ``op_name`` (module docstring), the one a TPU job needs."""
    import jax.extend.backend

    out: dict = {}
    for client in jax.extend.backend.backends().values():
        for exe in client.live_executables():
            for hlo in exe.hlo_modules():
                if hlo.name in names:
                    blob = hlo.as_serialized_hlo_module_proto()
                    out.setdefault(hlo.name, []).append(
                        _module(memoryview(blob), (0, len(blob))))
    return out


def _best(candidates, instrs):
    """Of several modules of one name (one program at two shapes), the one
    that holds most of the instruction names the capture ran under it; None
    where none holds any."""
    hits, best = max(((sum(n in m.instrs for n in instrs), i)
                      for i, m in enumerate(candidates)), default=(0, 0))
    return candidates[best] if hits else None


class Scoped(NamedTuple):
    op: Op
    path: str        # "" = unscoped
    leaf: bool
    short: str


def scoped_operations(xspace: bytes, loaded=loaded_modules):
    """(operations as `Scoped`, module_seconds, scopes_seen,
    modules_without_hlo, source): the one reader of a capture: `table`
    reduces it, obs/timeline labels the device events it merges with it.
    A module comes from the capture's own ``Hlo Proto`` (by its name and
    program id, as the "XLA Modules" line writes them) and, where the
    capture holds none, from ``loaded`` (`loaded_modules`; None: do not
    look). ``scopes_seen``: the registry's names those modules carry."""
    in_xplane = hlo_modules(xspace)
    ops, module_s = operations(xspace)
    ran: dict = {}       # module key -> the instruction names it ran
    for op in ops:
        ran.setdefault(op.module, set()).add(op.instr)
    modules = {key: in_xplane.get(key) for key in ran if key}
    source = SOURCE_XPLANE if any(modules.values()) else SOURCE_NONE
    wanted = {k.split("(")[0] for k, m in modules.items() if m is None}
    if wanted and loaded is not None:
        held = loaded(wanted)
        for key, mod in modules.items():
            if mod is None:
                modules[key] = _best(held.get(key.split("(")[0], ()), ran[key])
        if any(held.values()):
            source = (SOURCE_LOADED if source == SOURCE_NONE
                      else source + "+" + SOURCE_LOADED)
    paths = {key: assign(mod) if mod else {} for key, mod in modules.items()}
    seen = {name for by_instr in paths.values() for path in set(by_instr.values())
            for name in path.split("/") if name}
    memo: dict = {}      # an instruction runs once a trip: (module, name) ->
    out = []
    for op in ops:
        got = memo.get((op.module, op.instr))
        if got is None:
            mod = modules.get(op.module)
            instr = mod.instrs.get(op.instr) if mod else None
            opcode = instr.opcode if instr else op.instr.split(".")[0]
            got = memo[(op.module, op.instr)] = (
                paths.get(op.module, {}).get(op.instr, ""),
                opcode not in CONTAINERS, short_name(op.instr, instr, mod))
        out.append(Scoped(op, *got))
    missing = sorted({k.split("(")[0] for k, m in modules.items() if m is None}
                     | ({"(no module)"} if "" in ran else set()))
    return out, module_s, sorted(seen), missing, source


def event_scopes(xspace: bytes) -> dict:
    """{(plane name, `instruction_name` of the event, start_ns): scope path}
    of the device events under one of the program's scopes
    (obs/timeline._merge_xplane)."""
    return {(s.op.plane, s.op.instr, s.op.start_ns): s.path
            for s in scoped_operations(xspace, loaded=None)[0] if s.path}


# ---- the arithmetic ---------------------------------------------------------

def union_ns(intervals) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def reduce(rows, top: int = 10) -> dict:
    """``rows``: (device, path, leaf, start_ns, dur_ns, short, module) of every
    operation. Per device the union of all operations' intervals is its busy
    time; a scope's seconds are the union of the operations under its path
    (a parent's holds its children's; a loop under the path with its gaps),
    its ``ops`` the leaves among them; each is the mean over the devices that
    ran anything, so no scope exceeds ``busy_s``."""
    every: dict = {}
    scoped: dict = {}    # path -> device -> intervals
    count: dict = {}
    bare: dict = {}      # device -> intervals of unscoped leaves
    bare_by: dict = {}   # (short, module) -> summed ns
    for dev, path, leaf, s, d, short, module in rows:
        every.setdefault(dev, []).append((s, s + d))
        if not path:
            if leaf:
                bare.setdefault(dev, []).append((s, s + d))
                key = (short, module)
                bare_by[key] = bare_by.get(key, 0.0) + d
            continue
        parts = path.split("/")
        for k in range(1, len(parts) + 1):
            p = "/".join(parts[:k])
            scoped.setdefault(p, {}).setdefault(dev, []).append((s, s + d))
            count[p] = count.get(p, 0) + leaf
    ndev = len(every)

    def mean_s(by_dev):
        return sum(union_ns(iv) for iv in by_dev.values()) * 1e-9 / max(ndev, 1)

    ranked = sorted(bare_by.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": mean_s(every), "devices": ndev,
        "by_scope": {p: {"s": mean_s(by_dev), "ops": count[p]}
                     for p, by_dev in sorted(scoped.items())},
        "unscoped_s": mean_s(bare),
        "unscoped_top": [[short, module, ns * 1e-9 / max(ndev, 1)]
                         for (short, module), ns in ranked],
    }


def table(xspace: bytes, steps: int | None = None) -> dict:
    """What ``trace.scopes`` records (obs/trace.py) for one capture."""
    t0 = time.perf_counter()
    ops, module_s, seen, missing, source = scoped_operations(xspace)
    out = reduce((s.op.device, s.path, s.leaf, s.op.start_ns, s.op.dur_ns,
                  s.short, s.op.module.split("(")[0]) for s in ops)
    ndev = max(len(module_s), 1)
    by_module: dict = {}
    for per in module_s.values():
        for name, sec in per.items():
            key = name.split("(")[0]
            by_module[key] = by_module.get(key, 0.0) + sec / ndev
    out.update(
        by_module=dict(sorted(by_module.items(), key=lambda kv: -kv[1])),
        steps=steps, scopes_seen=seen, modules_without_hlo=missing,
        source=source, num_ops=len(ops), reduce_s=time.perf_counter() - t0)
    return out
