"""A capture's table of device seconds by the program's own scope names
(sirius_tpu/obs/device_scopes.py, the ``trace.scopes`` record of obs/trace.py).

The wire reader and the scope assignment run on a small XSpace recorded on the
CPU backend (tests/data/two_scopes.xplane.pb: a jitted fori_loop with a
``davidson_hpsi`` and a ``davidson_rr`` block; ``python
tests/test_device_scopes.py`` records it again) and on XSpaces written here
field by field, so that the reader is checked against an encoder that shares
no line with it; the union arithmetic on intervals small enough to add up in
the head; the benchmark's five readers on a hand-written record. No test runs
an SCF: the record on a captured job's trace id is held by
tests/test_span_tree.py inside a capture it already makes.
"""

import contextlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.obs import device_scopes as ds

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "tests", "data", "two_scopes.xplane.pb")


# ---- an encoder of its own --------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(field, value):
    """One field: an int is a varint, bytes/str length-delimited."""
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _instr(name, opcode, op_name="", calls=(), target="", packed=True):
    out = _f(1, name) + _f(2, opcode)
    if op_name:
        out += _f(7, _f(1, opcode) + _f(2, op_name))
    if target:
        out += _f(28, target)
    if calls and packed:
        out += _f(38, b"".join(_varint(c) for c in calls))
    else:
        out += b"".join(_f(38, c) for c in calls)
    return out + _f(35, 7)  # an id nobody reads


def _hlo_proto(name, computations):
    """computations: {id: (name, [instruction bytes])}."""
    mod = _f(1, name)
    for cid, (cname, instrs) in computations.items():
        mod += _f(3, _f(1, cname) + b"".join(_f(2, i) for i in instrs)
                  + _f(5, cid))
    return _f(1, mod) + _f(3, b"\x08\x01")  # a buffer assignment to skip


def _plane(name, lines=(), hlo=None):
    """lines: [(line name, [(event name, start_ns, dur_ns)])]; ``hlo``:
    {module key: HloProto bytes} makes it the metadata plane."""
    ids = {}
    body = _f(2, name)
    for lname, events in lines:
        ln = _f(2, lname)
        for ename, start, dur in events:
            mid = ids.setdefault(ename, len(ids) + 1)
            ln += _f(4, _f(1, mid) + _f(2, int(start * 1000))
                     + _f(3, int(dur * 1000)))
        body += _f(3, ln)
    for ename, mid in ids.items():
        body += _f(4, _f(1, mid) + _f(2, _f(1, mid) + _f(2, ename)))
    if hlo:
        body += _f(5, _f(1, 1) + _f(2, _f(1, 1) + _f(2, "Hlo Proto")))
        body += _f(5, _f(1, 2) + _f(2, _f(1, 2) + _f(2, "other")))
        for k, (key, proto) in enumerate(hlo.items(), start=1):
            stats = _f(5, _f(1, 2) + _f(5, "x")) + _f(5, _f(1, 1) + _f(6, proto))
            body += _f(4, _f(1, k) + _f(2, _f(1, k) + _f(2, key) + stats))
    return _f(1, body)


def _tpu_name(instr, opcode, calls=""):
    return f"%{instr} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p){calls}"


# ---- the recorded XSpace ----------------------------------------------------

def _two_scopes():
    @jax.jit
    def two_scopes(x):
        def body(i, a):
            with jax.named_scope("davidson_hpsi"):
                a = jnp.sin(a @ a) + 1.0
            with jax.named_scope("davidson_rr"):
                _, v = jnp.linalg.eigh(a + a.T)
            return a + v * 1e-3
        return jax.lax.fori_loop(0, 3, body, x)

    return two_scopes, jnp.eye(16, dtype=jnp.float32) * 0.1


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED, "rb") as f:
        return f.read()


def test_wire_reader_finds_the_module_and_its_op_names(recorded):
    mods = ds.hlo_modules(recorded)
    (key,) = [k for k in mods if k.startswith("jit_two_scopes(")]
    mod = mods[key]
    assert mod.name == "jit_two_scopes"
    (loop,) = [n for n, i in mod.instrs.items() if i.opcode == "while"]
    body = mod.computations[mod.instrs[loop].calls[0]]
    assert len(mod.instrs[loop].calls) == 2 and body[1]
    paths = {ds.scope_path(i.op_name) for i in mod.instrs.values()}
    assert paths == {"", "davidson_hpsi", "davidson_rr"}
    eighs = [i for i in mod.instrs.values() if i.opcode == "custom-call"]
    assert eighs and all(i.target for i in eighs)
    assert all("davidson_rr/jit(eigh)" in i.op_name for i in eighs)


def test_recorded_capture_reduces_to_the_two_scopes(recorded):
    t = ds.table(recorded, steps=1)
    assert t["source"] == ds.SOURCE_XPLANE and t["modules_without_hlo"] == []
    assert t["scopes_seen"] == ["davidson_hpsi", "davidson_rr"]
    assert sorted(t["by_scope"]) == ["davidson_hpsi", "davidson_rr"]
    assert t["devices"] == 1 and t["steps"] == 1 and t["reduce_s"] > 0
    # three trips: a product and its fusion under hpsi, one eigh under rr
    ops = {p: v["ops"] for p, v in t["by_scope"].items()}
    assert ops["davidson_hpsi"] % 3 == 0 and ops["davidson_rr"] % 3 == 0
    rr, hpsi = t["by_scope"]["davidson_rr"]["s"], t["by_scope"]["davidson_hpsi"]["s"]
    assert rr > hpsi > 0  # a 16-row eigh against a 16-row product
    # leaves do not overlap on one CPU device: the parts add up to at most
    # the busy time, and the while loop's own interval is only in that
    assert rr + hpsi + t["unscoped_s"] <= t["busy_s"] * (1 + 1e-9)
    assert rr + hpsi + t["unscoped_s"] > 0.5 * t["busy_s"]
    assert t["by_module"] == {"jit_two_scopes": pytest.approx(t["busy_s"])}
    assert all(m == "jit_two_scopes" for _, m, _ in t["unscoped_top"])
    assert not any(n.startswith("while") for n, _, _ in t["unscoped_top"])


# ---- scope paths and assignment ---------------------------------------------

@pytest.mark.parametrize("op_name, path", [
    ("jit(davidson_kset)/vmap(vmap(jit(davidson)))/while/body/closed_call/"
     "davidson_rr/jit(eigh)/eigh", "davidson_rr"),
    ("jit(f)/davidson_hpsi/vmap()/local_op/gather", "davidson_hpsi/local_op"),
    ("jit(f)/vmap(davidson_hpsi)/jvp(beta_proj)/dot_general",
     "davidson_hpsi/beta_proj"),
    ("jit(_step_impl)/step_xc/xc_gga/jit(g_to_r_gather)/box_fill/gather",
     "step_xc/xc_gga/box_fill"),
    ("jit(local_op_helper)/mul", ""),   # a name inside another is no scope
    ("jit(f)/collective.psum_beta/psum", "collective.psum_beta"),
    ("jit(density_kset)/density_kset/vmap(jit(inner))/density_kset/fft",
     "density_kset"),               # a function's frame is no scope
    ("jit(f)/step_d_matrix/jit(g)/step_d_matrix/step_ledger/step_d_matrix",
     "step_d_matrix/step_ledger/step_d_matrix"),
    ("", ""),
])
def test_scope_path(op_name, path):
    assert ds.scope_path(op_name) == path


def _module(name, computations):
    blob = _hlo_proto(name, computations)
    return ds.hlo_modules(_plane(ds.METADATA_PLANE, hlo={name + "(1)": blob})
                          )[name + "(1)"]


@pytest.mark.parametrize("packed", [True, False])
def test_fusion_with_a_generic_name_takes_its_bodys_scope(packed):
    """A fusion keeps its root's metadata: here the root is a copy the
    compiler made, the work inside is the local operator's."""
    mod = _module("jit_f", {
        1: ("main", [
            _instr("fusion.1", "fusion", "jit(f)/copy", calls=[2], packed=packed),
            _instr("fusion.2", "fusion", "jit(f)/davidson_rr/dot", calls=[3],
                   packed=packed),
            _instr("fusion.3", "fusion", "", calls=[4], packed=packed),
            _instr("while.1", "while", "jit(f)/while", calls=[5, 6],
                   packed=packed),
            _instr("copy.9", "copy", "jit(f)/copy"),
        ]),
        2: ("fused_computation.1", [
            _instr("p0", "parameter"),
            _instr("mul.1", "multiply", "jit(f)/davidson_hpsi/local_op/mul"),
            _instr("add.1", "add", "jit(f)/davidson_hpsi/local_op/add"),
            _instr("dot.1", "dot", "jit(f)/davidson_hpsi/beta_proj/dot"),
            _instr("copy.1", "copy", "jit(f)/copy"),
        ]),
        3: ("fused_computation.2", [
            _instr("mul.2", "multiply", "jit(f)/davidson_hpsi/mul")]),
        4: ("fused_computation.3", [_instr("neg.1", "negate", "jit(f)/neg")]),
        5: ("body", [_instr("fusion.4", "fusion", "", calls=[3])]),
        6: ("cond", [_instr("lt.1", "compare", "jit(f)/while/cond/lt")]),
    })
    got = ds.assign(mod)
    assert got["fusion.1"] == "davidson_hpsi/local_op"   # two of three votes
    assert got["fusion.2"] == "davidson_rr"              # its own name first
    assert got["fusion.3"] == "" and got["copy.9"] == ""
    assert got["while.1"] == ""  # a loop's body is no vote on the loop
    assert got["fusion.4"] == "davidson_hpsi"            # through the call
    assert ds.short_name("fusion.1", mod.instrs["fusion.1"], mod) == \
        "fusion.1 fused_computation.1"


def test_a_module_of_one_stage_gives_its_scope_to_what_carries_none():
    one = _module("jit_density_kset", {1: ("main", [
        _instr("fft.1", "fft", "jit(density_kset)/density_kset/fft"),
        _instr("fusion.1", "fusion", "", calls=[2]),
        _instr("copy.3", "copy", ""),
        _instr("while.1", "while", "", calls=[2])]),
        2: ("body", [_instr("abs.1", "abs",
                            "jit(density_kset)/density_kset/vmap(abs)")])})
    assert ds.assign(one) == {n: "density_kset" for n in one.instrs}
    two = _module("jit_f", {1: ("main", [
        _instr("fft.1", "fft", "jit(f)/density_kset/fft"),
        _instr("dot.1", "dot", "jit(f)/davidson_rr/dot"),
        _instr("copy.3", "copy", "")])})
    assert ds.assign(two)["copy.3"] == ""


def _two_module_xspace(with_second_hlo=True):
    """jit_a and jit_b both have a ``fusion.1``; jit_a's is the local
    operator, jit_b's the Rayleigh-Ritz. One device, jit_a then jit_b."""
    a = _hlo_proto("jit_a", {1: ("main", [
        _instr("fusion.1", "fusion", "jit(a)/davidson_hpsi/local_op/mul"),
        _instr("dot.5", "dot", "jit(a)/davidson_inner/dot"),  # two stages
        _instr("custom-call.2", "custom-call", "jit(a)/misc", target="Sort")])})
    b = _hlo_proto("jit_b", {1: ("main", [
        _instr("fusion.1", "fusion", "jit(b)/davidson_rr/eigh_kernel/dot")])})
    hlo = {"jit_a(11)": a}
    if with_second_hlo:
        hlo["jit_b(12)"] = b
    dev = _plane("/device:TPU:0", [
        ("XLA Modules", [("jit_a(11)", 0, 100), ("jit_b(12)", 200, 50)]),
        ("XLA Ops", [(_tpu_name("fusion.1", "fusion"), 10, 30),
                     (_tpu_name("custom-call.2", "custom-call"), 50, 20),
                     (_tpu_name("fusion.1", "fusion"), 200, 40)]),
        ("Async XLA Ops", [(_tpu_name("fusion.1", "fusion"), 0, 500)]),
    ])
    return (_plane(ds.METADATA_PLANE, hlo=hlo) + dev
            + _plane("/device:CUSTOM:Megascale Trace")
            + _plane("/host:CPU", [("python3", [("scf.iteration", 0, 300)])]))


def test_an_instruction_name_in_two_modules_goes_by_the_module_event():
    t = ds.table(_two_module_xspace(), steps=2)
    assert t["by_scope"] == {
        "davidson_hpsi": {"s": pytest.approx(30e-9), "ops": 1},
        "davidson_hpsi/local_op": {"s": pytest.approx(30e-9), "ops": 1},
        "davidson_rr": {"s": pytest.approx(40e-9), "ops": 1},
        "davidson_rr/eigh_kernel": {"s": pytest.approx(40e-9), "ops": 1},
    }
    assert t["unscoped_s"] == pytest.approx(20e-9)
    assert t["unscoped_top"] == [["custom-call.2 Sort", "jit_a",
                                  pytest.approx(20e-9)]]
    assert t["busy_s"] == pytest.approx(90e-9)
    assert t["by_module"] == {"jit_a": pytest.approx(100e-9),
                              "jit_b": pytest.approx(50e-9)}
    assert t["devices"] == 1 and t["steps"] == 2 and t["num_ops"] == 3
    assert t["scopes_seen"] == ["davidson_hpsi", "davidson_inner",
                                "davidson_rr", "eigh_kernel", "local_op"]


def test_a_module_without_hlo_is_listed_and_its_operations_are_unscoped():
    data = _two_module_xspace(with_second_hlo=False)
    ops, _, _, missing, source = ds.scoped_operations(data, loaded=None)
    assert missing == ["jit_b"] and source == ds.SOURCE_XPLANE
    assert [s.path for s in ops] == ["davidson_hpsi/local_op", "", ""]
    # the second route: a module of that name an executable holds
    held = ds.hlo_modules(_two_module_xspace())["jit_b(12)"]
    other = _module("jit_b", {1: ("main", [_instr("add.7", "add", "x")])})
    seen = []

    def loaded(names):
        seen.append(set(names))
        return {"jit_b": [other, held]}  # the one that ran fusion.1 is taken

    ops, _, _, missing, source = ds.scoped_operations(data, loaded=loaded)
    assert seen == [{"jit_b"}] and missing == []
    assert source == ds.SOURCE_XPLANE + "+" + ds.SOURCE_LOADED
    assert [s.path for s in ops][2] == "davidson_rr/eigh_kernel"
    assert ds.event_scopes(data) == {
        ("/device:TPU:0", "fusion.1", 10.0): "davidson_hpsi/local_op"}
    assert ds.instruction_name(_tpu_name("fusion.1", "fusion")) == "fusion.1"


def test_loaded_modules_reads_what_this_process_holds():
    fn, x = _two_scopes()
    fn(x).block_until_ready()
    (mod,) = ds.loaded_modules({"jit_two_scopes"})["jit_two_scopes"][-1:]
    assert {ds.scope_path(i.op_name) for i in mod.instrs.values()} == \
        {"", "davidson_hpsi", "davidson_rr"}
    assert ds.loaded_modules({"jit_no_such_program"}) == {}


# ---- the union arithmetic ---------------------------------------------------

def _row(dev, path, start, dur, leaf=True, short="op", module="jit_f"):
    return (dev, path, leaf, float(start), float(dur), short, module)


CASES = {
    # the solver's step loop [0, 100), under no scope, holds the reduction's
    # loop [10, 60) and three leaves: the inner loop's gaps are its scope's,
    # the outer loop's are nobody's, and no loop is an operation
    "nested_while": (
        [_row("d0", "", 0, 100, leaf=False, short="while.1"),
         _row("d0", "davidson_rr/eigh_reduce", 10, 50, leaf=False),
         _row("d0", "davidson_rr/eigh_reduce", 10, 20),
         _row("d0", "davidson_rr/eigh_reduce", 35, 20),
         _row("d0", "davidson_rr/eigh_kernel", 60, 30),
         _row("d0", "", 95, 5, short="copy.1")],
        {"busy_s": 100e-9, "unscoped_s": 5e-9, "devices": 1,
         "by_scope": {"davidson_rr": (80e-9, 3),
                      "davidson_rr/eigh_reduce": (50e-9, 2),
                      "davidson_rr/eigh_kernel": (30e-9, 1)}}),
    # two devices, the second ran half as long: means over both
    "two_devices": (
        [_row("d0", "davidson_hpsi/local_op", 0, 40),
         _row("d0", "davidson_hpsi/beta_proj", 40, 20),
         _row("d1", "davidson_hpsi/local_op", 100, 20),
         _row("d1", "", 120, 10, short="all-reduce.1")],
        {"busy_s": 45e-9, "unscoped_s": 5e-9, "devices": 2,
         "by_scope": {"davidson_hpsi": (40e-9, 3),
                      "davidson_hpsi/local_op": (30e-9, 2),
                      "davidson_hpsi/beta_proj": (10e-9, 1)}}),
    # an asynchronous copy [0, 50) under compute [10, 30) of another scope:
    # both scopes hold their whole intervals, busy time counts the overlap
    # once, so the parts add up to more than the whole
    "async_pair_over_compute": (
        [_row("d0", "step_density", 0, 50, short="copy-start.1"),
         _row("d0", "step_xc", 10, 20),
         _row("d0", "step_xc/xc_gga", 60, 10)],
        {"busy_s": 60e-9, "unscoped_s": 0.0, "devices": 1,
         "by_scope": {"step_density": (50e-9, 1), "step_xc": (30e-9, 2),
                      "step_xc/xc_gga": (10e-9, 1)}}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_union_arithmetic(case):
    rows, want = CASES[case]
    got = ds.reduce(rows)
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["unscoped_s"] == pytest.approx(want["unscoped_s"], abs=1e-18)
    assert got["devices"] == want["devices"]
    assert got["by_scope"] == {p: {"s": pytest.approx(s), "ops": n}
                               for p, (s, n) in want["by_scope"].items()}
    assert all(v["s"] <= got["busy_s"] for v in got["by_scope"].values())


def test_unscoped_top_is_ranked_and_cut():
    rows = [_row("d0", "", 10 * i, i + 1, short=f"fusion.{i}") for i in range(12)]
    rows += [_row("d0", "", 500, 4, short="fusion.2")]  # summed by name
    top = ds.reduce(rows, top=3)["unscoped_top"]
    assert [n for n, _, _ in top] == ["fusion.11", "fusion.10", "fusion.9"]
    top = ds.reduce(rows)["unscoped_top"]
    assert len(top) == 10 and ["fusion.2", "jit_f", pytest.approx(7e-9)] in top


# ---- the benchmark's five readers -------------------------------------------

SCOPES_RECORD = {
    "name": "trace.scopes", "busy_s": 2.0, "steps": 5, "unscoped_s": 0.05,
    "by_scope": {"davidson_hpsi": {"s": 1.2, "ops": 10},
                 "davidson_hpsi/local_op": {"s": 0.8, "ops": 6},
                 "davidson_rr": {"s": 0.5, "ops": 4},
                 "step_xc": {"s": 0.4, "ops": 3},
                 "step_xc/xc_gga": {"s": 0.35, "ops": 2}}}
READERS = {"hpsi_device_share": 60.0, "local_op_share": 40.0,
           "rayleigh_ritz_share": 25.0, "xc_gga_ms": 70.0,
           "unscoped_share": 2.5}


def _read_metric(name, record):
    from benchmark.harness import sources

    mdir = os.path.join(ROOT, "benchmark", "layer_metrics")
    with open(os.path.join(mdir, name + ".json")) as f:
        spec = json.load(f)
    assert spec["source"] == "device_trace" and spec["moves"] == "scf_s"
    return sources.read_metric(spec, mdir, name, record)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_hand_written_record(name):
    record = {"trace_job": {"spans": [{"name": "trace.stop"}, SCOPES_RECORD]}}
    assert _read_metric(name, record) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_returns_nothing_without_the_span(name):
    older = {"trace_job": {"spans": [{"name": "trace.capture"},
                                     {"name": "trace.stop"}]}}
    assert _read_metric(name, older) is None
    assert _read_metric(name, {"trace_job": None}) is None
    bare = dict(SCOPES_RECORD, by_scope={}, busy_s=0.0, steps=None)
    assert _read_metric(name, {"trace_job": {"spans": [bare]}}) is None


def test_benchmark_lists_the_five_metrics_in_one_run():
    """PR 36's five, appended together and in this order (entries are only
    ever appended: PR 37's counter follows them, then later PRs')."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        layer = json.load(f)["per_layer"]
    names = [m["name"] for m in layer]
    at = names.index("hpsi_device_share")
    assert names[at:at + 5] == [
        "hpsi_device_share", "local_op_share", "rayleigh_ritz_share",
        "xc_gga_ms", "unscoped_share"]
    assert all(m["source"] == "device_trace" for m in layer[at:at + 5])
    assert names[at + 5] == "davidson_steps_per_scf"


# ---- the registry and the call sites ----------------------------------------

def test_registry_is_the_trees_named_scopes_and_nothing_else():
    sites = {}
    for base, _, files in os.walk(os.path.join(ROOT, "sirius_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(base, name)
                with open(path) as f:
                    text = f.read()
                assert "named_call" not in text, path  # metadata only
                for m in re.finditer(r"named_scope\(\s*([^)]*)\)", text):
                    lit = re.fullmatch(r'"([\w.\-]+)"', m.group(1).strip())
                    assert lit, f"{path}: named_scope({m.group(1)}) is no literal"
                    sites.setdefault(lit.group(1), []).append(path)
    assert set(sites) == set(ds.SCOPES)
    assert len(set(ds.SCOPES)) == len(ds.SCOPES)


def test_the_reader_is_not_imported_with_the_capture():
    code = ("import sys; import sirius_tpu.obs.trace, sirius_tpu.obs.timeline;"
            "assert 'sirius_tpu.obs.device_scopes' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   timeout=120)


# ---- metadata only ----------------------------------------------------------

def _strip_loc(txt):
    return re.sub(r"loc\(.*?\)|#loc.*", "", txt)


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), tree)


LDA = ("XC_LDA_X", "XC_LDA_C_PZ")
PBE = ("XC_GGA_X_PBE", "XC_GGA_C_PBE")


def _tiny_ctx(ngridk, xc=LDA, symmetry=False):
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.serve.scheduler import build_job_context

    return build_job_context(load_config({
        "parameters": {"gk_cutoff": 3.0, "pw_cutoff": 7.0,
                       "ngridk": list(ngridk), "num_bands": 8,
                       "use_symmetry": symmetry, "precision_wf": "fp32",
                       "xc_functionals": list(xc)},
        "synthetic": {"ultrasoft": True}}), ".")


def _lower_kset():
    from sirius_tpu.parallel.batched import davidson_kset, make_hkset_params

    ctx = _tiny_ctx((2, 2, 3))  # generic k: the complex subspace
    nk, ngk, nb = ctx.gkvec.num_kpoints, ctx.gkvec.ngk_max, ctx.num_bands
    ps = _sds(make_hkset_params(ctx, np.zeros(ctx.fft_coarse.dims),
                                dtype=jnp.complex64))
    psi = jax.ShapeDtypeStruct((nk, 1, nb, ngk), np.float32)
    tol = jax.ShapeDtypeStruct((), np.float32)
    # for the TPU: the branch with the reduction's two scopes in it
    return lambda: davidson_kset.trace(
        ps, psi, psi, num_steps=3, res_tol=tol).lower(
            lowering_platforms=("tpu",))


def _lower_gamma():
    from sirius_tpu.ops.gamma import (
        build_gamma_map, davidson_gamma, make_gamma_params,
    )

    ctx = _tiny_ctx((1, 1, 1))
    gm = build_gamma_map(np.asarray(ctx.gkvec.millers[0]),
                         np.asarray(ctx.gkvec.mask[0]))
    gp = _sds(make_gamma_params(ctx, np.zeros(ctx.fft_coarse.dims), gm,
                                rdtype=jnp.float32))
    nb, ngk = ctx.num_bands, ctx.gkvec.ngk_max
    x0 = jax.ShapeDtypeStruct((nb, ngk), np.float32)
    diag = jax.ShapeDtypeStruct((ngk,), np.float32)
    tol = jax.ShapeDtypeStruct((), np.float32)
    return lambda: davidson_gamma.lower(gp, x0, diag, diag, num_steps=3,
                                        res_tol=tol)


def _lower_step(xc=PBE, symmetry=False):
    from types import SimpleNamespace

    from sirius_tpu.dft.fused import FusedScf
    from sirius_tpu.dft.mixer import Mixer
    from sirius_tpu.dft.xc import XCFunctional

    ctx = _tiny_ctx((1, 1, 1), xc=xc, symmetry=symmetry)
    cfg = ctx.cfg
    mixer = Mixer(cfg.mixer, ctx.gvec.glen2, num_components=1,
                  omega=ctx.unit_cell.omega)

    def lower():  # a FusedScf of its own: its jit is made at construction
        fused = FusedScf(ctx, XCFunctional(cfg.parameters.xc_functionals),
                         mixer, False, symmetry, wf_dtype=jnp.complex64)
        nk, ngk, nb = ctx.gkvec.num_kpoints, ctx.gkvec.ngk_max, ctx.num_bands
        nbeta = ctx.beta.num_beta_total
        pot0 = SimpleNamespace(veff_g=np.zeros(fused.ng, np.complex128),
                               bz_g=None)
        carry = fused.init_carry(np.zeros(fused.nx, np.complex128), pot0)

        def f(*shape):
            return jax.ShapeDtypeStruct(shape, np.float32)

        return fused._step.lower(
            _sds(fused.tables), _sds(carry), f(1, *fused.dims_coarse),
            f(1, nbeta, nbeta), f(1, nbeta, nbeta), f(nk, 1, nb),
            f(nk, 1, nb), f(), f(nk, 1, nb, ngk), f(nk, 1, nb, ngk))

    return lower


@pytest.mark.parametrize("program, scopes", [
    ("kset", ["davidson_ortho", "davidson_residual", "davidson_hpsi",
              "local_op", "beta_proj", "davidson_inner", "davidson_rr",
              "eigh_reduce", "eigh_kernel", "davidson_rotate"]),
    ("gamma", ["davidson_hpsi", "local_op", "beta_proj", "eigh_kernel"]),
    ("step", ["step_density", "step_mixing", "step_hartree", "step_xc",
              "xc_gga", "box_fill", "step_vloc", "step_d_matrix",
              "step_ledger"]),
    ("step_lda", ["step_density", "step_mixing", "step_hartree", "step_xc",
                  "box_fill", "step_vloc", "step_d_matrix", "step_ledger"]),
    # PR 38: the symmetrisers' scopes, in a step that runs them
    ("step_sym", ["step_density", "sym_dm", "sym_pw", "step_vloc",
                  "step_ledger"]),
])
def test_named_scopes_are_metadata_only(program, scopes, monkeypatch):
    """The lowered program with locations stripped is the same with
    jax.named_scope in force and with it patched to a null context, and the
    names are in the text that keeps them."""
    from sirius_tpu import runtime

    lower = {"kset": _lower_kset, "gamma": _lower_gamma, "step": _lower_step,
             "step_lda": lambda: _lower_step(LDA),
             "step_sym": lambda: _lower_step(LDA, symmetry=True)}[program]()
    with runtime.scf_scope():
        jax.clear_caches()
        named = lower()
        with_loc = named.as_text(debug_info=True)
        for s in scopes:
            assert re.search(rf'"[^"]*\b{s}\b[^"]*"', with_loc), s
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        jax.clear_caches()
        bare = lower()
        assert not any(s in bare.as_text(debug_info=True) for s in scopes)
        assert _strip_loc(named.as_text()) == _strip_loc(bare.as_text())
    monkeypatch.undo()
    jax.clear_caches()


if __name__ == "__main__":  # record tests/data/two_scopes.xplane.pb again
    import glob
    import shutil
    import tempfile

    jax.config.update("jax_platforms", "cpu")
    fn, x = _two_scopes()
    fn(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    fn(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "plugins/profile/*/*.xplane.pb"))
    shutil.copy(path, RECORDED)
    shutil.rmtree(tmp)
    print(RECORDED, os.path.getsize(RECORDED))
