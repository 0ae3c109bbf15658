"""A context keeps what does not depend on where the atoms are
(sirius_tpu/context.py `_TABLES`): the G-vector sets, the k-spheres and each
species' tables on them are built once a lattice, and a later build at that
lattice, cutoffs, k-set and species builds only the position stage. A hit's
context is the cold build's array by array; a new geometry hits, a changed
value of any key misses; what is shared is read-only; the memo is bounded
and builds a key once."""

import collections
import copy
import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

import sirius_tpu.context as cm
from sirius_tpu.config.schema import load_config
from sirius_tpu.core.sht import lm_index, ylm_real
from sirius_tpu.dft import radial_tables
from sirius_tpu.obs import spans
from sirius_tpu.ops.beta import beta_radial_table
from sirius_tpu.serve.scheduler import build_job_context
from sirius_tpu.testing import context_of_cell, synthetic_cell

_PARAMS = {
    "gk_cutoff": 3.0, "pw_cutoff": 7.0, "ngridk": [2, 2, 2],
    "use_symmetry": False, "num_bands": 8, "smearing_width": 0.025,
    "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"], "num_dft_iter": 40,
    "precision_wf": "fp64", "density_tol": 1e-8, "energy_tol": 1e-9,
}

# rehearsal-size decks of the benchmark's kinds
DECKS = {
    # silicon ultrasoft on a k-mesh, spheres padded to a quantum
    "kmesh": {"parameters": _PARAMS, "control": {"ngk_pad_quantum": 16},
              "synthetic": {"ultrasoft": True}},
    # a 16-atom supercell at Gamma
    "gamma": {"parameters": dict(_PARAMS, ngridk=[1, 1, 1], num_bands=40),
              "synthetic": {"ultrasoft": True, "supercell": 2}},
    # the d-shell species, polarised
    "dshell": {"parameters": dict(_PARAMS, num_mag_dims=1, num_bands=16,
                                  smearing_width=0.005),
               "control": {"ngk_pad_quantum": 16},
               "synthetic": {"ultrasoft": True, "species": "dshell",
                             "moments": [0.0, 0.0, 2.0]}},
    # the symmetric deck: the group's wedge of the mesh, weighted
    "sym": {"parameters": dict(_PARAMS, use_symmetry=True, ngridk=[4, 4, 4]),
            "synthetic": {"ultrasoft": True}},
}


def _positions(g, supercell=1):
    """Geometry g: every atom but the first displaced (g = 0: none)."""
    pos = synthetic_cell("si", supercell=supercell).positions.copy()
    if g:
        rng = np.random.default_rng(4700 + g)
        pos[1:] += rng.uniform(-0.004, 0.004, (len(pos) - 1, 3))
    return pos


def _deck(name, g=0, **over):
    """Deck ``name`` at geometry ``g``; ``over``: section.key=value as
    ``section__key``."""
    deck = copy.deepcopy(DECKS[name])
    syn = deck["synthetic"]
    if g and name != "sym":
        n = syn.pop("supercell", 1)
        syn["a"] = 10.26 * n
        syn["positions"] = _positions(g, n).tolist()
    for key, value in over.items():
        section, field = key.split("__")
        deck.setdefault(section, {})[field] = value
    return deck


def _build(deck):
    """(context, {span name: record}) of one build."""
    with spans.capture() as cap:
        ctx = build_job_context(load_config(deck), ".")
    recs = {name: cap.by_name(name)[0] for name in (
        "context.lattice_tables", "context.species_tables",
        "context.positions", "serve.context_build")}
    return ctx, recs


def _arrays(obj, path="ctx", out=None):
    """Every array and number of a context, by path."""
    out = {} if out is None else out
    if isinstance(obj, np.ndarray):
        out[path] = obj
    elif dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        if type(obj).__name__ in ("Config", "AtomType", "CrystalSymmetry"):
            return out
        for f in dataclasses.fields(obj):
            if f.name != "tables_reused":
                _arrays(getattr(obj, f.name), f"{path}.{f.name}", out)
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            _arrays(v, f"{path}[{i}]", out)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[path] = np.asarray(obj)
    return out


def _assert_same_context(a, b):
    fa, fb = _arrays(a), _arrays(b)
    assert fa.keys() == fb.keys() and len(fa) > 40
    for path in fa:
        assert fa[path].dtype == fb[path].dtype, path
        assert np.array_equal(fa[path], fb[path]), path


def _parent_beta_gk(uc, gkvec, qmax):
    """BetaProjectors.build's table as the program before the memo wrote
    it: every factor inside the loop over atoms, in that order."""
    nk, ngk = gkvec.num_kpoints, gkvec.ngk_max
    lmax = max(t.lmax_beta for t in uc.atom_types)
    tables = [beta_radial_table(t, qmax) for t in uc.atom_types]
    counts = [uc.atom_types[it].num_beta_lm for it in uc.type_of_atom]
    beta_gk = np.zeros((nk, int(np.sum(counts)), ngk), dtype=np.complex128)
    gk = gkvec.gkcart
    qlen = np.linalg.norm(gk, axis=-1)
    rhat = gk / np.maximum(qlen, 1e-30)[..., None]
    rhat = np.where(qlen[..., None] > 1e-30, rhat, np.array([0.0, 0, 1.0]))
    rlm = ylm_real(lmax, rhat)
    minus_i_pow = [(-1j) ** l for l in range(lmax + 1)]
    pref = 4.0 * np.pi / np.sqrt(uc.omega)
    off = 0
    for ia in range(uc.num_atoms):
        it = uc.type_of_atom[ia]
        t = uc.atom_types[it]
        ri = tables[it](qlen.reshape(-1)).reshape(t.num_beta, nk, ngk)
        mk = gkvec.millers + gkvec.kpoints[:, None, :]
        phase = np.exp(-2j * np.pi * (mk @ uc.positions[ia]))
        idxrf, ls, ms = t.beta_lm_table()
        for xi in range(t.num_beta_lm):
            l, m, ir = int(ls[xi]), int(ms[xi]), int(idxrf[xi])
            beta_gk[:, off + xi, :] = (
                pref * minus_i_pow[l] * rlm[..., lm_index(l, m)] * ri[ir]
                * phase * gkvec.mask)
        off += t.num_beta_lm
    return beta_gk


@pytest.fixture(autouse=True)
def fresh_memo():
    """A process that has built no context yet, as far as the memo goes."""
    cm._TABLES.clear()
    yield cm._TABLES
    cm._TABLES.clear()


@pytest.mark.parametrize("name", sorted(DECKS))
def test_hit_equals_cold_build(name):
    types = 1
    cold, rc = _build(_deck(name, g=1))
    assert cold.tables_reused == 0
    assert not rc["context.lattice_tables"]["hit"]
    assert not rc["context.species_tables"]["hit"]
    _build(_deck(name, g=2))  # another geometry in between
    hit, rh = _build(_deck(name, g=1))
    assert hit.tables_reused == 1 + types
    assert rh["context.lattice_tables"]["hit"]
    assert rh["context.species_tables"]["hit"]
    assert rh["context.species_tables"]["hits"] == types
    assert rh["context.lattice_tables"]["bytes"] > 0
    _assert_same_context(cold, hit)
    # the tables are the same objects, the position stage's are not
    assert hit.gvec is cold.gvec and hit.gkvec is cold.gkvec
    assert hit.aug.per_type[0] is cold.aug.per_type[0]
    assert hit.beta.beta_gk is not cold.beta.beta_gk
    # and the projectors are the parent program's, to the bit
    assert np.array_equal(
        hit.beta.beta_gk,
        _parent_beta_gk(hit.unit_cell, hit.gkvec,
                        hit.cfg.parameters.gk_cutoff + 1e-9))
    if name in ("kmesh", "dshell"):
        assert hit.gkvec.ngk_max % 16 == 0
    if name == "sym":
        assert hit.symmetry.num_ops == 48
        assert hit.gkvec.num_kpoints < 64


@pytest.fixture(scope="module")
def jobs():
    """Geometry 1 of the k-mesh deck run twice in f64: from a cold context,
    and from one whose tables an earlier build at geometry 0 left."""
    from sirius_tpu.dft.scf import run_scf

    def run(ctx):
        ctx.cfg.control.telemetry = True
        return run_scf(ctx.cfg, ctx=ctx, devices=jax.devices()[:1])

    cm._TABLES.clear()
    cold, _ = _build(_deck("kmesh", g=1))
    r_cold = run(cold)
    cm._TABLES.clear()
    _build(_deck("kmesh", g=0))
    keys = list(cm._TABLES._entries)
    hit, recs = _build(_deck("kmesh", g=1))  # never seen on this memo
    r_hit = run(hit)
    return {"cold": r_cold, "hit": r_hit, "recs": recs, "keys": keys,
            "keys_after": list(cm._TABLES._entries)}


def test_energy_to_the_bit_from_cold_and_hit_context(jobs):
    cold, hit = jobs["cold"], jobs["hit"]
    assert cold["converged"] and hit["converged"]
    assert cold["energy"]["total"] == hit["energy"]["total"]
    assert cold["num_scf_iterations"] == hit["num_scf_iterations"]


def test_new_geometry_on_seen_lattice_books_the_reuse(jobs):
    assert jobs["cold"]["counters"]["context_tables_reused"] == 0
    assert jobs["hit"]["counters"]["context_tables_reused"] == 2
    recs = jobs["recs"]
    assert recs["context.lattice_tables"]["hit"] is True
    assert recs["context.species_tables"]["hit"] is True
    assert recs["context.species_tables"]["types"] == 1
    # the three stages are children of the build's span, and a geometry
    # adds nothing to the memo
    for name in ("context.lattice_tables", "context.species_tables",
                 "context.positions"):
        assert recs[name]["parent_id"] == recs["serve.context_build"]["span_id"]
    assert jobs["keys_after"] == jobs["keys"] and len(jobs["keys"]) == 2


def test_memo_holds_nothing_of_a_geometry():
    """Its values are the two table classes, whose fields name no position,
    moment, structure factor, energy or symmetry operation."""
    _build(_deck("dshell", g=1))
    values = [v for v, _ in cm._TABLES._entries.values()]
    assert sorted(type(v).__name__ for v in values) == [
        "_LatticeTables", "_SpeciesTables"]
    assert {f.name for f in dataclasses.fields(cm._LatticeTables)} == {
        "gvec", "gvec_coarse", "fft_coarse", "coarse_to_fine",
        "gkvec", "gk_len", "gk_hat", "qshell"}
    assert {f.name for f in dataclasses.fields(cm._SpeciesTables)} == {
        "beta_form", "aug", "ff_shells"}


def test_new_geometry_costs_what_a_repeated_one_costs():
    _build(_deck("gamma", g=0))

    def seconds(g):
        t0 = time.perf_counter()
        ctx, _ = _build(_deck("gamma", g=g))
        assert ctx.tables_reused == 2
        return time.perf_counter() - t0

    repeated = min(seconds(1) for _ in range(3))
    fresh = min(seconds(g) for g in (2, 3, 4))
    assert fresh < 2.0 * repeated + 0.05


@pytest.mark.parametrize("over, lattice_hit", [
    ({"synthetic__a": 10.30}, False),
    ({"parameters__pw_cutoff": 7.5}, False),
    ({"parameters__gk_cutoff": 3.2}, False),
    ({"parameters__ngridk": [2, 2, 1]}, False),
    ({"control__ngk_pad_quantum": 32}, False),
    ({"settings__pseudo_grid_cutoff": 8.0}, True),
], ids=["lattice_constant", "pw_cutoff", "gk_cutoff", "ngridk",
        "ngk_pad_quantum", "pseudo_grid_cutoff"])
def test_a_changed_key_value_misses(over, lattice_hit):
    _build(_deck("kmesh"))
    ctx, recs = _build(_deck("kmesh", **over))
    assert recs["context.lattice_tables"]["hit"] is lattice_hit
    assert recs["context.species_tables"]["hit"] is False
    assert recs["context.species_tables"]["hits"] == 0
    assert ctx.tables_reused == int(lattice_hit)
    # and the base deck's entries are still what a third build finds
    again, _ = _build(_deck("kmesh"))
    assert again.tables_reused == 2


def _touch(t, what):
    """One changed sample of one radial function of an atom type (the
    silicon species has no core charge: it gets one of zeros)."""
    if what == "rho_core":
        assert t.rho_core is None
        t.rho_core = np.zeros_like(t.r)
        return
    arr = {"rbeta": t.beta[0].rbeta, "vloc": t.vloc,
           "rho_total": t.rho_total, "qr": t.augmentation[0].qr}[what]
    arr[len(arr) // 3] *= 1.0 + 1e-12


@pytest.mark.parametrize(
    "what", ["rbeta", "vloc", "rho_core", "rho_total", "qr", "d_ion"])
def test_same_label_different_content_does_not_hit(what):
    cfg = load_config(_deck("kmesh"))

    def build(touch):
        uc = synthetic_cell("si", ultrasoft=True)
        if touch and what == "d_ion":
            uc.atom_types[0].d_ion[0, 0] += 1e-9
        elif touch:
            _touch(uc.atom_types[0], what)
        with spans.capture() as cap:
            ctx = context_of_cell(cfg, uc)
        return ctx, cap.by_name("context.species_tables")[0]

    first, _ = build(False)
    same, rec = build(False)  # a fresh AtomType of the same content
    assert rec["hit"] and same.unit_cell.atom_types[0] is not \
        first.unit_cell.atom_types[0]
    other, rec = build(True)
    assert other.unit_cell.atom_types[0].label == \
        first.unit_cell.atom_types[0].label
    assert rec["hit"] is False and other.tables_reused == 1


def test_host_callback_is_called_on_every_build():
    """A registered radial-integral hook stands in for the species stage's
    form factor on every build, hit or not."""
    calls = []
    t0 = synthetic_cell("si", ultrasoft=True).atom_types[0]

    def hook(iat, q):
        calls.append(iat)
        return radial_tables.vloc_form_factor(t0, q, rc=10.0)

    plain, _ = _build(_deck("kmesh"))
    radial_tables.HOST_CALLBACKS["vloc_ri"] = hook
    try:
        a, ra = _build(_deck("kmesh"))
        b, rb = _build(_deck("kmesh", g=1))
    finally:
        del radial_tables.HOST_CALLBACKS["vloc_ri"]
    assert calls == [1, 1]
    # the hooked species entry is its own (it holds no vloc form factor)
    assert ra["context.lattice_tables"]["hit"]
    assert not ra["context.species_tables"]["hit"]
    assert rb["context.species_tables"]["hit"]
    assert np.array_equal(a.vloc_g, plain.vloc_g)
    after, rec = _build(_deck("kmesh"))
    assert rec["context.species_tables"]["hit"] and calls == [1, 1]
    assert np.array_equal(after.vloc_g, plain.vloc_g)


def test_writing_into_a_shared_table_raises():
    ctx, _ = _build(_deck("kmesh"))
    shared = [ctx.gvec.millers, ctx.gvec.gcart, ctx.gvec.fft_index,
              ctx.gvec_coarse.glen2, ctx.coarse_to_fine, ctx.gkvec.mask,
              ctx.gkvec.gkcart, ctx.gkvec.kpoints, ctx.gkvec.weights,
              ctx.aug.per_type[0].q_pw, ctx.aug.per_type[0].q_mtrx]
    for arr in shared:
        with pytest.raises(ValueError, match="read-only"):
            arr[...] = 0
    # what reads the positions is the context's own
    for arr in (ctx.beta.beta_gk, ctx.beta.dion, ctx.beta.qmat, ctx.vloc_g,
                ctx.rho_core_g, ctx.rho_atomic_g, ctx.kweights,
                ctx.unit_cell.lattice, ctx.unit_cell.positions):
        assert arr.flags.writeable


def test_bounds_evict_the_oldest():
    memo = cm._TableMemo(max_entries=3, max_bytes=1000)
    block = lambda n: (lambda: np.zeros(n, dtype=np.uint8))  # noqa: E731
    for key in "abc":
        assert memo.get(key, block(100))[1:] == (100, False)
    assert memo.get("a", block(100))[2] is True  # a is now the newest
    memo.get("d", block(100))  # the entry bound: b, the oldest, goes
    assert list(memo._entries) == ["c", "a", "d"]
    memo.get("e", block(850))  # the byte bound: c and a go
    assert list(memo._entries) == ["d", "e"] and memo.bytes() == 950
    # larger than the byte bound alone: handed out frozen, not kept, and
    # nothing goes to make room for it
    value, nbytes, hit = memo.get("f", block(2000))
    assert (nbytes, hit) == (2000, False) and not value.flags.writeable
    assert list(memo._entries) == ["d", "e"] and memo.bytes() == 950
    assert memo.get("f", block(2000))[2] is False  # built again


def test_two_threads_build_one_cold_key_once():
    memo = cm._TableMemo(max_entries=4, max_bytes=1 << 20)
    started, built, out = threading.Event(), [], {}

    def build():
        built.append(threading.current_thread().name)
        started.set()
        time.sleep(0.2)
        return np.arange(8)

    def ask(name):
        out[name] = memo.get("k", build)

    first = threading.Thread(target=ask, args=("first",), name="first")
    second = threading.Thread(target=ask, args=("second",), name="second")
    first.start()
    assert started.wait(5.0)
    second.start()
    first.join(5.0)
    second.join(5.0)
    assert built == ["first"]
    assert out["first"][0] is out["second"][0]
    assert (out["first"][2], out["second"][2]) == (False, True)

    def fail():
        raise RuntimeError("no table")

    with pytest.raises(RuntimeError):
        memo.get("bad", fail)
    assert memo.get("bad", lambda: np.ones(2))[2] is False  # not left building


def test_many_threads_build_each_key_once():
    """More threads than cores on a handful of cold keys, the interpreter
    switching every few microseconds: a lost update would build a key twice
    or hand two threads different tables."""
    import sys

    memo = cm._TableMemo(max_entries=16, max_bytes=1 << 20)
    keys = [f"k{i}" for i in range(5)]
    built = collections.Counter()
    got = collections.defaultdict(list)
    lock = threading.Lock()

    def build(key):
        with lock:
            built[key] += 1
        time.sleep(0.002)
        return np.full(4, int(key[1:]))

    def worker(seed):
        order = np.random.default_rng(seed).permutation(len(keys))
        for _ in range(20):
            for i in order:
                value, _, _ = memo.get(keys[i], lambda k=keys[i]: build(k))
                with lock:
                    got[keys[i]].append(id(value))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(s,))
                   for s in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert dict(built) == {k: 1 for k in keys}
    assert all(len(set(ids)) == 1 and len(ids) == 24 * 20
               for ids in got.values())


def test_digest_tool_tells_a_geometry_from_a_lattice_table():
    """tools/context_digest.py, which holds a tree's contexts to another
    tree's: its walk gives the shared tables of two geometries one digest
    each and the position stage's arrays two, and a tree against itself
    differs nowhere."""
    import importlib.util
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(root, "tools", "context_digest.py")
    spec = importlib.util.spec_from_file_location("context_digest", tool)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    a = mod.digests(_build(_deck("kmesh", g=1))[0])
    b = mod.digests(_build(_deck("kmesh", g=2))[0])
    assert a.keys() == b.keys() and len(a) > 40
    differ = {p.split(".")[1].split("[")[0] for p in a if a[p] != b[p]}
    # (the silicon species has no core density: rho_core_g is zero in both)
    assert differ == {"unit_cell", "beta", "vloc_g", "rho_atomic_g",
                      "e_ewald"}
    out = subprocess.run(
        [sys.executable, tool, "--against", root, "--block", "rehearse",
         "si2-k444-us"], capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"builds": 4' in out.stdout and '"differ": 0' in out.stdout
