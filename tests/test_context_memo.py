"""A context keeps what does not depend on where the atoms are
(sirius_tpu/context.py `_TABLES`): the G-vector sets, the k-spheres and each
species' tables on them are built once a lattice, and a later build at that
lattice, cutoffs, k-set and species builds only the position stage. A hit's
context is the cold build's array by array; a new geometry hits, a changed
value of any key misses; what is shared is read-only; the memo is bounded
and builds a key once. A job builds ONE table of its atoms' phases on the
fine G set, and every reader there takes it; the LCAO start's form factors
are a species' tables, its random rows and the Gamma sphere's pairing the
lattice's: all to the bit what each reader built for itself before."""

import collections
import copy
import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

import sirius_tpu.context as cm
import sirius_tpu.core.gvec as gvm
from sirius_tpu.config.schema import load_config
from sirius_tpu.core.radial import RadialIntegralTable
from sirius_tpu.core.sht import lm_index, ylm_real
from sirius_tpu.dft import radial_tables
from sirius_tpu.dft.ewald import ewald_energy
from sirius_tpu.obs import spans
from sirius_tpu.ops import augmentation as augm
from sirius_tpu.ops.beta import beta_radial_table
from sirius_tpu.ops.gamma import build_gamma_map
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
        "gkvec", "gk_len", "gk_hat", "qshell", "deferred"}
    assert {f.name for f in dataclasses.fields(cm._SpeciesTables)} == {
        "beta_form", "ao_form", "aug", "ff_shells"}
    # nor does what a job asks of the lattice's entry later, and the atoms'
    # phases are nowhere in it
    ctx, _ = _build(_deck("gamma", g=1))
    ctx.gamma_map()
    ctx.random_rows(3)
    for v in (v for v, _ in cm._TABLES._entries.values()):
        if isinstance(v, cm._LatticeTables):
            assert set(v.deferred._kept) <= {"gamma_map", "random_rows"}
    held = [a for v, _ in cm._TABLES._entries.values()
            for a in _arrays(v, "t").values()]
    assert not any(np.shares_memory(a, ctx.phases.table) for a in held)


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
           "rho_total": t.rho_total, "qr": t.augmentation[0].qr,
           "chi": t.atomic_wfs[1].chi}[what]
    if what == "chi":  # a move the LCAO form's integrals do not round away
        arr[len(arr) * 9 // 10] *= 1.0 + 1e-6
    else:
        arr[len(arr) // 3] *= 1.0 + 1e-12


@pytest.mark.parametrize(
    "what", ["rbeta", "vloc", "rho_core", "rho_total", "qr", "d_ion", "chi"])
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
    # two types that differ in one sample share no LCAO form either
    assert other.ao_forms[0] is not first.ao_forms[0]
    assert same.ao_forms[0] is first.ao_forms[0]
    assert np.array_equal(other.ao_forms[0], first.ao_forms[0]) == (
        what != "chi")


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
    gm = ctx.gamma_map()
    shared += [ctx.ao_forms[0], ctx.random_rows(2), gm.rep, gm.slot_re,
               gm.scale, ctx.phases.table]
    for arr in shared:
        with pytest.raises(ValueError, match="read-only"):
            arr[...] = 0
    # what reads the positions is the context's own
    for arr in (ctx.beta.beta_gk, ctx.beta.dion, ctx.beta.qmat, ctx.vloc_g,
                ctx.rho_core_g, ctx.rho_atomic_g, ctx.kweights,
                ctx.unit_cell.lattice, ctx.unit_cell.positions):
        assert arr.flags.writeable


# ---------------------------------------------------------------------------
# One atom-phase table a job, and the LCAO start's tables in the memo
# ---------------------------------------------------------------------------


def _two_species_cell(lone):
    """Silicon and the d-shell species in the fcc cell: two atoms of each,
    or (``lone``) two silicon atoms and ONE d-shell atom, whose column numpy
    multiplies through another BLAS routine than a block of columns."""
    import sirius_tpu.crystal.unit_cell as ucm
    from sirius_tpu.testing import (
        synthetic_dshell_type, synthetic_silicon_type)

    pos = np.array([[0.0, 0, 0], [0.25, 0.25, 0.25], [0.52, 0.49, 0.013],
                    [0.74, 0.77, 0.26]])[:3 if lone else 4]
    types = np.array([0, 0, 1, 1][:len(pos)], dtype=np.int32)
    return ucm.UnitCell(
        lattice=10.26 / 2 * np.array([[0.0, 1, 1], [1, 0, 1], [1, 1, 0]]),
        atom_types=[synthetic_silicon_type(), synthetic_dshell_type()],
        type_of_atom=types, positions=pos, moments=np.zeros((len(pos), 3)))


def _context(name):
    """A context of one of this section's cells, with 12 bands on the
    k-mesh decks so that the LCAO start has random rows."""
    if name in ("two_species", "lone_atom"):
        cfg = load_config(_deck("kmesh", parameters__num_bands=40))
        return context_of_cell(cfg, _two_species_cell(name == "lone_atom"))
    over = {} if name == "gamma" else {"parameters__num_bands": 12}
    return _build(_deck(name, g=1, **over))[0]


CELLS = ["kmesh", "gamma", "dshell", "two_species", "lone_atom"]


def _parent_d_operator(uc, gvec, aug, veff_g, beta):
    """ops/augmentation.d_operator's augmentation term as the program
    before the shared table wrote it (a phase_factors call a type)."""
    out = {}
    for it, at in enumerate(aug.per_type):
        atoms = uc.atoms_of_type(it)
        ph = gvm.phase_factors(gvec.millers, uc.positions[atoms], -1.0)
        vq = uc.omega * np.real(at.q_pw @ (np.conj(veff_g)[:, None] * ph))
        for j, ia in enumerate(atoms):
            out[ia] = vq[:, j]
    d = beta.dion.copy()
    for ia, off, nbf in beta.atom_blocks(uc):
        at = aug.per_type[uc.type_of_atom[ia]]
        block = np.zeros((nbf, nbf))
        block[at.xi1, at.xi2] = out[ia]
        block[at.xi2, at.xi1] = out[ia]
        d[off:off + nbf, off:off + nbf] += block
    return d


def _parent_rho_aug_g(uc, gvec, aug, dm):
    out = np.zeros(gvec.num_gvec, dtype=np.complex128)
    for it, at in enumerate(aug.per_type):
        atoms = uc.atoms_of_type(it)
        w = np.where(at.xi1 == at.xi2, 1.0, 2.0)
        dmp = np.stack([w * np.real(dm[ia][at.xi1, at.xi2]) for ia in atoms])
        ph = gvm.phase_factors(gvec.millers, uc.positions[atoms], -1.0)
        out += np.einsum("ga,aq,qg->g", ph, dmp, at.q_pw, optimize=True)
    return out


def _reader_pair(reader, ctx):
    """(from the context's table, the reader's own phase_factors call of the
    program before the table) as lists of arrays."""
    uc, gv, ph = ctx.unit_cell, ctx.gvec, ctx.phases
    rng = np.random.default_rng(48)
    if reader == "structure_factors":
        plus = gvm.phase_factors(gv.millers, uc.positions)
        want = [plus[:, uc.type_of_atom == it].sum(axis=1)
                for it in range(len(uc.atom_types))]
        return list(radial_tables.structure_factors(uc, gv, ph)), want
    if reader == "ewald_energy":
        z = np.asarray([uc.atom_types[t].zn for t in uc.type_of_atom])
        args = (uc.lattice, uc.positions, z, gv.gcart, gv.millers,
                ctx.cfg.parameters.pw_cutoff)
        # |S(G)|^2 of the program before: the +i table without G = 0
        s = gvm.phase_factors(gv.millers[1:], uc.positions) @ z
        got = ph.minus(gv.millers, uc.positions)[1:] @ z
        return ([np.abs(got) ** 2, np.asarray(ewald_energy(*args, phases=ph)),
                 np.asarray(ctx.e_ewald)],
                [np.abs(s) ** 2, np.asarray(ewald_energy(*args)),
                 np.asarray(ewald_energy(*args))])
    if reader == "aug_device_tables":
        got = augm.build_aug_device_tables(uc, gv, ctx.aug, ctx.beta, ph)
        want = [gvm.phase_factors(gv.millers, uc.positions[uc.atoms_of_type(it)],
                                  -1.0) for it in range(len(uc.atom_types))]
        return ([t[k] for t in got for k in ("ph_re", "ph_im")],
                [f(w) for w in want for f in (np.real, np.imag)])
    if reader == "d_operator":
        veff = (rng.standard_normal(gv.num_gvec)
                + 1j * rng.standard_normal(gv.num_gvec))
        return ([augm.d_operator(uc, gv, ctx.aug, veff, ctx.beta, phases=ph),
                 augm.d_operator(uc, gv, ctx.aug, veff, ctx.beta)],
                [_parent_d_operator(uc, gv, ctx.aug, veff, ctx.beta)] * 2)
    if reader == "forces_ewald":
        from sirius_tpu.dft.forces import forces_ewald

        # without a table the function builds e^{-iG.r} for itself; the
        # program before read np.exp(+2 pi i m.x) without G = 0
        plus = np.exp(2j * np.pi * (gv.millers[1:] @ uc.positions.T))
        return ([forces_ewald(ctx), np.conj(ph.minus(gv.millers, uc.positions)[1:])],
                [forces_ewald(dataclasses.replace(ctx, phases=None)), plus])
    assert reader == "rho_aug_g"
    dm = []
    for _, _, nbf in ctx.beta.atom_blocks(uc):
        a = rng.standard_normal((nbf, nbf)) + 1j * rng.standard_normal((nbf, nbf))
        dm.append(a + a.conj().T)
    return ([augm.rho_aug_g(uc, gv, ctx.aug, dm, phases=ph),
             augm.rho_aug_g(uc, gv, ctx.aug, dm)],
            [_parent_rho_aug_g(uc, gv, ctx.aug, dm)] * 2)


@pytest.fixture(scope="module")
def cells():
    cm._TABLES.clear()
    return {name: _context(name) for name in CELLS}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("reader", [
    "structure_factors", "ewald_energy", "aug_device_tables", "d_operator",
    "rho_aug_g", "forces_ewald"])
def test_reader_on_the_shared_table_equals_its_own_call(cells, reader, cell):
    ctx = cells[cell]
    reads = ctx.phases.reads
    got, want = _reader_pair(reader, ctx)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert ctx.phases.reads > reads


def test_the_table_is_the_conjugate_of_the_plus_table(cells):
    """One sign is kept; the other is its conjugate, to the bit."""
    ctx = cells["gamma"]
    plus = gvm.phase_factors(ctx.gvec.millers, ctx.unit_cell.positions)
    assert np.array_equal(np.conj(ctx.phases.table), plus)
    assert not ctx.phases.table.flags.writeable


def test_a_lone_atom_of_a_type_keeps_its_own_call(cells):
    """A [ng, 3] by [3, 1] product goes through another BLAS routine than a
    block of columns and rounds the angle differently: the table's column
    is NOT what the reader computed before, so it is not handed out."""
    ctx = cells["lone_atom"]
    gv, uc = ctx.gvec, ctx.unit_cell
    reads = ctx.phases.reads
    got = ctx.phases.minus(gv.millers, uc.positions, uc.atoms_of_type(1))
    assert np.array_equal(
        got, gvm.phase_factors(gv.millers, uc.positions[[2]], -1.0))
    assert ctx.phases.reads == reads
    pair = ctx.phases.minus(gv.millers, uc.positions, uc.atoms_of_type(0))
    assert np.array_equal(
        pair, gvm.phase_factors(gv.millers, uc.positions[:2], -1.0))
    assert ctx.phases.reads == reads + 1


def test_phases_of_other_positions_are_refused(cells):
    ctx = cells["kmesh"]
    moved = ctx.unit_cell.positions + 1e-9
    with pytest.raises(ValueError, match="other positions"):
        ctx.phases.minus(ctx.gvec.millers, moved)
    with pytest.raises(ValueError, match="another G set"):
        ctx.phases.minus(ctx.gvec_coarse.millers, ctx.unit_cell.positions)


def _parent_atomic_orbitals(uc, gkvec, qmax):
    """ops/atomic.atomic_orbitals as the program before the memo wrote it:
    one radial table a type but its spline an ATOM, every factor inside the
    loop over atoms."""
    nk, ngk = gkvec.num_kpoints, gkvec.ngk_max
    lmax = max(max((w.l for w in t.atomic_wfs), default=-1)
               for t in uc.atom_types)
    nao = sum(uc.atom_types[it].num_atomic_wf_lm for it in uc.type_of_atom)
    out = np.zeros((nk, nao, ngk), dtype=np.complex128)
    tables = [RadialIntegralTable.build(
        t.r, np.stack([w.chi for w in t.atomic_wfs]),
        np.array([w.l for w in t.atomic_wfs]), qmax, m=1)
        for t in uc.atom_types]
    gk = gkvec.gkcart
    qlen = np.linalg.norm(gk, axis=-1)
    rhat = np.where(qlen[..., None] > 1e-30,
                    gk / np.maximum(qlen, 1e-30)[..., None],
                    np.array([0.0, 0, 1.0]))
    rlm = ylm_real(lmax, rhat)
    pref = 4.0 * np.pi / np.sqrt(uc.omega)
    off = 0
    for ia in range(uc.num_atoms):
        t = uc.atom_types[uc.type_of_atom[ia]]
        ri = tables[uc.type_of_atom[ia]](qlen.reshape(-1)).reshape(
            len(t.atomic_wfs), nk, ngk)
        mk = gkvec.millers + gkvec.kpoints[:, None, :]
        phase = np.exp(-2j * np.pi * (mk @ uc.positions[ia]))
        xi = 0
        for iw, w in enumerate(t.atomic_wfs):
            for m in range(-w.l, w.l + 1):
                out[:, off + xi, :] = (
                    pref * (-1j) ** w.l * rlm[..., lm_index(w.l, m)] * ri[iw]
                    * phase * gkvec.mask)
                xi += 1
        off += t.num_atomic_wf_lm
    return out


def _parent_initial_subspace(ctx):
    """dft/scf._initial_subspace as the program before the memo wrote it."""
    nk, nb, ngk = ctx.gkvec.num_kpoints, ctx.num_bands, ctx.gkvec.ngk_max
    ao = _parent_atomic_orbitals(ctx.unit_cell, ctx.gkvec,
                                 ctx.cfg.parameters.gk_cutoff + 1e-9)
    nao = ao.shape[1]
    nbig = max(nb, nao)
    rng = np.random.default_rng(42)
    psi = np.zeros((nk, ctx.num_spins, nbig, ngk), dtype=np.complex128)
    for ik in range(nk):
        base = np.zeros((nbig, ngk), dtype=np.complex128)
        n0 = min(nao, nbig)
        if n0:
            base[:n0] = ao[ik, :n0]
        if nbig > n0:
            r = (rng.standard_normal((nbig - n0, ngk))
                 + 1j * rng.standard_normal((nbig - n0, ngk)))
            base[n0:] = r * (1.0 / (1.0 + ctx.gkvec.kinetic()[ik]))
        base *= ctx.gkvec.mask[ik]
        for ispn in range(ctx.num_spins):
            psi[ik, ispn] = base
    return psi


@pytest.mark.parametrize("cell", CELLS)
def test_lcao_start_from_the_memo_equals_the_parent_program(cell):
    """Cold and from a hit, on another geometry in between: the start a job
    takes, its orbitals and (at one k-point) the sphere's pairing."""
    from sirius_tpu.dft.scf import _initial_subspace
    from sirius_tpu.ops.atomic import atomic_orbitals

    cold = _context(cell)
    assert cold.tables_reused == 0
    random_rows = max(cold.num_bands - sum(
        cold.unit_cell.atom_types[it].num_atomic_wf_lm
        for it in cold.unit_cell.type_of_atom), 0)
    assert (random_rows > 0) == (cell in ("kmesh", "two_species", "lone_atom"))
    want = _parent_initial_subspace(cold)
    x_cold = _initial_subspace(cold)
    if cell in ("kmesh", "gamma", "dshell"):
        _build(_deck(cell, g=2))
    hit = _context(cell)
    assert hit.tables_reused == 1 + len(hit.unit_cell.atom_types)
    with spans.capture() as cap:
        x_hit = _initial_subspace(hit)
    rec = cap.by_name("scf.setup.subspace")[0]
    assert rec["random_rows"] == random_rows
    for x in (x_cold, x_hit):  # the signs of the padded slots' zeros too
        assert x.dtype == want.dtype and x.tobytes() == want.tobytes()
    qmax = hit.cfg.parameters.gk_cutoff + 1e-9
    ao = atomic_orbitals(hit.unit_cell, hit.gkvec, qmax, forms=hit.ao_forms)
    assert np.array_equal(
        ao, _parent_atomic_orbitals(hit.unit_cell, hit.gkvec, qmax))
    assert np.array_equal(ao, atomic_orbitals(hit.unit_cell, hit.gkvec, qmax))
    # shared: the forms and the random rows are the cold context's objects
    assert all(a is b for a, b in zip(hit.ao_forms, cold.ao_forms))
    if random_rows:
        assert hit.random_rows(random_rows) is cold.random_rows(random_rows)
    gm, again = hit.gamma_map(), cold.gamma_map()
    fresh = build_gamma_map(np.asarray(hit.gkvec.millers[0]),
                            np.asarray(hit.gkvec.mask[0]))
    assert gm is again and gm.zero == fresh.zero
    for a, b in zip(gm[1:], fresh[1:]):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_deferred_tables_count_in_the_memo_and_replace_by_tag():
    ctx, _ = _build(_deck("kmesh"))
    before = cm._TABLES.bytes()
    rows = ctx.random_rows(4)
    assert cm._TABLES.bytes() == before + rows.nbytes
    assert ctx.random_rows(4) is rows
    more = ctx.random_rows(6)  # another row count takes the first's place
    assert more.shape[1] == 6
    assert cm._TABLES.bytes() == before + more.nbytes
    # the numbers are the parent's stream for that count, not a prefix
    assert not np.array_equal(more[:, :4], rows)


@pytest.fixture(scope="module")
def phase_job():
    """One ultrasoft job, context build included, counting every call of
    core/gvec.phase_factors and the calls on the fine G set."""
    from sirius_tpu.dft.scf import run_scf

    calls = []
    orig = gvm.phase_factors

    def counted(millers, positions, sign=1.0):
        calls.append(len(millers))
        return orig(millers, positions, sign)

    cm._TABLES.clear()
    gvm.phase_factors = counted
    try:
        with spans.capture() as cap:
            ctx, _ = _build(_deck("kmesh", g=1))
            ctx.cfg.control.telemetry = True
            res = run_scf(ctx.cfg, ctx=ctx, devices=jax.devices()[:1])
    finally:
        gvm.phase_factors = orig
    return {"calls": calls, "ctx": ctx, "res": res, "cap": cap}


def test_phase_factors_is_called_once_a_job(phase_job):
    ctx, res = phase_job["ctx"], phase_job["res"]
    assert res["converged"]
    assert phase_job["calls"] == [ctx.gvec.num_gvec]
    # structure factors, Ewald sum, the fused step's tables, the first
    # iteration's host D matrix
    assert res["counters"]["phase_table_builds"] == 1
    assert res["counters"]["phase_table_reads"] >= 4
    assert res["counters"]["phase_table_reads"] == ctx.phases.reads


def test_spans_of_the_phase_table_and_the_lcao_start(phase_job):
    cap = phase_job["cap"]
    (ph,) = cap.by_name("context.phases")
    (pos,) = cap.by_name("context.positions")
    assert ph["parent_id"] == pos["span_id"]
    assert ph["bytes"] == phase_job["ctx"].phases.table.nbytes
    (sub,) = cap.by_name("scf.setup.subspace")
    (setup,) = cap.by_name("scf.setup")
    assert sub["parent_id"] == setup["span_id"]
    assert sub["atomic_orbitals"] == 8 and sub["random_rows"] == 0


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
