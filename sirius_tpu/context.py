"""Simulation context: the composition root (reference:
src/context/simulation_context.cpp Simulation_context::initialize, :154).

Builds, from a Config: unit cell + symmetry + irreducible k-mesh, fine
(density/potential, |G| <= pw_cutoff) and coarse (wave-function,
|G| <= 2*gk_cutoff) G-vector sets with their FFT boxes, the fine<->coarse
index map, per-k |G+k| spheres, beta projectors, local-potential / core /
free-atom-density form-factor fields, Ewald energy, and the band count
(nbnd = nval/2 + max(10, 0.1*nval), simulation_context.cpp:333).

A build is four stages. The cell stage reads the deck (cell, symmetry group,
k-points). The lattice stage (G-vector sets, boxes, k-spheres) and the
species stage (each atom type's tables on them) read no atomic position and
are kept by the values they are functions of (`_TABLES`): a job, MD step or
relaxation step on the lattice, cutoffs, k-set and species of an earlier
one builds only the position stage."""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import threading

import numpy as np

from sirius_tpu import runtime
from sirius_tpu.config.schema import Config
from sirius_tpu.core.fftgrid import FFTGrid
from sirius_tpu.core.gvec import AtomPhases, Gvec, GkVec
from sirius_tpu.crystal.kpoints import irreducible_kmesh
from sirius_tpu.crystal.symmetry import CrystalSymmetry
from sirius_tpu.crystal.unit_cell import UnitCell
from sirius_tpu.dft.ewald import ewald_energy
from sirius_tpu.dft.radial_tables import (
    HOST_CALLBACKS,
    make_periodic_function,
    rho_core_form_factor,
    rho_total_form_factor,
    structure_factors,
    vloc_ff,
)
from sirius_tpu.obs import spans as obs_spans
from sirius_tpu.ops.atomic import ao_form
from sirius_tpu.ops.augmentation import Augmentation, AugmentationType, build_type
from sirius_tpu.ops.beta import BetaProjectors, beta_form, gk_directions
from sirius_tpu.ops.gamma import build_gamma_map

# ---------------------------------------------------------------------------
# Tables that read no atomic position, kept between contexts
# ---------------------------------------------------------------------------

# The bounds of the memo. One lattice takes 1 + (its atom types) entries. The
# 2-atom decks' entries are a few MB each (36 k-spheres of ~1100 vectors, a
# 36 325-vector fine set); a 54-atom silicon cell's lattice entry is ~80 MB
# (984 161 fine G-vectors at 64 B each, the coarse set, one k-sphere) and its
# species entry ~160 MB (q_pw: 10 packed pairs x 984 161 x 16 B). 1 GiB
# holds four such cells, or every small lattice a screening campaign
# alternates between; 16 entries are five to eight lattices of one or two
# species. An entry larger than the byte bound alone is handed out and not
# kept (it evicts nothing).
_TABLES_MAX_ENTRIES = 16
_TABLES_MAX_BYTES = 1 << 30


def _freeze(obj) -> int:
    """Make every array under ``obj`` (arrays, dataclasses, lists, tuples)
    read-only and return their bytes: contexts share what the memo hands
    out, so a writer must fail loudly, not corrupt the next job."""
    if isinstance(obj, np.ndarray):
        obj.setflags(write=False)
        return obj.nbytes
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return sum(_freeze(getattr(obj, f.name))
                   for f in dataclasses.fields(obj))
    if isinstance(obj, (list, tuple)):
        return sum(_freeze(v) for v in obj)
    return 0


class _TableMemo:
    """Least-recently-used map from a digest of values to tables built from
    exactly those values, bounded by entries and by bytes, behind its own
    lock (contexts are built under the scheduler's lock, and outside it by
    run_scf, relaxation, MD and the stepper). A thread that asks for a key
    another thread is building waits for that build."""

    def __init__(self, max_entries: int, max_bytes: int):
        self.max_entries = int(max_entries)
        self.max_bytes = int(max_bytes)
        self._cond = threading.Condition()
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._building: set = set()

    def get(self, key: str, build):
        """``(value, bytes, hit)``: the entry of ``key``, or ``build()``
        frozen, kept and counted."""
        with self._cond:
            while True:
                ent = self._entries.get(key)
                if ent is not None:
                    self._entries.move_to_end(key)
                    return ent[0], ent[1], True
                if key not in self._building:
                    self._building.add(key)
                    break
                self._cond.wait()
        try:
            value = build()
            nbytes = _freeze(value)
        except BaseException:
            with self._cond:
                self._building.discard(key)
                self._cond.notify_all()
            raise
        with self._cond:
            self._building.discard(key)
            if nbytes <= self.max_bytes:
                self._entries[key] = (value, nbytes)
                while (len(self._entries) > self.max_entries
                       or self.bytes() > self.max_bytes):
                    self._entries.popitem(last=False)
            self._cond.notify_all()
        return value, nbytes, False

    def bytes(self) -> int:
        with self._cond:
            return sum(n + getattr(getattr(v, "deferred", None), "nbytes", 0)
                       for v, n in self._entries.values())

    def clear(self) -> None:
        with self._cond:
            self._entries.clear()


class _Deferred:
    """Tables of a memo entry that only some jobs read, built at the first
    request, frozen, kept with the entry and counted in its bytes (the
    bounds are enforced at the memo's next insert). One table a name: a
    request under another ``tag`` (the LCAO start's random rows for another
    row count) replaces it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._kept: dict = {}  # name -> (tag, value, bytes)
        self.nbytes = 0

    def get(self, name: str, tag, build):
        with self._lock:
            ent = self._kept.get(name)
            if ent is None or ent[0] != tag:
                value = build()
                nbytes = _freeze(value)
                self.nbytes += nbytes - (0 if ent is None else ent[2])
                ent = self._kept[name] = (tag, value, nbytes)
            return ent[1]


_TABLES = _TableMemo(_TABLES_MAX_ENTRIES, _TABLES_MAX_BYTES)


def _digest(*parts) -> str:
    """Digest of values: arrays by dtype, shape and bytes, the rest by
    repr (floats to the last bit)."""
    h = hashlib.blake2b(digest_size=16)
    for part in parts:
        if isinstance(part, np.ndarray):
            a = np.ascontiguousarray(part)
            h.update(f"<{a.dtype.str}{a.shape}>".encode())
            h.update(a.tobytes())
        else:
            h.update(f"<{part!r}>".encode())
    return h.hexdigest()


def _type_digest(t) -> str:
    """An atom type by content: every array and number the species stage
    (and the projectors' D_ion) reads, not its label or its id(): the
    scheduler builds a fresh AtomType every job. ~100 KB through blake2b,
    0.1-0.2 ms a type."""
    parts = [t.pseudo_type, float(t.zn), t.r, t.vloc, t.d_ion,
             t.rho_core, t.rho_total]
    for b in t.beta:
        parts += [int(b.l), int(b.nr), b.j, b.rbeta]
    for w in t.atomic_wfs:
        parts += [int(w.l), w.chi]
    for ch in t.augmentation:
        parts += [int(ch.i), int(ch.j), int(ch.l), ch.qr]
    return _digest(*parts)


@dataclasses.dataclass(frozen=True)
class _LatticeTables:
    """What a context holds that is a function of the lattice, the two
    cutoffs, the fine box's override, the k-points and weights by value and
    the sphere padding quantum."""

    gvec: Gvec
    gvec_coarse: Gvec
    fft_coarse: FFTGrid
    coarse_to_fine: np.ndarray
    gkvec: GkVec  # padded
    gk_len: np.ndarray  # (nk, ngk) |G+k|
    gk_hat: np.ndarray  # (nk, ngk, 3) unit vectors
    qshell: np.ndarray  # (nshell,) sqrt(gvec.shell_g2)
    # the LCAO start's random rows and the Gamma sphere's pairing: read by
    # some jobs only, so built at the first one's request
    deferred: _Deferred


def _build_lattice_tables(lattice, pw_cutoff, gk_cutoff, fgs, kpts, kw,
                          quantum) -> _LatticeTables:
    # the memo never aliases a job's own arrays (Gvec keeps its lattice,
    # GkVec its k-points and weights)
    lattice = np.array(lattice, dtype=np.float64)
    kpts = np.array(kpts, dtype=np.float64)
    kw = np.array(kw, dtype=np.float64)
    # fine/coarse FFT boxes: the reference's exact sizing (5-smooth,
    # min grid around the sphere) — the nonlinear XC is evaluated on
    # the fine box, so dims are part of the numerical definition;
    # settings.fft_grid_size (recorded in every reference output)
    # overrides when set
    if fgs:
        fft_fine = FFTGrid(fgs)
    else:
        fft_fine = FFTGrid.ref_min_grid(lattice, pw_cutoff)
    gvec = Gvec.build(lattice, pw_cutoff, fft=fft_fine)
    fft_coarse = FFTGrid.ref_min_grid(lattice, 2 * gk_cutoff)
    gvec_coarse = Gvec.build(lattice, 2 * gk_cutoff, fft=fft_coarse)
    c2f = gvec.index_of_millers(gvec_coarse.millers)
    assert np.all(c2f >= 0)
    gkvec = GkVec.build(gvec, kpts, gk_cutoff, fft_coarse, weights=kw)
    if quantum > 0:
        gkvec = gkvec.pad_to(-(-gkvec.ngk_max // quantum) * quantum)
    gk_len, gk_hat = gk_directions(gkvec)
    return _LatticeTables(
        gvec=gvec, gvec_coarse=gvec_coarse, fft_coarse=fft_coarse,
        coarse_to_fine=c2f, gkvec=gkvec, gk_len=gk_len, gk_hat=gk_hat,
        qshell=np.sqrt(gvec.shell_g2), deferred=_Deferred())


# the three form factors on the fine set's shells (local potential, core
# density, free-atom density), by the name of the host callback that
# replaces each (dft/radial_tables.HOST_CALLBACKS)
_FF_HOOKS = ("vloc_ri", "rhoc_ri", "ps_rho_ri")


@dataclasses.dataclass(frozen=True)
class _SpeciesTables:
    """One atom type's tables on a lattice's G-vector sets: a function of
    the lattice tables' key, the type's content and the settings read here.
    No atom phase anywhere."""

    beta_form: np.ndarray | None  # (nbeta_lm, nk, ngk), ops/beta.beta_form
    ao_form: np.ndarray | None  # (nao_lm, nk, ngk), ops/atomic.ao_form
    aug: AugmentationType | None
    ff_shells: tuple  # by _FF_HOOKS; None where a host callback stands in


def _build_species_tables(t, lat: _LatticeTables, qmax, rc,
                          hooked) -> _SpeciesTables:
    return _SpeciesTables(
        beta_form=beta_form(t, lat.gk_len, lat.gk_hat, lat.gvec.omega, qmax),
        ao_form=ao_form(t, lat.gk_len, lat.gk_hat, lat.gvec.omega, qmax),
        aug=build_type(t, lat.gvec, lat.gvec.omega) if t.augmentation else None,
        ff_shells=tuple(
            None if skip else np.asarray(fn(t, lat.qshell))
            for fn, skip in zip(
                (vloc_ff(rc), rho_core_form_factor, rho_total_form_factor),
                hooked)),
    )


def _position_stage(uc: UnitCell, lat: _LatticeTables, species: list,
                    pw_cutoff: float, qmax: float):
    """Everything of a context that reads ``uc.positions`` or
    ``uc.moments``: the projectors' atom phases, the block-diagonal D_ion
    and Q matrices, the atoms' phases on the fine G set, the structure
    factors, the three periodic functions summed over them and the Ewald
    energy. Built every time. The phase table is built here once
    (``context.phases``) and goes with the context: the structure factors
    and the Ewald sum read it here, the augmentation's host and device
    tables later in the job."""
    gvec = lat.gvec
    beta = BetaProjectors.build(uc, lat.gkvec, qmax=qmax,
                                forms=[s.beta_form for s in species])
    aug = None
    if any(t.augmentation for t in uc.atom_types):
        aug = Augmentation(per_type=[s.aug for s in species])
        # assemble the block-diagonal S-operator integrals q_mtrx
        qmat = np.zeros_like(beta.dion)
        for ia, off, nbf in beta.atom_blocks(uc):
            at = aug.per_type[uc.type_of_atom[ia]]
            if at is not None:
                qmat[off : off + nbf, off : off + nbf] = at.q_mtrx
        beta = dataclasses.replace(beta, qmat=qmat)
    with obs_spans.span("context.phases") as sp:
        phases = AtomPhases(gvec.millers, uc.positions)
        sp.set(bytes=phases.table.nbytes)
    sfact = structure_factors(uc, gvec, phases)
    vloc_g, rho_core_g, rho_at_g = (
        make_periodic_function(uc, gvec, [s.ff_shells[i] for s in species],
                               sfact, hook=hook)
        for i, hook in enumerate(_FF_HOOKS))
    e_ewald = ewald_energy(
        uc.lattice,
        uc.positions,
        np.asarray([uc.atom_types[t].zn for t in uc.type_of_atom]),
        gvec.gcart,
        gvec.millers,
        pw_cutoff,
        phases=phases,
    )
    return beta, aug, vloc_g, rho_core_g, rho_at_g, e_ewald, phases


@dataclasses.dataclass
class SimulationContext:
    cfg: Config
    unit_cell: UnitCell
    symmetry: CrystalSymmetry | None
    gvec: Gvec  # fine set (density/potential)
    gvec_coarse: Gvec  # coarse set (wave functions)
    fft_coarse: FFTGrid
    coarse_to_fine: np.ndarray  # fine index of each coarse G
    gkvec: GkVec
    kweights: np.ndarray
    beta: BetaProjectors
    aug: Augmentation | None
    vloc_g: np.ndarray  # (ng_fine,) local potential
    rho_core_g: np.ndarray  # (ng_fine,)
    rho_atomic_g: np.ndarray  # (ng_fine,) superposition of free atoms
    e_ewald: float
    num_bands: int
    num_spins: int
    num_mag_dims: int
    # of the 1 + (atom types) position-independent table sets of this
    # build, how many an earlier context of the process had built
    tables_reused: int = 0
    # e^{-2 pi i G.x_a} of the atoms on the fine set, built once in the
    # position stage: this context's own, gone with it
    phases: AtomPhases | None = None
    # each atom type's LCAO form on the k-spheres (ops/atomic.ao_form) and
    # the lattice entry's on-demand tables: shared, read-only (`_TABLES`)
    ao_forms: list | None = None
    deferred: _Deferred = dataclasses.field(default_factory=_Deferred)

    @staticmethod
    def create(cfg: Config, base_dir: str = ".") -> "SimulationContext":
        """Build the context of a deck.

        The G-vector sets, the FFT boxes, the coarse-to-fine map, the
        k-spheres and each atom type's augmentation tables are SHARED with
        every other context of the process on the same lattice, cutoffs,
        k-points and species content (`_TABLES`), and read-only: writing
        into ``ctx.gvec``, ``ctx.gvec_coarse``, ``ctx.coarse_to_fine``,
        ``ctx.gkvec``, ``ctx.aug.per_type[i]``, ``ctx.ao_forms[i]``,
        ``ctx.random_rows(n)`` or ``ctx.gamma_map()`` raises. Everything
        that reads a position or a moment (``beta``, ``phases``, ``vloc_g``,
        ``rho_core_g``, ``rho_atomic_g``, ``e_ewald``, ``symmetry``) is this
        context's own and built every time, so a build at a new geometry of
        a seen lattice costs what one at a repeated geometry costs."""
        # set-up tables are host work (runtime.py placement rule)
        with runtime.host_scope():
            return SimulationContext._create(cfg, base_dir)

    @staticmethod
    def _create(cfg: Config, base_dir: str) -> "SimulationContext":
        p = cfg.parameters
        uc = UnitCell.from_config(cfg.unit_cell, base_dir)
        if p.gk_cutoff <= 0 or p.pw_cutoff <= 0:
            raise ValueError("gk_cutoff and pw_cutoff must be set")
        if p.pw_cutoff < 2 * p.gk_cutoff:
            raise ValueError(
                f"pw_cutoff ({p.pw_cutoff}) must be >= 2*gk_cutoff "
                f"({2 * p.gk_cutoff}) to hold wave-function products"
            )
        sym = None
        # the group search and the wedge of the mesh: a child span of
        # whatever builds the context (serve.context_build)
        with obs_spans.span("context.symmetry") as sp:
            if p.use_symmetry:
                sym = CrystalSymmetry.find(
                    uc.lattice, uc.positions, uc.type_of_atom, uc.moments,
                    p.num_mag_dims
                )
            kpts, kw = irreducible_kmesh(
                p.ngridk, p.shiftk, sym,
                use_symmetry=p.use_symmetry and p.use_ibz,
                time_reversal=p.num_mag_dims != 3,
            )
            sp.set(num_ops=0 if sym is None else int(sym.num_ops),
                   kpoints_mesh=int(np.prod(p.ngridk)),
                   kpoints_irreducible=len(kpts))
        if len(p.vk):
            kpts = np.asarray(p.vk, dtype=np.float64)
            kw = np.full(len(kpts), 1.0 / len(kpts))

        fgs = cfg.settings.fft_grid_size
        fgs = (tuple(int(x) for x in fgs)
               if fgs and all(int(x) > 0 for x in fgs) else None)
        quantum = int(getattr(cfg.control, "ngk_pad_quantum", 0) or 0)
        with obs_spans.span("context.lattice_tables") as sp:
            lkey = _digest("lattice", np.asarray(uc.lattice, np.float64),
                           float(p.pw_cutoff), float(p.gk_cutoff), fgs,
                           np.asarray(kpts, np.float64),
                           np.asarray(kw, np.float64), quantum)
            lat, nbytes, hit = _TABLES.get(lkey, lambda: _build_lattice_tables(
                uc.lattice, p.pw_cutoff, p.gk_cutoff, fgs, kpts, kw, quantum))
            sp.set(hit=hit, bytes=nbytes)
        reused = int(hit)
        with obs_spans.span("context.species_tables") as sp:
            qmax = p.gk_cutoff + 1e-9
            rc = float(cfg.settings.pseudo_grid_cutoff)
            hooked = tuple(h in HOST_CALLBACKS for h in _FF_HOOKS)
            species, nbytes, hits = [], 0, 0
            for t in uc.atom_types:
                skey = _digest("species", lkey, _type_digest(t), qmax, rc,
                               hooked)
                tab, nb, hit = _TABLES.get(
                    skey, lambda t=t: _build_species_tables(
                        t, lat, qmax, rc, hooked))
                species.append(tab)
                nbytes += nb
                hits += int(hit)
            sp.set(hit=hits == len(species), bytes=nbytes,
                   types=len(species), hits=hits)
        reused += hits
        with obs_spans.span("context.positions"):
            beta, aug, vloc_g, rho_core_g, rho_at_g, e_ewald, phases = (
                _position_stage(uc, lat, species, p.pw_cutoff, qmax))
        nval = uc.num_valence_electrons
        nbnd = int(nval / 2.0) + max(10, int(0.1 * nval))
        if p.num_mag_dims == 3:
            nbnd *= 2
        if p.num_bands > 0:
            nbnd = p.num_bands
        elif p.num_fv_states > 0:
            nbnd = p.num_fv_states
        return SimulationContext(
            cfg=cfg,
            unit_cell=uc,
            symmetry=sym,
            gvec=lat.gvec,
            gvec_coarse=lat.gvec_coarse,
            fft_coarse=lat.fft_coarse,
            coarse_to_fine=lat.coarse_to_fine,
            gkvec=lat.gkvec,
            kweights=kw,
            beta=beta,
            aug=aug,
            vloc_g=vloc_g,
            rho_core_g=rho_core_g,
            rho_atomic_g=rho_at_g,
            e_ewald=e_ewald,
            num_bands=nbnd,
            num_spins=2 if p.num_mag_dims > 0 else 1,
            num_mag_dims=p.num_mag_dims,
            tables_reused=reused,
            phases=phases,
            ao_forms=[s.ao_form for s in species],
            deferred=lat.deferred,
        )

    def gamma_map(self):
        """ops/gamma.build_gamma_map of the first k-sphere: a function of
        the lattice's tables, kept with them."""
        return self.deferred.get("gamma_map", None, lambda: build_gamma_map(
            np.asarray(self.gkvec.millers[0]), np.asarray(self.gkvec.mask[0])))

    def random_rows(self, rows: int) -> np.ndarray:
        """[nk, rows, ngk] complex128: the rows that fill an LCAO start
        beyond the atomic orbitals (dft/scf._initial_subspace), the same
        numbers every job: default_rng(42) normals a k-point, damped by
        1 / (1 + |G+k|^2 / 2) and masked. A function of the k-spheres and
        the row count, kept with the lattice's tables."""
        def build():
            gk = self.gkvec
            rng = np.random.default_rng(42)
            damp = 1.0 / (1.0 + gk.kinetic())
            out = np.empty((gk.num_kpoints, rows, gk.ngk_max),
                           dtype=np.complex128)
            for ik in range(gk.num_kpoints):
                r = (rng.standard_normal((rows, gk.ngk_max))
                     + 1j * rng.standard_normal((rows, gk.ngk_max)))
                out[ik] = r * damp[ik]
                out[ik] *= gk.mask[ik]
            return out

        return self.deferred.get("random_rows", int(rows), build)

    @property
    def max_occupancy(self) -> float:
        return 1.0 if self.num_mag_dims > 0 else 2.0
