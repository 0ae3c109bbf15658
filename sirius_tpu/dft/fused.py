"""Device-resident SCF iteration: density -> potential -> mixer fused into
one compiled XLA program.

The host loop in dft/scf.py historically round-tripped the full G-sphere
density, potential and mixer history through numpy every iteration. On TPU
that per-iteration host traffic (plus the numpy Anderson solve) dominates
wall time once the band solve itself is compiled. This module packages the
entire post-band-solve pipeline

  coarse |psi|^2 accumulation -> fine-G density (+ ultrasoft augmentation,
  + point-group symmetrization) -> mixer (linear / Anderson) -> Hartree +
  XC + local potential assembly -> D-operator + H-diagonal refresh

as one jitted step over a donated carry (FusedCarry), so the only thing
fetched to the host per iteration is a [NUM_SCALARS] vector of convergence
and energy scalars. Everything obeys the real-boundary contract of
parallel/batched.py: the carry and all step outputs are REAL leaves —
(re, im) pairs for complex quantities — and complex dtypes exist only
inside the compiled program.

The Ewald energy and all geometry tables are hoisted: built once on the
host at FusedScf construction and uploaded as a constant pytree of device
arrays (`self.tables`), passed (not closed over) so the executable does not
embed them.

Selection: run_scf uses this path when control.device_scf is "auto"/true
and the deck is in the supported regime (PP-PW, no Hubbard/PAW/mGGA, plain
or Anderson mixing) behind either production band solve: the batched k-set
solve, or on one device at Gamma the packed-real solve (ops/gamma.py),
whose block reaches step() as the (re, im) pair of unpack_device.
control.device_scf = false keeps the host path — bit-identical to the
pre-fusion code — as the f64 reference and debug fallback;
tests/test_fused_scf.py pins the two paths to ~1e-8 Ha agreement.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from sirius_tpu.core import hilo
from sirius_tpu.core.fftgrid import r_to_g
from sirius_tpu.dft.density import (
    num_sym_pw,
    symmetrize_density_matrix_device,
    symmetrize_pw_device,
    symmetry_tables,
)
from sirius_tpu.dft.mixer import (
    DeviceMixerState,
    device_mix,
    device_mixer_init,
    device_mixer_weights,
)
from sirius_tpu.dft.potential import (
    build_potential_device_tables,
    constant_fields_device,
    generate_potential_device,
    num_box_fills,
    num_gradient_transforms,
)
from sirius_tpu.dft.xc import XCFunctional
from sirius_tpu.ops.augmentation import (
    build_aug_device_tables,
    d_operator_device,
    rho_aug_g_device,
)
from sirius_tpu.ops.hamiltonian import real_dtype_of
from sirius_tpu.parallel.batched import (
    compute_h_diag_device,
    join_cplx,
    split_cplx,
)
from sirius_tpu.utils.profiler import counters

# indices into the per-iteration scalar record (the ONLY device->host
# traffic of a fused iteration)
S_RMS = 0  # mixer rms (pre-mix)
S_EHA = 1  # Hartree energy of the (mixed - new) charge residual
S_VHA = 2  # int rho v_ha
S_VXC = 3  # int rho v_xc
S_VLOC = 4  # int rho v_loc
S_VEFF = 5  # int rho v_eff
S_EXC = 6  # int (rho + rho_core) eps_xc
S_BXC = 7  # int m b_xc
S_E1 = 8  # E_pot[rho_out] under the OLD potential
S_E2 = 9  # E_pot[rho_out] under the NEW potential
S_EVAL = 10  # sum_k w_k occ eps
S_NEL = 11  # electron count from rho_out (audit)
S_MAG = 12  # total moment from m_out (pre-mix)
S_V0 = 13  # Re veff(G=0)
S_ENT = 14  # smearing entropy sum
S_FINITE = 15  # 1.0 when the mixed vector and new potential are all-finite
# -- numerics ledger (obs/numerics.py): cheap per-iteration invariants
# appended to the SAME record, so they ride the one existing readback --
S_ORTHO = 16  # max |psi^H S psi - I| (S-orthonormality of the band block)
S_CHG = 17  # |Re x_mixed[0] - Re x_new[0]| * omega (mixer charge drift)
S_SYM = 18  # max |P_sym rho_new - rho_new| (symmetrization idempotency)
S_HERM = 19  # max |H_nl - H_nl^H| (subspace nonlocal-H hermiticity)
NUM_SCALARS = 20
# The summed energy terms leave the device as two words each (core/hilo.py:
# one float32 word at a few hundred Ha resolves 1.5e-5 Ha, and the loop asks
# about 1e-5): the record is [NUM_SCALARS + len(S_PAIRED)], the second word
# of S_PAIRED[i] at NUM_SCALARS + i, zero in float64. fold_scalars() adds
# them on the host; everything downstream sees [NUM_SCALARS] float64.
S_PAIRED = (S_VHA, S_VXC, S_VLOC, S_VEFF, S_EXC, S_BXC, S_E1, S_E2, S_EVAL)
# How far a 32-bit step's accumulated electron count may lie from the count
# and still be its rounding, in units of the type's eps times the count (the
# chip reads 3 to 4 on the 2-atom cell: 8.0000029 to 8.0000038 electrons)
CHARGE_EPS = 64


def fold_scalars(record) -> np.ndarray:
    """The [NUM_SCALARS] float64 scalars of one readback, the two words of
    each paired term added."""
    raw = np.asarray(record, dtype=np.float64)
    out = raw[:NUM_SCALARS].copy()
    out[list(S_PAIRED)] += raw[NUM_SCALARS:]
    return out


@dataclasses.dataclass(frozen=True)
class StepConstants:
    """Every value _step_impl bakes into its trace, and nothing else: the
    step's one static argument. Frozen and hashable (dtypes, numbers,
    tuples, names: no context, no array), so equal records mean equal
    programs for equal input shapes, and the record is the key under
    which a process finds the step it has already built."""

    cdt: np.dtype  # the working precision: complex64/float32 on a TPU
    rdt: np.dtype
    ns: int
    ng: int
    omega: float
    nel: float
    charge_tol: float
    dims: tuple
    dims_coarse: tuple
    kind: str  # the mixer's
    mix_beta: float
    max_history: int
    has_aug: bool
    do_symmetrize: bool
    polarized: bool
    xc: tuple  # the functional by its names


# The steps this process has built, by their constants, least recently
# used out past STEP_PROGRAMS_MAX (the bound the serving engine's cache
# always had). An entry is a jax.jit of _step_impl with its record bound:
# it holds no FusedScf, no context and no device array, jit keys its
# traces on the inputs' shapes, dtypes and shardings as it does for every
# module-level program, and dropping the entry drops its traces and loaded
# executables with it (one jit over a static argument could not let one
# record go).
STEP_PROGRAMS_MAX = 32
_step_programs: OrderedDict = OrderedDict()
_step_programs_lock = threading.Lock()


def step_program(rec: StepConstants):
    """(the process's compiled step for `rec`, whether it was there):
    the one way to obtain the step, whoever calls run_scf."""
    with _step_programs_lock:
        step = _step_programs.get(rec)
        found = step is not None
        if found:
            _step_programs.move_to_end(rec)
        else:
            bound = functools.partial(_step_impl, rec)
            # jit names a program after its function, and a partial has no
            # name of its own: the module stays jit__step_impl
            bound.__name__ = _step_impl.__name__
            step = jax.jit(bound, donate_argnums=(1,))
            _step_programs[rec] = step
            while len(_step_programs) > STEP_PROGRAMS_MAX:
                _step_programs.popitem(last=False)
        return step, found


class FusedCarry(NamedTuple):
    """Donated SCF carry: all-real leaves (the jit-boundary contract)."""

    x_re: jnp.ndarray  # [nx] packed mixed vector (rho fine-G [+ mag])
    x_im: jnp.ndarray
    hx_re: jnp.ndarray  # [M, nx] mixer input history
    hx_im: jnp.ndarray
    hf_re: jnp.ndarray  # [M, nx] mixer residual history
    hf_im: jnp.ndarray
    count: jnp.ndarray  # int32, valid history rows
    veff_re: jnp.ndarray  # [ng] effective potential (for the e1 term)
    veff_im: jnp.ndarray
    bz_re: jnp.ndarray  # [ng] collinear field (zeros when unpolarized)
    bz_im: jnp.ndarray


class FusedScf:
    """One SCF deck's fused device-resident iteration.

    Construction uploads every geometry/metric table once; step() is the
    compiled per-iteration program; finalize() is the single end-of-loop
    host fetch that reconstitutes what the final report needs.
    """

    def __init__(self, ctx, xc, mixer, polarized: bool, do_symmetrize: bool,
                 beta_dev=None, exec_cache=None, wf_dtype=jnp.complex128,
                 place=None):
        # place: run_scf's placement of a pytree on the compute device(s)
        # (replicated on a mesh); None leaves the uploads where jnp puts them
        # one dtype policy: the step works in the band solve's precision —
        # complex64/float32 on a TPU, where 64-bit types do not run
        # (runtime.py); with complex128 the program is the f64 one
        self.cdt = jnp.dtype(wf_dtype)
        self.rdt = jnp.dtype(real_dtype_of(wf_dtype))
        self.ctx = ctx
        self.xc = xc
        self.polarized = bool(polarized)
        self.do_symmetrize = bool(do_symmetrize)
        self.ns = 2 if polarized else 1
        self.ng = ctx.gvec.num_gvec
        self.omega = float(ctx.unit_cell.omega)
        self.nel = float(ctx.unit_cell.num_valence_electrons
                         - ctx.cfg.parameters.extra_charge)
        self.charge_tol = (CHARGE_EPS * float(jnp.finfo(self.rdt).eps)
                           * max(self.nel, 1.0))
        self.dims = tuple(ctx.gvec.fft.dims)
        self.dims_coarse = tuple(ctx.fft_coarse.dims)
        self.kind = mixer.kind
        self.mix_beta = float(mixer.beta)
        self.max_history = int(mixer.max_history)
        self.nx = self.ns * self.ng
        nbeta = ctx.beta.num_beta_total
        # same gate as the host density/D path: with ctx.aug present the
        # density matrix is accumulated and D screened even if some species
        # carry no augmentation (their tables are simply absent)
        self.has_aug = ctx.aug is not None and nbeta > 0

        tables = {
            "mixw": device_mixer_weights(mixer),
            "pot": build_potential_device_tables(ctx),
            "fft_index_coarse": ctx.gvec_coarse.fft_index,
            "c2f": ctx.coarse_to_fine,
            "ekin": np.asarray(ctx.gkvec.kinetic()),
            "gmask": np.asarray(ctx.gkvec.mask),
            "dion": np.real(np.asarray(ctx.beta.dion))
            if nbeta
            else np.zeros((0, 0)),
            # bare augmentation overlap Q: the S metric of the ledger's
            # orthonormality invariant (same table make_hkset_params uses)
            "qmat": np.real(np.asarray(ctx.beta.qmat))
            if (nbeta and ctx.beta.qmat is not None)
            else np.zeros((nbeta, nbeta)),
        }
        if beta_dev is not None:
            tables["beta_re"], tables["beta_im"] = beta_dev
        elif nbeta:
            tables["beta_re"], tables["beta_im"] = split_cplx(
                np.asarray(ctx.beta.beta_gk), self.rdt
            )
        else:
            nk = ctx.gkvec.num_kpoints
            z = np.zeros((nk, 0, ctx.gkvec.ngk_max))
            tables["beta_re"], tables["beta_im"] = z, z
        if self.has_aug:
            tables["aug"] = build_aug_device_tables(
                ctx.unit_cell, ctx.gvec, ctx.aug, ctx.beta, phases=ctx.phases
            )
        if self.do_symmetrize:
            tables.update(symmetry_tables(ctx))
        # one-time upload; step() takes these as an argument so they are
        # program inputs, not baked-in constants
        place = place or (lambda t: t)
        self.tables = place(jax.tree_util.tree_map(self._table, tables))
        self.kweights_dev = place(self._table(np.asarray(ctx.kweights)))
        # rho_core(r) and v_loc(r) do not change in a job: transformed here,
        # once, where the tables live and in their precision (the bits the
        # step itself would compute), not in every iteration
        self.tables["pot"].update(
            constant_fields_device(self.tables["pot"], self.dims))
        # sphere-to-box placements one step runs (counters.num_tail_box_fills)
        self.box_fills = num_box_fills(xc, self.polarized)
        # plane-wave symmetrisations one step runs (counters.num_sym_pw) and
        # the operations each sums over
        self.sym_pw = num_sym_pw(self.do_symmetrize, self.polarized)
        self.sym_ops = (int(ctx.symmetry.num_ops) if self.do_symmetrize
                        else 0)
        # fine-box transforms the gradient correction adds to one step
        # (counters.num_xc_gradient_transforms; 0 for LDA)
        self.xc_gradient_transforms = num_gradient_transforms(
            xc, self.polarized)
        self.xc_kind = "gga" if xc.is_gga else "lda"  # the step's XC branch
        self.constants = StepConstants(
            cdt=self.cdt, rdt=self.rdt, ns=self.ns, ng=self.ng,
            omega=self.omega, nel=self.nel, charge_tol=self.charge_tol,
            dims=self.dims, dims_coarse=self.dims_coarse, kind=self.kind,
            mix_beta=self.mix_beta, max_history=self.max_history,
            has_aug=self.has_aug, do_symmetrize=self.do_symmetrize,
            polarized=self.polarized, xc=tuple(xc.names))
        # The step is a program of the process (step_program), found by
        # its constants: a second FusedScf with an equal record and equal
        # input shapes, dtypes and shardings runs the first one's trace,
        # lowering and loaded executable, whoever built it (run_scf in a
        # loop, the engine, a relaxation, MD). The tables are program
        # inputs, so the reuse is exact.
        self._step, self.step_reused = step_program(self.constants)
        if exec_cache is not None:
            # the serving engine's books (hits, misses, /metrics): what it
            # is told, not what decides whether anything is traced
            exec_cache.get(("fused_step", *self._trace_signature()),
                           lambda: self._step)

    def _table(self, a):
        """Upload one table leaf in the working precision (index leaves
        keep their integer dtype)."""
        if jnp.issubdtype(a.dtype, jnp.complexfloating):
            return jnp.asarray(a, dtype=self.cdt)
        if jnp.issubdtype(a.dtype, jnp.floating):
            return jnp.asarray(a, dtype=self.rdt)
        return jnp.asarray(a)

    def _trace_signature(self) -> tuple:
        """What decides which compiled step a call of step() runs: the
        constants and the shapes/dtypes of the table inputs and the
        per-call arrays (nk/nb/ngk). The serving engine books its
        executable hits and misses under it."""
        leaves, treedef = jax.tree_util.tree_flatten(self.tables)
        tab = tuple((tuple(x.shape), str(x.dtype)) for x in leaves)
        return (
            self.constants,
            self.ctx.gkvec.num_kpoints, self.ctx.num_bands,
            self.ctx.gkvec.ngk_max,
            str(treedef), tab,
            tuple(self.kweights_dev.shape),
        )

    # -- host <-> device edges -------------------------------------------

    def init_carry(self, x_mix: np.ndarray, pot,
                   history: dict | None = None) -> FusedCarry:
        """Seed the carry from the host-side initial packed vector and the
        initial potential (generated on the host once, before the loop).
        `history` optionally restores a checkpointed mixer history
        ({'mix_x': [m, nx], 'mix_f': [m, nx]} complex, oldest first) so a
        resumed fused run continues the same Anderson trajectory."""
        rdt = self.rdt
        x_re, x_im = split_cplx(np.asarray(x_mix), rdt)
        st = device_mixer_init(self.nx, self.max_history, dtype=rdt)
        if history and "mix_x" in history:
            hx = np.asarray(history["mix_x"])[-self.max_history:]
            hf = np.asarray(history["mix_f"])[-self.max_history:]
            m = hx.shape[0]
            hx_re = np.asarray(st.hx_re).copy()
            hx_im = np.asarray(st.hx_im).copy()
            hf_re = np.asarray(st.hf_re).copy()
            hf_im = np.asarray(st.hf_im).copy()
            hx_re[:m], hx_im[:m] = np.real(hx), np.imag(hx)
            hf_re[:m], hf_im[:m] = np.real(hf), np.imag(hf)
            st = DeviceMixerState(
                jnp.asarray(hx_re), jnp.asarray(hx_im),
                jnp.asarray(hf_re), jnp.asarray(hf_im),
                jnp.asarray(np.int32(m)),
            )
        v_re, v_im = split_cplx(np.asarray(pot.veff_g), rdt)
        if self.polarized and pot.bz_g is not None:
            b_re, b_im = split_cplx(np.asarray(pot.bz_g), rdt)
        else:
            # distinct buffers (donated leaves must not alias)
            b_re, b_im = np.zeros(self.ng, rdt), np.zeros(self.ng, rdt)
        return FusedCarry(
            jnp.asarray(x_re), jnp.asarray(x_im),
            st.hx_re, st.hx_im, st.hf_re, st.hf_im, st.count,
            jnp.asarray(v_re), jnp.asarray(v_im),
            jnp.asarray(b_re), jnp.asarray(b_im),
        )

    def fetch_state(self, carry: FusedCarry, with_history: bool = False):
        """Host copy of the packed mixed vector (and optionally the mixer
        history) from a carry — the rollback-snapshot / autosave fetch of
        dft/recovery.py. Called OUTSIDE the scf::fused_step profile span:
        it is an explicit, supervised host transfer, not per-iteration
        traffic."""
        x = join_cplx(carry.x_re, carry.x_im)
        if not with_history:
            return x, None
        m = int(np.asarray(carry.count))
        hist = {}
        if m > 0:
            hist["mix_x"] = join_cplx(carry.hx_re, carry.hx_im)[:m]
            hist["mix_f"] = join_cplx(carry.hf_re, carry.hf_im)[:m]
        return x, hist

    def fetch_potential(self, carry: FusedCarry):
        """Host copy of the carried potential in the shape init_carry
        takes (veff_g / bz_g) — for re-seeding a carry in another
        precision."""
        from types import SimpleNamespace

        return SimpleNamespace(
            veff_g=join_cplx(carry.veff_re, carry.veff_im),
            bz_g=join_cplx(carry.bz_re, carry.bz_im)
            if self.polarized else None)

    def step(self, carry, acc, dm_re, dm_im, ev, occ_w, ent, pr, pi):
        """One fused iteration. acc: [ns, coarse box] occupation-weighted
        |psi(r)|^2 from density_kset; (dm_re, dm_im): [ns, nbeta, nbeta]
        from density_matrix_kset (empty for norm-conserving); ev: [nk, ns,
        nb] eigenvalues; occ_w = occ * kweights; ent: entropy sum;
        (pr, pi): [nk, ns, nb, ngk] band block (already live on device for
        the density matrix — feeding it here adds no transfer) for the
        numerics ledger. All device arrays. Returns (new_carry, out_dict)."""
        return self._step(self.tables, carry, acc, dm_re, dm_im, ev,
                          occ_w, ent, pr, pi)

    def finalize(self, carry, out) -> dict:
        """The single end-of-loop host fetch: mixed density, D matrices,
        density-matrix blocks and residual for the final report/forces."""
        ctx = self.ctx
        x = join_cplx(carry.x_re, carry.x_im)
        rho_g = x[: self.ng]
        mag_g = x[self.ng :] if self.polarized else None
        d_by_spin = list(np.asarray(out["dion"], dtype=np.float64))
        rho_resid_g = join_cplx(out["resid_re"], out["resid_im"])
        dm_blocks_by_spin = []
        if self.has_aug:
            dm = join_cplx(out["dm_re"], out["dm_im"])
            for ispn in range(self.ns):
                dm_blocks_by_spin.append([
                    dm[ispn, off : off + nbf, off : off + nbf]
                    for _, off, nbf in ctx.beta.atom_blocks(ctx.unit_cell)
                ])
        return {
            "rho_g": rho_g,
            "mag_g": mag_g,
            "d_by_spin": d_by_spin,
            "rho_resid_g": rho_resid_g,
            "dm_blocks_by_spin": dm_blocks_by_spin,
        }


# -- the compiled program ------------------------------------------------


def _step_impl(rec: StepConstants, tables, carry, acc, dm_re, dm_im, ev,
               occ_w, ent, pr, pi):
    """One fused iteration, traced: FusedScf.step's arguments behind the
    record of its constants and the tables. It reads nothing else, so the
    program is a function of `rec` and the inputs' avals alone."""
    # runs where JAX traces the body, not where a table was asked: the
    # tracing job's count of what was really built
    counters["num_fused_step_traces"] += 1
    ng, ns, omega = rec.ng, rec.ns, rec.omega
    cdt, rdt = rec.cdt, rec.rdt
    xc = XCFunctional(list(rec.xc))

    # The step_* scopes name the stages for a capture's scope table
    # (obs/device_scopes.py; generate_potential_device holds
    # step_hartree, step_xc and step_vloc): metadata of the emitted
    # operations only, the traced order is the one it always was.
    with jax.named_scope("step_density"):
        # density_from_coarse_acc, traced: 1/Omega, coarse r -> coarse G,
        # scatter onto the fine sphere
        acc = acc.astype(rdt)
        rho_c = r_to_g(
            (acc / omega).astype(cdt), tables["fft_index_coarse"],
            rec.dims_coarse,
        )
        rho_spin = jnp.zeros((ns, ng), dtype=cdt).at[:, tables["c2f"]].set(
            rho_c
        )

        dm = jax.lax.complex(dm_re.astype(rdt), dm_im.astype(rdt))
        if rec.has_aug:
            if rec.do_symmetrize:
                dm = symmetrize_density_matrix_device(dm, tables["dm_sym"])
            rho_spin = rho_spin + rho_aug_g_device(dm, tables["aug"], ng)

        rho_new = jnp.sum(rho_spin, axis=0)
        mag_new = rho_spin[0] - rho_spin[1] if rec.polarized else None
        nel_got = jnp.real(rho_new[0]) * omega
        # A 32-bit step accumulates the electron count to a few eps of
        # it (band norms, the FFT's 1/N), differently in every iteration,
        # and the occupations were solved for the count itself: where what
        # was accumulated is the count to that rounding, the G = 0
        # component is set to it (the 54-atom cell converges in 14
        # iterations on the chip with this and in 17 without, PERF.md,
        # PR 27). A count further off is not rounding but a fault (a lost
        # band norm, wrong occupations, a wrong augmentation charge): it
        # stays in the density, in S_NEL and in the energy. A 64-bit step,
        # like the host tail, carries what it accumulated.
        if hilo.compensated(rdt):
            near = jnp.abs(nel_got - rec.nel) <= rec.charge_tol
            rho_new = rho_new.at[0].set(jnp.where(
                near, jnp.asarray(rec.nel / omega, dtype=cdt), rho_new[0]
            ))
        if rec.do_symmetrize:
            rho_new = symmetrize_pw_device(rho_new, tables["sym"])
            if rec.polarized:
                mag_new = symmetrize_pw_device(
                    mag_new, tables["sym"], axial_z=True
                )
        mag_moment = (
            jnp.real(mag_new[0]) * omega if rec.polarized
            else jnp.zeros((), dtype=rdt)
        )

    with jax.named_scope("step_mixing"):
        # mixing (host-sequence semantics: rms pre-mix, eha post-mix)
        x_new = (
            jnp.concatenate([rho_new, mag_new]) if rec.polarized
            else rho_new
        )
        x_in = jax.lax.complex(carry.x_re, carry.x_im)
        state = DeviceMixerState(
            carry.hx_re, carry.hx_im, carry.hf_re, carry.hf_im,
            carry.count,
        )
        state, x_mixed, rms, eha = device_mix(
            state, x_in, x_new, tables["mixw"], rec.mix_beta, rec.kind,
            rec.max_history,
        )
        # output - input density (scf-corr force)
        resid = rho_new - x_in[:ng]

    # Harris term e1 against the potential this iteration's bands saw
    # (the energy terms are (hi, lo) pairs, core/hilo.py)
    veff_old = jax.lax.complex(carry.veff_re, carry.veff_im)
    e1 = hilo.cdot_scaled(rho_new, veff_old, omega)
    if rec.polarized:
        bz_old = jax.lax.complex(carry.bz_re, carry.bz_im)
        e1 = hilo.add_pairs(e1, hilo.cdot_scaled(mag_new, bz_old, omega))

    # potential from the MIXED density
    rho_mix = x_mixed[:ng]
    mag_mix = x_mixed[ng:] if rec.polarized else None
    pot = generate_potential_device(
        xc, rho_mix, mag_mix, tables["pot"], rec.dims,
        rec.dims_coarse, omega,
        sym_tb=tables["sym"] if rec.do_symmetrize else None,
    )
    veff_new = pot["veff_g"]
    bz_new = pot["bz_g"]
    e2 = hilo.cdot_scaled(rho_new, veff_new, omega)
    if rec.polarized:
        e2 = hilo.add_pairs(e2, hilo.cdot_scaled(mag_new, bz_new, omega))
    v0 = jnp.real(veff_new[0])

    with jax.named_scope("step_d_matrix"):
        # next iteration's D matrices and H diagonal
        if rec.has_aug:
            ds = []
            for s in range(ns):
                if rec.polarized:
                    vs = veff_new + (bz_new if s == 0 else -bz_new)
                else:
                    vs = veff_new
                ds.append(
                    d_operator_device(vs, tables["dion"], tables["aug"],
                                      omega)
                )
            dion_new = jnp.stack(ds)
        else:
            dion_new = jnp.broadcast_to(
                tables["dion"][None], (ns,) + tables["dion"].shape
            )
        h_diag = compute_h_diag_device(
            tables["ekin"], tables["gmask"], tables["beta_re"],
            tables["beta_im"], dion_new, v0,
        )

    with jax.named_scope("step_ledger"):
        # ---- numerics ledger: per-iteration invariants, same record ----
        # Note the choice of invariants: quantities whose exact value is
        # known (I, 0) so the scalar directly reads as accumulated rounding
        # + algorithmic drift. The Gram matrix itself and the density
        # matrix are hermitian BITWISE in IEEE arithmetic (conjugate-mirror
        # products round identically), so their asymmetry is useless; the
        # chained-GEMM subspace H_nl below is not mirror-exact and does
        # measure rounding. dion here is the BARE table (not dion_new):
        # host and device then score the identical quantity regardless of
        # where each path is in its D-refresh cycle.
        psi_c = jax.lax.complex(
            pr.astype(rdt), pi.astype(rdt)
        ) * tables["gmask"][:, None, None, :]
        beta_c = jax.lax.complex(
            tables["beta_re"].astype(rdt), tables["beta_im"].astype(rdt),
        )
        qmat_r = tables["qmat"].astype(rdt)
        bp = jnp.einsum("kxg,ksbg->ksbx", jnp.conj(beta_c), psi_c)
        gram = jnp.einsum("ksbg,kscg->ksbc", jnp.conj(psi_c), psi_c)
        gram = gram + jnp.einsum(
            "ksbx,xy,kscy->ksbc", jnp.conj(bp), qmat_r, bp
        )
        nb = psi_c.shape[2]
        s_ortho = jnp.max(jnp.abs(gram - jnp.eye(nb, dtype=gram.dtype)))
        s_chg = jnp.abs(
            jnp.real(x_mixed[0]) - jnp.real(x_new[0])
        ) * omega
        if rec.do_symmetrize:
            s_sym = jnp.max(jnp.abs(
                symmetrize_pw_device(rho_new, tables["sym"]) - rho_new
            ))
        else:
            s_sym = jnp.zeros((), dtype=rdt)
        dion_r = tables["dion"].astype(rdt)
        h_nl = jnp.einsum("ksbx,xy,kscy->ksbc", jnp.conj(bp), dion_r, bp)
        s_herm = jnp.max(jnp.abs(
            h_nl - jnp.conj(jnp.swapaxes(h_nl, -1, -2))
        ))

    eval_sum = hilo.dot_scaled(occ_w.astype(rdt), ev.astype(rdt), 1.0)
    e = pot["energies"]
    paired = [e["vha"], e["vxc"], e["vloc"], e["veff"], e["exc"],
              e["bxc"], e1, e2, eval_sum]  # S_PAIRED's order
    # device-side health sentinel (dft/recovery.py): a NaN anywhere in
    # the mixed vector or the new potential collapses every scalar to
    # NaN anyway, but jnp.isfinite makes the check explicit and also
    # catches an Inf confined to a single G component that the energy
    # sums could mask by cancellation
    finite = (
        jnp.all(jnp.isfinite(jnp.real(x_mixed)))
        & jnp.all(jnp.isfinite(jnp.imag(x_mixed)))
        & jnp.all(jnp.isfinite(jnp.real(veff_new)))
        & jnp.all(jnp.isfinite(jnp.imag(veff_new)))
        & jnp.all(jnp.isfinite(ev))
    ).astype(rdt)
    scalars = jnp.stack([
        rms, eha, *[hi for hi, _ in paired], nel_got, mag_moment, v0,
        ent.astype(rdt), finite,
        s_ortho, s_chg, s_sym, s_herm,
        *[lo for _, lo in paired],  # the second words
    ])

    if rec.polarized:
        bz_re, bz_im = jnp.real(bz_new), jnp.imag(bz_new)
    else:
        bz_re = bz_im = jnp.zeros(ng, dtype=rdt)
    new_carry = FusedCarry(
        jnp.real(x_mixed), jnp.imag(x_mixed),
        state.hx_re, state.hx_im, state.hf_re, state.hf_im, state.count,
        jnp.real(veff_new), jnp.imag(veff_new), bz_re, bz_im,
    )
    out = {
        "scalars": scalars,
        "veff_r_coarse": pot["veff_r_coarse"],
        "dion": dion_new,
        "h_diag": h_diag,
        "dm_re": jnp.real(dm),
        "dm_im": jnp.imag(dm),
        "resid_re": jnp.real(resid),
        "resid_im": jnp.imag(resid),
    }
    return new_carry, out
