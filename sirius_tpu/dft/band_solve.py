"""The band solve of the SCF loop behind one seam.

`choose` decides once, at set-up, which of five solvers a run gets, from
what it can observe (the k-set, the devices, the footprints, the terms of
the Hamiltonian); `degrade` is the OOM ladder's swap to the chunked
projectors mid-run. The loop of dft/scf.py holds one solver and does not
know which. Each solver owns the state only its path uses and answers the
same questions:

- ``solve(inputs, res_tol, wf_dtype, tail_rdt)`` -> ``BandOut``; the first
  iteration's LCAO rotation, a warm start from complex wave functions, the
  re-cast on a precision switch and the H-application counters are inside;
- ``density_acc(occ_w)``: the coarse-box density accumulator off the
  solver's own storage, or None: the tail then uses
  ``generate_density_g(ctx, host_psi())``; ``density_route()``: what the
  loop books of it (counters.num_density_rows, the scf.density span);
- ``book()``: the counters of the solves since the last call, from the steps
  and chunks each ran (device integers until then: the loop calls it where
  it fetches anyway, at its end);
- ``host_psi()`` (autosave, Hubbard occupations, finalize),
  ``restart(psi_big)`` / ``load(psi)`` (recovery, resume, warm start),
  ``rescue(...)`` (the stagnation sentinel), ``after_solve(t0, it)``;
- what the result reports: ``name`` (``name_fused`` under the fused tail),
  ``feeds_fused``, ``mesh``, ``gshard_devices``, ``placed()``, and
  ``plan(wf_dtype)``: the solver's fields of the ``scf.setup`` span (the
  k-set program's size; nothing elsewhere).

None of the five can go: ``batched`` and ``gamma`` each win where the code
sends them, ``gshard`` and ``beta_chunked`` are each the only path for an
input, ``serial`` is the reference the tests compare against."""

from __future__ import annotations

import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from sirius_tpu import runtime
from sirius_tpu.obs import metrics as obs_metrics
from sirius_tpu.obs import spans as obs_spans
from sirius_tpu.ops.hamiltonian import apply_h_s, make_hk_params, real_dtype_of
from sirius_tpu.parallel.batched import (
    compute_h_diag,
    compute_o_diag,
    join_cplx,
    split_cplx,
)
from sirius_tpu.solvers import subspace_eigh
from sirius_tpu.solvers.davidson import (
    count_applies,
    count_solve,
    davidson,
    num_applies,
)
from sirius_tpu.utils.profiler import counters


class Inputs(NamedTuple):
    """What one band solve is given: the host potential with its screened D
    (first iteration, host tail, the iteration after a rollback), or the
    fused step's device outputs (veff_r_coarse, dion, h_diag)."""

    pot: object = None
    d_by_spin: list | None = None
    v0: float = 0.0
    fused_out: dict | None = None
    vhub: np.ndarray | None = None  # per-k Hubbard apply matrices


class BandOut(NamedTuple):
    """ev [nk, ns, nb]: a device array in the fused tail's dtype where that
    tail follows, host float64 otherwise; rn: exit residual norms; (pr, pi):
    the device-resident (re, im) band block, None where psi is on the host."""

    ev: object
    rn: object
    pr: object = None
    pi: object = None


def up(x, device, dtype=None):
    """Upload one band-solve operand to the single compute device (the
    one-device twin of the mesh placements)."""
    if not isinstance(x, jax.Array):
        x = np.asarray(x)
    if dtype is not None and x.dtype != np.dtype(dtype):
        x = x.astype(dtype)
    return jax.device_put(x, device)


def _rtol(res_tol, rdt):
    # typed scalar: a python float would enter the band-solve program as a
    # (weak) f64 parameter whatever the working precision
    return np.dtype(rdt).type(res_tol)


def _h_o_diag(ctx, ik: int, v0: float, dmat: np.ndarray):
    """Diagonals of H and S for the preconditioner at one k — same formulas
    as the production k-set path, by construction."""
    h = compute_h_diag(ctx, np.asarray(dmat)[None], v0)[ik, 0]
    o = compute_o_diag(ctx)[ik]
    return h, o


def _subspace_rotate_host(x, hx, sx, nb):
    """Host wrapper over the shared solvers.davidson.subspace_rotate."""
    from sirius_tpu.solvers.davidson import subspace_rotate

    return np.asarray(
        subspace_rotate(jnp.asarray(x), jnp.asarray(hx), jnp.asarray(sx), nb)
    )


def _lcao_rotate(apply, params, xb, wf_dtype, nb):
    """The full atomic-orbital block xb rotated down to the lowest nb Ritz
    vectors through one H/S application (reference initialize_subspace)."""
    hx, sx = apply(params, jnp.asarray(xb, dtype=wf_dtype))
    return _subspace_rotate_host(
        xb, np.asarray(hx, dtype=np.complex128),
        np.asarray(sx, dtype=np.complex128), nb,
    )


def _hk_params(cache, ctx, hub, ik, veff_r, dmat, dtype, vhub_s=None):
    """Per-(k, dtype) Hamiltonian parameters: only veff_r/dion/vhub change
    between iterations, everything else is uploaded once via _replace."""
    key = (ik, dtype)
    if key not in cache:
        cache[key] = make_hk_params(
            ctx, ik, veff_r, dmat, dtype=dtype,
            hub_phi=None if hub is None else hub.phi_s_gk[ik],
            vhub=vhub_s,
        )
        return cache[key]
    rdt = real_dtype_of(dtype)
    return cache[key]._replace(
        veff_r=jnp.asarray(veff_r, dtype=rdt),
        dion=jnp.asarray(dmat if dmat is not None else ctx.beta.dion, dtype=rdt),
        vhub=None if vhub_s is None else jnp.asarray(vhub_s, dtype=dtype),
    )


class _Booked:
    """What every solver does with the steps and chunks a solve ran: they
    leave davidson() as device integers and are held here, so that no solve
    costs a fetch; book() fetches them and books the H applications
    (reference num_loc_op_applied counter), the FFT boxes behind them, the
    subspace eigenproblems and the steps (davidson.count_solve)."""

    rows_per_box = 1
    # complex Hermitian subspace matrices: every solver but the packed-real
    # Gamma one
    complex_subspace = True

    def _rule(self, itsol):
        # the most steps a solve takes, and what res_tol bars: a step's
        # move of the eigenvalue or the residual norm (davidson())
        self.num_steps = itsol.num_steps
        self.by_energy = bool(itsol.converge_by_energy)
        self._unbooked: list = []

    def _ran(self, ran, copies=1):
        # one solve of the whole (k, spin) set: `ran` an array or a list of
        # them, `copies` the (k, spin) lanes behind each of its rows
        self._unbooked.append((ran, copies))

    def last_cost(self, ngk, nbeta, box):
        """obs/costs.band_solve_cost of the newest solve (a fetch of its
        steps and chunks: for a caller that has the solve's values)."""
        from sirius_tpu.obs.costs import band_solve_cost

        ran, copies = jax.device_get(self._unbooked[-1])
        return band_solve_cost(self.ctx.num_bands, ngk, nbeta, box, ran,
                               copies=copies)

    def density_route(self) -> tuple[int, dict]:
        """(band rows one density_acc carries sphere -> box through the
        cube's products: counters.num_density_rows, the fields of its
        scf.density span); a solver that does not take that route books an
        explicit 0 and no field."""
        return 0, {}

    def book(self):
        unbooked, self._unbooked = self._unbooked, []
        for ran, copies in jax.device_get(unbooked):
            count_solve(counters, ran, self.ctx.num_bands, copies=copies,
                        rows_per_box=self.rows_per_box,
                        complex_subspace=self.complex_subspace)


def _host_evals(ctx, ev_by_spin):
    """Host float64 eigenvalues [1, ns, nb] of a single-k solve."""
    evals = np.zeros((1, ctx.num_spins, ctx.num_bands))
    for ispn, ev in enumerate(ev_by_spin):
        evals[0, ispn] = np.asarray(ev)
    return evals


def generic_kpoints(kpoints) -> np.ndarray:
    """[nk] bool: the k-points (fractional) that are not their own -k, 2k
    being no reciprocal lattice vector (the ``kset.generic_kpoints`` field
    of the ``scf.setup`` span)."""
    two_k = 2.0 * np.asarray(kpoints, dtype=np.float64).reshape(-1, 3)
    return np.abs(two_k - np.rint(two_k)).max(axis=1) > 1e-9


class KsetSolver(_Booked):
    """Production path: the whole (k, spin) set as ONE program
    (parallel/batched.py; shards over the ("k", "b") mesh). Real-boundary:
    psi crosses the jit boundary as a (re, im) pair and stays device-
    resident between iterations. The subspace eigenproblems are complex
    Hermitian whatever the k-points are: one program a shape. Gamma alone
    has GammaSolver; where that is refused (several devices, reduce_gvec
    off) the deck runs this program too."""

    name = "batched"
    name_fused = "batched+fused"  # the result's path word under the fused tail
    feeds_fused = True
    gshard_devices = 0

    def __init__(self, ctx, cfg, devs, mesh, psi_spec, hub, mgga):
        self.ctx, self.dev, self.mesh, self.mgga = ctx, devs[0], mesh, mgga
        self._rule(cfg.iterative_solver)
        self.hub_phi = None if hub is None else np.stack(
            [hub.phi_s_gk[ik] for ik in range(ctx.gkvec.num_kpoints)])
        self._cache: dict = {}  # dtype -> HkSetParams, constant tables cached
        self._gkc: dict = {}
        self.ps = self.rdt = None
        self.psi = self.psi_big = self.pr = self.pi = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            self._psi_sharding = NamedSharding(mesh, psi_spec)

    def _place_psi(self, x):
        if self.mesh is not None:
            return jax.device_put(x, self._psi_sharding)
        return up(x, self.dev)

    def _block_shard(self):
        """The shape of one device's shard of the [nk, ns, nb, ngk] block."""
        ctx = self.ctx
        block = (ctx.gkvec.num_kpoints, ctx.num_spins, ctx.num_bands,
                 int(ctx.gkvec.ngk_max))
        if self.mesh is not None:
            block = self._psi_sharding.shard_shape(block)
        return block

    def plan(self, wf_dtype) -> dict:
        """Fields of the ``scf.setup`` span: the size of the one program.
        The whole (k, spin) set goes through one vmap with no chunk over k,
        so ``workspace_bytes`` is one temporary of one H application to
        [X; P], a coarse FFT box a row, on one device (the compiler keeps
        several of them; the peak is a small multiple). ``local_rows``:
        the rows one box transform of the local operator carries on one
        device, in a step and at a chunk boundary ([X; P]), read off the
        shard of the [nk, ns, nb, ngk] block a device is given: under the
        vmap over k the k-points of a spin channel go through together,
        rows on the minor axis (``local_layout``: the one form the k-set
        programs have, ops/local.py). ``generic_kpoints``: the k-points
        solved that are not their own -k; ``weights``: the distinct
        k-weights in units of the smallest share, one point of the mesh (or
        of an explicit list)."""
        ctx = self.ctx
        nk, nb = ctx.gkvec.num_kpoints, ctx.num_bands
        p = ctx.cfg.parameters
        mesh_points = len(p.vk) or int(np.prod(p.ngridk))
        ndev = 1 if self.mesh is None else self.mesh.size
        rows = nk * ctx.num_spins * 2 * nb
        block = self._block_shard()
        local_rows = block[0] * block[2]
        return {"kset": {
            "nk": nk, "ngk_max": int(ctx.gkvec.ngk_max),
            "subspace_rows": 3 * nb,
            "generic_kpoints": int(generic_kpoints(ctx.gkvec.kpoints).sum()),
            "weights": sorted({int(round(float(w) * mesh_points))
                               for w in ctx.kweights}),
            "workspace_bytes": rows * int(np.prod(ctx.fft_coarse.dims))
            * np.dtype(wf_dtype).itemsize // ndev,
            "local_rows": [local_rows, 2 * local_rows],
            "local_layout": "rows_minor",
            # who diagonalises the subspace matrices, by the rule the
            # program's own choice comes from, and what one call carries on
            # one device (its k-points x spin channels)
            "subspace_eigh": {
                "form": subspace_eigh.form(wf_dtype, self.dev.platform),
                "rows": 3 * nb, "batch": block[0] * block[1]},
        }}

    def _gkc_dev(self, rdt):
        """Device-resident cartesian G+k components [nk, ngk, 3] for the
        mGGA tau operator, uploaded once per working precision."""
        key = str(rdt)
        if key not in self._gkc:
            self._gkc.clear()  # drop the stale-precision copy
            self._gkc[key] = jnp.asarray(self.ctx.gkvec.gkcart, dtype=rdt)
        return self._gkc[key]

    def _params(self, veff_stack, d_stack, v0, vhub_s, dtype):
        """Batched-path parameters with cached constant tables (only the
        potential-dependent leaves are re-uploaded per iteration)."""
        from sirius_tpu.parallel.batched import make_hkset_params

        rdt = real_dtype_of(dtype)
        cache = self._cache
        if dtype not in cache:
            # a lower-precision entry is dead after the fp32->fp64 polish
            # switch: evict it so two full projector stacks never coexist
            cache.clear()
            cache[dtype] = make_hkset_params(
                self.ctx, veff_stack, d_stack, dtype=dtype, v0=v0,
                hub_phi=self.hub_phi, vhub=vhub_s,
            )
            return cache[dtype]
        h_diag = compute_h_diag(self.ctx, np.asarray(d_stack), v0)
        vh = (None, None) if vhub_s is None else split_cplx(vhub_s, rdt)
        # store the refreshed params back so the previous iteration's
        # potential-dependent device buffers are released
        cache[dtype] = cache[dtype]._replace(
            veff_r=jnp.asarray(veff_stack, dtype=rdt),
            dion=jnp.asarray(d_stack, dtype=rdt),
            h_diag=jnp.asarray(h_diag, dtype=rdt),
            vhub_re=None if vh[0] is None else jnp.asarray(vh[0]),
            vhub_im=None if vh[1] is None else jnp.asarray(vh[1]),
        )
        return cache[dtype]

    def solve(self, inputs, res_tol, wf_dtype, tail_rdt=None):
        from sirius_tpu.parallel.batched import davidson_kset
        from sirius_tpu.parallel.mesh import place_kset_params

        ctx, nb = self.ctx, self.ctx.num_bands
        ns = ctx.num_spins
        rdt = self.rdt = real_dtype_of(wf_dtype)
        fo = inputs.fused_out
        if fo is not None and wf_dtype in self._cache:
            # device-resident refresh: the fused step already produced
            # veff_r/D/h_diag on device — swap them into the cached params
            # without any host round-trip
            self._cache[wf_dtype] = self._cache[wf_dtype]._replace(
                veff_r=fo["veff_r_coarse"].astype(rdt),
                dion=fo["dion"].astype(rdt),
                h_diag=fo["h_diag"].astype(rdt),
            )
            ps = self._cache[wf_dtype]
        elif fo is not None:
            # precision switch (fp32 -> fp64 polish): one-time host fetch
            # to build the new-precision constant tables
            ps = self._params(
                np.asarray(fo["veff_r_coarse"]), np.asarray(fo["dion"]),
                inputs.v0, inputs.vhub, wf_dtype,
            )
        else:
            ps = self._params(
                inputs.pot.veff_r_coarse[:ns], np.stack(inputs.d_by_spin),
                inputs.v0, inputs.vhub, wf_dtype,
            )
        ps = self.ps = place_kset_params(ps, self.mesh, self.dev)
        pr, pi = self.pr, self.pi
        if pr is None and self.psi is None and self.psi_big is not None:
            # first iteration from a fresh LCAO block: rotate the full
            # atomic-orbital subspace down to the lowest nb Ritz vectors
            # (reference initialize_subspace.hpp:279)
            from sirius_tpu.parallel.batched import initialize_subspace_kset

            pb_re, pb_im = split_cplx(self.psi_big, rdt)
            if self.mesh is not None:
                # the LCAO block has nbig >= nb orbitals — shard it over
                # "k" only (nbig need not divide the band axis)
                from jax.sharding import NamedSharding, PartitionSpec

                _big = NamedSharding(
                    self.mesh, PartitionSpec("k", None, None, None))
                pb_re = jax.device_put(jnp.asarray(pb_re), _big)
                pb_im = jax.device_put(jnp.asarray(pb_im), _big)
            pr, pi = initialize_subspace_kset(
                ps, jnp.asarray(pb_re), jnp.asarray(pb_im), nb, mesh=self.mesh,
            )
            pr, pi = self._place_psi(pr), self._place_psi(pi)
            count_applies(counters, [(self.psi_big.shape[2], 1)],
                          copies=ctx.gkvec.num_kpoints * ns)
            self.psi_big = None
        if pr is None or pr.dtype != np.dtype(rdt):
            # initial entry or precision switch
            src = self.psi if self.psi is not None else join_cplx(pr, pi)
            pr, pi = split_cplx(np.asarray(src), rdt)
            pr = self._place_psi(jnp.asarray(pr))
            pi = self._place_psi(jnp.asarray(pi))
        if self.mgga and inputs.pot.vtau_r_coarse is not None:
            from sirius_tpu.ops.mgga import davidson_kset_mgga

            ev, pr, pi, rn, ran = davidson_kset_mgga(
                ps, jnp.asarray(inputs.pot.vtau_r_coarse, dtype=rdt),
                self._gkc_dev(rdt), pr, pi,
                num_steps=self.num_steps,
                res_tol=res_tol, by_energy=self.by_energy,
            )
        else:
            ev, pr, pi, rn, ran = davidson_kset(
                ps, pr, pi,
                num_steps=self.num_steps,
                res_tol=_rtol(res_tol, rdt), mesh=self.mesh,
                by_energy=self.by_energy,
            )
        # canonicalize the pair onto the explicit psi sharding (a no-op
        # when GSPMD already placed it there): downstream consumers must
        # see the SAME placement whether psi came from this solve or from a
        # mid-SCF resume warm start, or the executables (and their
        # reduction orders) differ and break bit-reproducible resume
        self.pr, self.pi = self._place_psi(pr), self._place_psi(pi)
        # the complex host copy is materialized only for consumers that
        # need it (host_psi: Hubbard occupations each iteration,
        # forces/stress/checkpoint after the loop)
        self.psi = None
        self._ran(ran, copies=ns)
        # with the fused tail the eigenvalues stay on device; the host copy
        # is fetched once after the loop for the final report
        return BandOut(
            ev.astype(tail_rdt) if tail_rdt is not None
            else np.asarray(ev, dtype=np.float64),
            rn, self.pr, self.pi)

    def density_acc(self, occ_w):
        from sirius_tpu.parallel.batched import density_kset

        return density_kset(self.ps, self.pr, self.pi, occ_w, mesh=self.mesh)

    def density_route(self):
        # every row of the set goes through the one form density_kset has;
        # ``rows``: what one call carries on one device, off its shard
        ctx = self.ctx
        nk, ns, nb, _ = self._block_shard()
        return (ctx.gkvec.num_kpoints * ctx.num_spins * ctx.num_bands,
                {"form": "rows_minor", "rows": nk * ns * nb,
                 "cube": list(self.ps.cube.shape[1:])})

    def tau_acc(self, occ_w):
        """Coarse-box kinetic-energy density of the block (mGGA)."""
        from sirius_tpu.ops.mgga import tau_kset

        return tau_kset(
            self.ps.fft_index, self._gkc_dev(self.rdt), self.pr, self.pi,
            occ_w, tuple(self.ctx.fft_coarse.dims),
        )

    def host_psi(self):
        if self.pr is not None:
            return join_cplx(self.pr, self.pi)
        return self.psi

    def restart(self, psi_big):
        self.psi = self.pr = self.pi = None
        self.psi_big = psi_big

    def load(self, psi):
        self.restart(None)
        self.psi = psi

    def rescue(self, inputs, out, res_tol):
        """One deeper retry, warm-started from the stagnated block (a static
        bound means this compiles once and is then cached)."""
        if self.mgga:
            return None
        from sirius_tpu.parallel.batched import davidson_kset

        ev, self.pr, self.pi, rn, _ = davidson_kset(
            self.ps, self.pr, self.pi, num_steps=2 * self.num_steps,
            res_tol=res_tol, mesh=self.mesh, by_energy=self.by_energy,
        )
        return BandOut(np.asarray(ev, dtype=np.float64), rn, self.pr, self.pi)

    def after_solve(self, t0, it):
        pass

    def placed(self):
        return self.pr if self.pr is not None else self.psi


class GammaSolver(_Booked):
    """Gamma-point real-storage band solve on one device (ops/gamma.py;
    reference reduce_gvec, wave_functions.hpp:1589-1626): packed-real
    vectors make the solver's GEMMs/eigh real, two bands share a box."""

    name = "gamma"
    complex_subspace = False  # packed-real vectors: real symmetric matrices
    name_fused = "gamma"  # one word under both tails (the parent's record)
    feeds_fused = True
    gshard_devices = 0
    mesh = None

    def __init__(self, ctx, cfg, devs):
        from sirius_tpu.ops.gamma import ROWS_PER_BOX

        self.ctx, self.dev = ctx, devs[0]
        self.rows_per_box = ROWS_PER_BOX  # two real bands share a box
        self._rule(cfg.iterative_solver)
        self.gm = ctx.gamma_map()  # the lattice's, read-only
        self.x_packed: list = [None] * ctx.num_spins
        self._cache: dict = {}  # rdtype -> constant-table GammaParams
        self.rdt = None
        self.psi = self.psi_big = self.pr = self.pi = None

    def _up(self, x, dtype=None):
        return up(x, self.dev, dtype)

    def plan(self, wf_dtype) -> dict:
        return {}

    def solve(self, inputs, res_tol, wf_dtype, tail_rdt=None):
        from sirius_tpu.ops import gamma as gmod

        ctx, gm = self.ctx, self.gm
        nb, ns = ctx.num_bands, ctx.num_spins
        _up = self._up
        rdt = self.rdt = real_dtype_of(wf_dtype)
        x_packed = self.x_packed
        if x_packed[0] is not None and x_packed[0].dtype != np.dtype(rdt):
            # fp32 -> fp64 polish: re-cast the packed block
            x_packed = [_up(x, rdt) for x in x_packed]
        if self.psi is not None and x_packed[0] is None:
            # restart / warm start from full complex psi
            x_packed = [
                _up(gmod.pack(gm, np.asarray(self.psi[0, ispn])), rdt)
                for ispn in range(ns)
            ]
        if rdt not in self._cache:
            # constant tables (packed beta, gather maps, the packed S
            # diagonal and the gather of pack_diags_device) uploaded once
            # per precision; per-iteration leaves are swapped in below
            self._cache.clear()
            self._cache[rdt] = jax.tree_util.tree_map(_up, (
                gmod.make_gamma_params(
                    ctx, np.zeros(ctx.fft_coarse.dims), gm, rdtype=rdt),
                gmod.pack_index(gm, ctx.gkvec.ngk_max),
                np.asarray(compute_o_diag(ctx)[0], dtype=rdt)))
        gp0, pidx, o_diag_dev = self._cache[rdt]
        # once the fused step has run, the potential, the screened D and
        # the H diagonal of the next solve are its outputs, already on the
        # device; the host potential feeds the first iteration and the one
        # after a rollback
        dev_inputs = None
        if inputs.fused_out is not None:
            fo = inputs.fused_out
            dev_inputs = gmod.solve_inputs_device(
                pidx, gp0.mask_p, o_diag_dev,
                fo["veff_r_coarse"], fo["dion"], fo["h_diag"])
        ev_spin, ran = [], []
        for ispn in range(ns):
            if dev_inputs is not None:
                veff_s, dion_s, hd_p, od_p = dev_inputs[ispn]
            else:
                d_s = inputs.d_by_spin[ispn]
                veff_s = _up(inputs.pot.veff_r_coarse[ispn], rdt)
                dion_s = _up(np.real(d_s), rdt)
                h_diag = compute_h_diag(
                    ctx, np.asarray(d_s)[None], inputs.v0)[0, 0]
                hd_p, od_p = gmod.pack_diags_device(
                    pidx, gp0.mask_p, _up(h_diag, rdt), o_diag_dev)
            gp = gp0._replace(veff_r=veff_s, dion=dion_s)
            if x_packed[ispn] is None:
                # first iteration: rotate the packed LCAO block to the
                # lowest nb Ritz vectors (initialize_subspace)
                x_packed[ispn] = gmod.initialize_subspace_gamma(
                    gp, _up(gmod.pack(gm, self.psi_big[0, ispn]), rdt), nb)
                count_applies(counters, [(self.psi_big.shape[2], 1)],
                              rows_per_box=gmod.ROWS_PER_BOX)
            ev, x_packed[ispn], rn, ran_s = gmod.davidson_gamma(
                gp, x_packed[ispn], hd_p, od_p,
                num_steps=self.num_steps,
                res_tol=_up(_rtol(res_tol, rdt)), by_energy=self.by_energy,
            )
            ev_spin.append(ev)
            ran.append(ran_s)
        self.x_packed = x_packed
        self.psi_big = None
        self._ran(ran)
        if tail_rdt is not None:
            # the packed block and the eigenvalues stay on the device; the
            # fused tail takes the band block as the (re, im) pair of its
            # sphere coefficients, and the host complex psi is joined from
            # that pair once, after the loop (or by an autosave)
            self.pr, self.pi = (a[None] for a in gmod.unpack_device(
                gp0, jnp.stack(x_packed)))
            self.psi = None
            return BandOut(jnp.stack(ev_spin)[None].astype(tail_rdt), rn,
                           self.pr, self.pi)
        self.psi = np.zeros(
            (1, ns, nb, ctx.gkvec.ngk_max), dtype=np.complex128
        )
        for ispn in range(ns):
            self.psi[0, ispn] = gmod.unpack(gm, np.asarray(x_packed[ispn]))
        return BandOut(_host_evals(ctx, ev_spin), rn)

    def density_acc(self, occ_w):
        """Real field per band, |Re psi(r)|^2 off the packed block, where
        the fused tail follows; the host tail goes through host_psi()."""
        if self.pr is None:
            return None
        from sirius_tpu.ops.gamma import density_gamma

        return density_gamma(
            self._cache[self.rdt][0], jnp.stack(self.x_packed),
            occ_w.reshape(self.ctx.num_spins, self.ctx.num_bands))

    def host_psi(self):
        if self.pr is not None:
            return join_cplx(self.pr, self.pi)
        return self.psi

    def restart(self, psi_big):
        self.psi = self.pr = self.pi = None
        self.x_packed = [None] * self.ctx.num_spins
        self.psi_big = psi_big

    def load(self, psi):
        self.restart(None)
        self.psi = psi

    def rescue(self, inputs, out, res_tol):
        return None

    def after_solve(self, t0, it):
        pass

    def placed(self):
        return self.x_packed[0]


class GshardSolver(_Booked):
    """G-sharded band solve (slab FFT over a "g" mesh): for a replicated
    projector + wave-function footprint that would not fit a single device.
    Single-k no-U regime — the Si-supercell flagship class."""

    name = "gshard"
    feeds_fused = False

    def __init__(self, ctx, cfg, devs, wf_dtype):
        self.ctx, self.devs = ctx, devs
        self.gshard_devices = len(devs)
        self._rule(cfg.iterative_solver)
        self._hk: dict = {}
        self.psi = self.psi_big = None
        self._setup(wf_dtype)
        if obs_metrics.enabled() and getattr(
                cfg.control, "collective_probe", True):
            # measure each named collective of the sharded apply once, in
            # isolation, at this deck's shapes — the per-iteration
            # compute/collective split of scf.band_solve scales these by
            # the analytic H-application row count
            try:
                from sirius_tpu.parallel.dist_fft import probe_collectives

                _pbatch = max(1, min(ctx.num_bands, 64))
                self.probe = {
                    "batch": _pbatch,
                    "per_call": probe_collectives(
                        self.mesh, tuple(ctx.fft_coarse.dims), _pbatch,
                        nbeta=int(ctx.beta.num_beta_total),
                        ngk=int(self.order.size), dtype=wf_dtype,
                        reps=2),
                }
            except Exception:
                self.probe = None

    def _setup(self, dtype):
        """Tables and the sharded apply in one working precision (again at
        the fp32 -> fp64 polish; the serial path gets this from the
        (ik, dtype)-keyed _hk_params cache)."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from sirius_tpu.parallel.dist_fft import (
            gshard_partition,
            make_apply_h_s_gshard,
            reorder_to_gshard,
        )

        ctx, ndev = self.ctx, len(self.devs)
        dims = ctx.fft_coarse.dims
        self.mesh = Mesh(np.array(self.devs).reshape(ndev), ("g",))
        mill0 = np.asarray(ctx.gkvec.millers[0])
        self.order, g_lidx, _ = gshard_partition(mill0, dims, ndev)
        prm0 = _hk_params(self._hk, ctx, None, 0, np.zeros(dims), None, dtype)
        self.fn, self.sharding = make_apply_h_s_gshard(
            self.mesh, dims, g_lidx,
            reorder_to_gshard(np.asarray(prm0.ekin), self.order),
            reorder_to_gshard(np.asarray(prm0.mask), self.order),
            reorder_to_gshard(np.asarray(prm0.beta), self.order),
            np.asarray(prm0.dion), np.asarray(prm0.qmat),
            np.zeros(dims, dtype=real_dtype_of(dtype)),
        )
        self.sh_g = NamedSharding(self.mesh, PartitionSpec("g"))
        self.sh_rep = NamedSharding(self.mesh, PartitionSpec())
        self.mask = jax.device_put(
            reorder_to_gshard(np.asarray(prm0.mask), self.order), self.sh_g)
        self.dtype, self.x, self.probe = dtype, None, None

    def plan(self, wf_dtype) -> dict:
        return {}

    def solve(self, inputs, res_tol, wf_dtype, tail_rdt=None):
        from sirius_tpu.parallel.dist_fft import (
            reorder_from_gshard,
            reorder_to_gshard,
        )

        ctx, nb = self.ctx, self.ctx.num_bands
        pot, d0, v0 = inputs.pot, inputs.d_by_spin[0], inputs.v0
        if self.dtype != wf_dtype:
            self._setup(wf_dtype)
        if self.psi is None and self.psi_big is not None:
            # one-off LCAO subspace init on the replicated path
            params = _hk_params(
                self._hk, ctx, None, 0, pot.veff_r_coarse[0], d0, wf_dtype)
            xb = self.psi_big[0, 0] * np.asarray(ctx.gkvec.mask[0])
            self.psi = np.zeros(
                (1, 1, nb, ctx.gkvec.ngk_max), dtype=np.complex128
            )
            self.psi[0, 0] = _lcao_rotate(apply_h_s, params, xb, wf_dtype, nb)
            count_applies(counters, [(self.psi_big.shape[2], 1)])
            self.psi_big = None
        x0 = self.x
        if x0 is None:
            x0 = jax.device_put(
                reorder_to_gshard(
                    np.asarray(self.psi[0, 0]).astype(wf_dtype), self.order),
                self.sharding,
            )
        h_diag, o_diag = _h_o_diag(ctx, 0, v0, d0)
        hd = reorder_to_gshard(np.asarray(h_diag), self.order)
        od = reorder_to_gshard(np.asarray(o_diag), self.order)
        od[od == 0.0] = 1.0  # padding slots: finite preconditioner
        rdt = real_dtype_of(wf_dtype)
        # every operand placed on the "g" mesh in the working precision (an
        # f64 potential would promote the c64 apply)
        veff_d = jax.device_put(
            np.asarray(pot.veff_r_coarse[0], dtype=rdt),
            self.fn.sharding_veff,
        )
        ev, x, rn, self.last_ran = davidson(
            self.fn,
            (veff_d, jax.device_put(np.asarray(d0, dtype=rdt), self.sh_rep)),
            x0,
            jax.device_put(np.asarray(hd, dtype=rdt), self.sh_g),
            jax.device_put(np.asarray(od, dtype=rdt), self.sh_g),
            self.mask,
            num_steps=self.num_steps,
            res_tol=_rtol(res_tol, rdt), by_energy=self.by_energy,
        )
        self.x = x
        # host round-trip for the density consumer; a device-side gather +
        # sharded density accumulation would avoid it (known cost on this
        # path — the band solve dominates)
        self.psi = jnp.asarray(
            reorder_from_gshard(np.asarray(x), self.order, ctx.gkvec.ngk_max)
        )[None, None]
        self._ran(self.last_ran)
        return BandOut(_host_evals(ctx, [ev]), rn)

    def density_acc(self, occ_w):
        return None

    def host_psi(self):
        return self.psi

    def restart(self, psi_big):
        self.psi = self.x = None
        self.psi_big = psi_big

    def load(self, psi):
        self.restart(None)
        self.psi = psi

    def rescue(self, inputs, out, res_tol):
        return None

    def after_solve(self, t0, it):
        """Split the measured solve wall into collective vs compute: fenced
        per-collective probe costs (probe_collectives, taken once at setup)
        x the analytic H-application row count. A host timer cannot see
        inside the jitted apply, so this is a model (attrs say so) —
        cross-checked by bench_gshard_large against the 1-device baseline."""
        if not self.probe:
            return
        ctx, ndev = self.ctx, len(self.devs)
        dt = time.perf_counter() - t0
        t_ns = time.time_ns() - int(dt * 1e9)
        # this path's tail is the host's: the solve's values are fetched
        # every iteration, its (steps, chunks) with them
        rows = ctx.gkvec.num_kpoints * ctx.num_spins * num_applies(
            *np.asarray(self.last_ran).tolist(), ctx.num_bands)
        coll = sum(
            v for k, v in self.probe["per_call"].items()
            if k != "collective.fft_local"
        ) / self.probe["batch"] * rows
        coll = min(coll, dt)
        # a model's split of the measured interval, not two measurements:
        # recorded from outside, under the band solve
        obs_spans.record("scf.band_solve.collective", coll,
                         start_unix_ns=t_ns, it=it + 1,
                         method="probe", ndev=ndev)
        obs_spans.record("scf.band_solve.compute", dt - coll,
                         start_unix_ns=t_ns + int(coll * 1e9),
                         it=it + 1, method="probe", ndev=ndev)

    def placed(self):
        return self.x


class ChunkedSolver(_Booked):
    """Chunk-generated beta projectors (ops/beta_chunked.py): the H/S
    application rebuilds each atom chunk's beta block on the fly
    (lax.scan), so the dense [nbeta, ngk] table never exists on device.
    Single-k unpolarized no-U regime, like gshard; host tail only."""

    name = "beta_chunked"
    feeds_fused = False
    gshard_devices = 0

    def __init__(self, ctx, cfg, mesh):
        self.ctx, self.control, self.mesh = ctx, cfg.control, mesh
        self._rule(cfg.iterative_solver)
        self.params = self.dtype = None
        self.psi = self.psi_big = None

    def plan(self, wf_dtype) -> dict:
        return {}

    def solve(self, inputs, res_tol, wf_dtype, tail_rdt=None):
        from sirius_tpu.ops.beta_chunked import (
            apply_h_s_chunked,
            make_chunked_hk,
            pack_dmat_chunks,
        )

        ctx, nb = self.ctx, self.ctx.num_bands
        pot, d0 = inputs.pot, inputs.d_by_spin[0]
        chunk = self.control.beta_chunk_size
        rdt = real_dtype_of(wf_dtype)
        if self.dtype != wf_dtype:
            self.params = make_chunked_hk(ctx, 0, dtype=wf_dtype, chunk=chunk)
            self.dtype = wf_dtype
        prm = dict(
            self.params,
            veff_r=jnp.asarray(pot.veff_r_coarse[0], dtype=rdt),
            dmat=jnp.asarray(
                pack_dmat_chunks(ctx, np.real(np.asarray(d0)), chunk),
                dtype=rdt,
            ),
        )
        if self.psi is None and self.psi_big is not None:
            # one-off LCAO subspace init through the chunked apply
            xb = self.psi_big[0, 0] * np.asarray(ctx.gkvec.mask[0])
            self.psi = np.zeros(
                (1, 1, nb, ctx.gkvec.ngk_max), dtype=np.complex128
            )
            self.psi[0, 0] = _lcao_rotate(
                apply_h_s_chunked, prm, xb, wf_dtype, nb)
            count_applies(counters, [(self.psi_big.shape[2], 1)])
            self.psi_big = None
        h_diag, o_diag = _h_o_diag(ctx, 0, inputs.v0, d0)
        ev, x, rn, ran = davidson(
            apply_h_s_chunked, prm,
            jnp.asarray(np.asarray(self.psi[0, 0]), dtype=wf_dtype),
            jnp.asarray(h_diag, dtype=rdt),
            jnp.asarray(o_diag, dtype=rdt),
            jnp.asarray(ctx.gkvec.mask[0], dtype=rdt),
            num_steps=self.num_steps,
            res_tol=res_tol, by_energy=self.by_energy,
        )
        self.psi = np.asarray(x).astype(np.complex128)[None, None]
        self._ran(ran)
        return BandOut(_host_evals(ctx, [ev]), rn)

    def density_acc(self, occ_w):
        return None

    def host_psi(self):
        return self.psi

    def restart(self, psi_big):
        self.psi, self.psi_big = None, psi_big

    def load(self, psi):
        self.psi, self.psi_big = psi, None

    def rescue(self, inputs, out, res_tol):
        return None

    def after_solve(self, t0, it):
        pass

    def placed(self):
        return self.psi


class SerialSolver(_Booked):
    """Per-(k, spin) debug path: the reference the tests compare the
    production paths against."""

    name = "serial"
    feeds_fused = False
    gshard_devices = 0
    mesh = None

    def __init__(self, ctx, cfg, hub):
        self.ctx, self.control, self.hub = ctx, cfg.control, hub
        self._rule(cfg.iterative_solver)
        self._hk: dict = {}
        self.psi = self.psi_big = None

    def _params(self, inputs, ik, ispn, wf_dtype):
        return _hk_params(
            self._hk, self.ctx, self.hub, ik,
            inputs.pot.veff_r_coarse[ispn], inputs.d_by_spin[ispn], wf_dtype,
            vhub_s=None if inputs.vhub is None else inputs.vhub[ik, ispn],
        )

    def plan(self, wf_dtype) -> dict:
        return {}

    def solve(self, inputs, res_tol, wf_dtype, tail_rdt=None):
        ctx, nb = self.ctx, self.ctx.num_bands
        nk, ns = ctx.gkvec.num_kpoints, ctx.num_spins
        if self.psi is None and self.psi_big is not None:
            # first iteration from a fresh LCAO block: rotate the full
            # atomic-orbital subspace down to nb Ritz vectors
            psi0 = np.zeros(
                (nk, ns, nb, ctx.gkvec.ngk_max), dtype=np.complex128
            )
            for ik in range(nk):
                for ispn in range(ns):
                    xb = self.psi_big[ik, ispn] * np.asarray(ctx.gkvec.mask[ik])
                    psi0[ik, ispn] = _lcao_rotate(
                        apply_h_s, self._params(inputs, ik, ispn, wf_dtype),
                        xb, wf_dtype, nb)
            count_applies(counters, [(self.psi_big.shape[2], 1)],
                          copies=nk * ns)
            self.psi, self.psi_big = psi0, None
        rdt = real_dtype_of(wf_dtype)
        evals = np.zeros((nk, ns, nb))
        new_psi, ran = [], []
        for ik in range(nk):
            per_spin = []
            for ispn in range(ns):
                params = self._params(inputs, ik, ispn, wf_dtype)
                h_diag, o_diag = _h_o_diag(
                    ctx, ik, inputs.v0, inputs.d_by_spin[ispn])
                ev, x, rn, ran_ks = davidson(
                    apply_h_s,
                    params,
                    self.psi[ik, ispn].astype(wf_dtype),
                    jnp.asarray(h_diag, dtype=rdt),
                    jnp.asarray(o_diag, dtype=rdt),
                    params.mask,
                    num_steps=self.num_steps,
                    res_tol=res_tol, by_energy=self.by_energy,
                )
                evals[ik, ispn] = np.asarray(ev)
                per_spin.append(x)
                ran.append(ran_ks)
            new_psi.append(jnp.stack(per_spin))
        self.psi = jnp.stack(new_psi)
        self._ran(ran)
        # rn covers the last (k, spin) solve, a proxy that still catches
        # whole-solve stagnation
        return BandOut(evals, rn)

    def density_acc(self, occ_w):
        return None

    def host_psi(self):
        return self.psi

    def restart(self, psi_big):
        self.psi, self.psi_big = None, psi_big

    def load(self, psi):
        self.psi, self.psi_big = psi, None

    def rescue(self, inputs, out, res_tol):
        """Dense diagonalization for small |G+k| spheres (the reference's
        "robust" exact-solver escape hatch); writes out.ev in place."""
        ctx, nb = self.ctx, self.ctx.num_bands
        if int(ctx.gkvec.ngk_max) > int(self.control.exact_diag_max_ngk):
            return None
        from sirius_tpu.solvers.eigen import build_h_s_matrices, exact_diag

        pot = inputs.pot
        try:
            psi_r = np.asarray(self.psi, dtype=np.complex128).copy()
            qmat = (
                None if ctx.beta.qmat is None else np.asarray(ctx.beta.qmat)
            )
            for ik in range(ctx.gkvec.num_kpoints):
                n_gk = int(ctx.gkvec.num_gk[ik])
                gkd = {
                    "millers": np.asarray(ctx.gkvec.millers[ik][:n_gk]),
                    "ekin": np.asarray(ctx.gkvec.kinetic()[ik][:n_gk]),
                }
                bk = (
                    np.asarray(ctx.beta.beta_gk[ik])
                    if ctx.beta.num_beta_total else None
                )
                for ispn in range(ctx.num_spins):
                    vg = np.asarray(pot.veff_g)
                    if ctx.num_mag_dims == 1 and pot.bz_g is not None:
                        vg = vg + np.asarray(
                            pot.bz_g if ispn == 0 else -pot.bz_g
                        )
                    h, s = build_h_s_matrices(
                        gkd, vg, ctx.gvec.index_of_millers,
                        beta_k=bk,
                        dion=np.asarray(inputs.d_by_spin[ispn]),
                        qmat=qmat,
                    )
                    ev_d, vec = exact_diag(h, s, nb)
                    out.ev[ik, ispn] = ev_d
                    psi_r[ik, ispn] = 0.0
                    psi_r[ik, ispn, :nb, :n_gk] = vec.T
            self.psi = psi_r
            return out
        except ValueError:
            # fine G set lacks some G-G' differences (pw_cutoff <
            # 2*gk_cutoff): keep the iterative result rather than build a
            # truncated dense H
            return None

    def after_solve(self, t0, it):
        pass

    def placed(self):
        return self.psi


def choose(ctx, cfg, devices, *, serial_bands, hub, paw, mgga, wf_dtype):
    """The one decision: which solver this run gets. The gates are tried in
    this order — gshard, beta_chunked, gamma, serial, batched — and each
    presumes that the earlier ones did not engage."""
    from sirius_tpu.parallel.mesh import production_mesh

    nk, ns, nb = ctx.gkvec.num_kpoints, ctx.num_spins, ctx.num_bands
    devs = list(devices) if devices is not None else jax.devices()
    ndev = len(devs)
    # production multi-device mesh: k-points over "k", bands over "b"
    # (GSPMD — same program, XLA inserts the collectives; None on 1 device)
    mesh, psi_spec = (None, None) if serial_bands else production_mesh(
        nk, nb, devices=devices)
    # single-k unpolarized no-U with projectors: the gshard/chunked regime
    big_cell = bool(not serial_bands and nk == 1 and ns == 1 and hub is None
                    and ctx.beta.num_beta_total)
    # ---- G-sharded: when the replicated projector + wave-function
    # footprint would not fit a single device (control.gshard "auto"/True)
    g_flag = cfg.control.gshard
    gsh_want = False
    if big_cell and g_flag not in (False, "false", "off") and ndev > 1:
        # replicated per-device footprint: projector table + psi workspace
        foot = (ctx.beta.num_beta_total + 4 * nb) * ctx.gkvec.ngk_max * 16
        dims_ok = (
            ctx.fft_coarse.dims[0] % ndev == 0
            and ctx.fft_coarse.dims[1] % ndev == 0
        )
        forced = g_flag in (True, "force")
        gsh_want = dims_ok and (
            forced
            or (g_flag == "auto" and foot > cfg.control.gshard_budget_bytes)
        )
        if forced and not dims_ok:
            raise ValueError(
                f"control.gshard is forced but the coarse box "
                f"{ctx.fft_coarse.dims} is not divisible by {ndev} devices "
                "along x and y, so the G-sharded band solve cannot engage"
            )
    if mesh is not None or gsh_want:
        runtime.refuse_large_subspace_on_tpu_mesh(devs, nb)
    if mgga and gsh_want:
        # the G-sharded operator has no tau term and the gshard density
        # branch never updates tau_g — it would silently produce SCAN
        # energies from tau = 0
        raise NotImplementedError(
            "mGGA with the G-sharded band solve is not supported; set "
            "control.gshard = false"
        )
    # ---- chunked beta projectors: engage when the dense table would
    # exceed beta_chunk_budget_bytes (control.beta_chunked "auto"), or
    # always when forced. The eligibility is kept apart from the budget
    # decision: the OOM ladder (degrade) engages the path mid-run after an
    # HBM exhaustion, even when the budget did not trip it at set-up.
    bc_flag = cfg.control.beta_chunked
    chunk_foot = ctx.beta.num_beta_total * ctx.gkvec.ngk_max * 16
    chunk_ok = bool(
        big_cell and not gsh_want and bc_flag not in (False, "false", "off")
        and paw is None and not mgga
    )
    if gsh_want:
        # the "g" mesh replaces the (k, b) mesh
        band = GshardSolver(ctx, cfg, devs, wf_dtype)
    elif chunk_ok and (bc_flag in (True, "force") or (
            bc_flag == "auto"
            and chunk_foot > cfg.control.beta_chunk_budget_bytes)):
        band = ChunkedSolver(ctx, cfg, mesh)
    elif (
        # Hubbard needs the complex per-k U apply and mGGA the complex tau
        # operator — both keep the generic path; multi-device runs keep the
        # band-sharded batched path — the packed solve is single-device
        # and would idle the rest of the mesh
        cfg.control.reduce_gvec and not serial_bands and nk == 1
        and float(np.abs(np.asarray(ctx.gkvec.kpoints[0])).max()) < 1e-12
        and hub is None and not mgga and ndev == 1
    ):
        band = GammaSolver(ctx, cfg, devs)
    elif serial_bands:
        band = SerialSolver(ctx, cfg, hub)
    else:
        band = KsetSolver(ctx, cfg, devs, mesh, psi_spec, hub, mgga)
    band.chunk_ok, band.chunk_foot = chunk_ok, chunk_foot
    return band


def oom_state(band, cfg) -> dict:
    """OOM-ladder applicability flags (dft/recovery.py _recover_oom)."""
    return {
        "beta_chunked": isinstance(band, ChunkedSolver),
        "beta_chunk_eligible": band.chunk_ok,
        "beta_chunk_can_halve": int(cfg.control.beta_chunk_size) > 16,
    }


def degrade(band, d, cfg):
    """The OOM ladder's swap (dft/recovery.py decision `d`): the same
    solver, or a ChunkedSolver where the regime allows one."""
    foot = band.chunk_foot
    if d.shrink_beta_budget:
        # rung 0 (repeatable): quarter the dense-beta engagement budget to
        # below the current table's footprint and halve the chunk size, so
        # the next band solve allocates strictly less HBM than the one
        # that exhausted it
        cfg.control.beta_chunk_budget_bytes = min(
            float(cfg.control.beta_chunk_budget_bytes) / 4.0, foot / 2.0)
        cfg.control.beta_chunk_size = max(
            16, int(cfg.control.beta_chunk_size) // 2)
    if (d.shrink_beta_budget or d.force_beta_chunked) and band.chunk_ok and (
            d.force_beta_chunked or isinstance(band, ChunkedSolver)
            or foot > cfg.control.beta_chunk_budget_bytes):
        # (re)engage the chunked projector path; a fresh solver builds its
        # tables at the next band solve, at the new beta_chunk_size
        band.book()  # what the solver that goes has run
        new = ChunkedSolver(band.ctx, cfg, band.mesh)
        new.chunk_ok, new.chunk_foot = True, foot
        return new
    return band
