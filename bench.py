"""Benchmark: SCF-iteration wall time of the flagship PP-PW path.

Workload: BASELINE config 1 class — 2-atom silicon, ultrasoft-style
projectors, gk_cutoff 6 / pw_cutoff 20, Gamma-only, 26 bands — one full SCF
iteration (20-step blocked band solve + Fermi search + density reduction) as
ONE jitted program with real-array boundaries, in 32-bit types on the local
accelerator. This is a hand-assembled loop, not run_scf (chip_smoke.py drives
the real entry points); x64 stays on as it is for every deck, and the
operands are typed float32/complex64 explicitly.

Baseline anchor: the reference's own verification run of the same class
(verification/test08 output_ref.json: scf_time 6.33 s / 30 iterations =
0.211 s per SCF iteration on the reference's CPU node; no per-GPU numbers
are published in-tree, BASELINE.json "published": {}). vs_baseline =
baseline_iter_time / measured_iter_time (>1 = faster than that anchor).

Each tier runs in a child process with a hard timeout (the parent never
touches JAX, so the child gets the chip) and prints one JSON line; the
anchored `full` tier runs last, so its line is the last line of stdout. There
is no CPU tier and no recorded number: where JAX finds no accelerator, or a
tier fails, the exit code is non-zero.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REF_ITER_TIME_S = 6.325581577 / 30  # test08 scf_time / num_scf_iterations

# Large-tier anchor from the published Si511Ge time-to-solution table
# (BASELINE.md: 9 XC50 nodes x 214 s, QE+SIRIUS GPU): node-seconds scaled to
# the 54-atom bench cell by the cubic cost law and divided by an assumed
# 20-iteration SCF (the published number is time-to-solution; the iteration
# count is not in-tree). vs_baseline = anchor / measured — honest in order of
# magnitude, not a calibrated per-iteration figure.
SI511GE_NODE_S = 214.0 * 9
SI511GE_ASSUMED_ITERS = 20.0
LARGE_ANCHOR_S = (
    SI511GE_NODE_S / SI511GE_ASSUMED_ITERS * (54.0 / 512.0) ** 3
)

# accelerator peak table for the MFU figure: the shared one in
# sirius_tpu/obs/costs.py (override with BENCH_PEAK_GFLOPS or
# SIRIUS_TPU_PEAK_GFLOPS when the actual chip is unlisted)
def _peak_gflops(device_kind: str) -> float:
    from sirius_tpu.obs.costs import peak_gflops

    return peak_gflops(device_kind)


def _hpsi_flops(nb: int, ngk: int, nbeta: int, box) -> float:
    """Flops of ONE H*psi + S*psi application on [nb, ngk] — delegates to
    the shared analytic cost model (sirius_tpu/obs/costs.py), which keeps
    the historical formula and is unit-tested against hand counts."""
    from sirius_tpu.obs.costs import hpsi_flops

    return hpsi_flops(nb, ngk, nbeta, box)


def _workload(tier: str) -> None:
    """Run one tier and print its JSON result (subprocess entry)."""

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sirius_tpu import runtime

    runtime.enable_compile_cache()
    if jax.devices()[0].platform == "cpu":
        raise SystemExit(
            "bench: JAX found no accelerator; this benchmark has no CPU tier")

    from sirius_tpu.dft.occupation import find_fermi
    from sirius_tpu.parallel.batched import (
        davidson_kset,
        density_kset,
        make_hkset_params,
    )
    from sirius_tpu.testing import synthetic_silicon_context

    plat = jax.devices()[0].platform
    sys.stderr.write(f"[bench] tier={tier} platform={plat}\n")
    if tier == "micro":
        # sub-minute tier: tiny shapes so the program compiles in seconds
        ctx = synthetic_silicon_context(
            gk_cutoff=4.0, pw_cutoff=12.0, ngridk=(1, 1, 1), num_bands=8,
            use_symmetry=False,
        )
        nk, ns, nb, ngk = 1, 1, 8, ctx.gkvec.ngk_max
    elif tier == "large":
        # flagship-regime tier (BASELINE.md Si-supercell class): 3x3x3
        # supercell (54 atoms), 512 bands — the band-dominated regime where
        # the per-chip GFLOPS figure is meaningful, not extrapolated
        ctx = synthetic_silicon_context(
            gk_cutoff=5.0, pw_cutoff=15.0, ngridk=(1, 1, 1), num_bands=512,
            use_symmetry=False, supercell=3,
        )
        nk, ns, nb, ngk = 1, 1, 512, ctx.gkvec.ngk_max
    else:
        ctx = synthetic_silicon_context(
            gk_cutoff=6.0, pw_cutoff=20.0, ngridk=(1, 1, 1), num_bands=26,
            use_symmetry=False,
        )
        nk, ns, nb, ngk = 1, 1, 26, ctx.gkvec.ngk_max
    params = make_hkset_params(
        ctx, np.full(ctx.fft_coarse.dims, 0.05), dtype=jnp.complex64
    )
    rng = np.random.default_rng(0)
    psi = (
        rng.standard_normal((nk, ns, nb, ngk))
        + 1j * rng.standard_normal((nk, ns, nb, ngk))
    ).astype(np.complex64) * ctx.gkvec.mask[:, None, None, :].astype(np.float32)
    kw = jnp.asarray(np.ones(nk), dtype=jnp.float32)

    if tier in ("full", "large"):
        num_steps = 20 if tier == "full" else 10

        # Gamma-only workload -> production run_scf takes the packed-real
        # path (ops/gamma.py reduce_gvec); the bench measures the same:
        # real GEMMs/eigh in the solver, complex only inside the FFT step
        from sirius_tpu.ops.gamma import (
            apply_h_s_gamma,
            build_gamma_map,
            density_gamma,
            make_gamma_params,
            pack,
            pack_diags,
        )
        from sirius_tpu.parallel.batched import compute_h_diag, compute_o_diag
        from sirius_tpu.solvers.davidson import davidson

        gm = build_gamma_map(
            np.asarray(ctx.gkvec.millers[0]), np.asarray(ctx.gkvec.mask[0])
        )
        gparams = make_gamma_params(
            ctx, np.full(ctx.fft_coarse.dims, 0.05), gm, rdtype=jnp.float32
        )
        hd, od = pack_diags(
            gm,
            compute_h_diag(ctx, np.asarray(ctx.beta.dion)[None], 0.05)[0, 0],
            compute_o_diag(ctx)[0],
        )
        hd = jnp.asarray(hd, jnp.float32)
        od = jnp.asarray(od, jnp.float32)
        nel = 8.0 if tier == "full" else 4.0 * ctx.unit_cell.num_atoms

        # params as jit ARGUMENTS (real leaves only): closure capture would
        # embed device arrays as program constants; argument passing keeps
        # buffers device-side. The psi carry is DONATED — the chained
        # timed_block feeds each call's subspace into the next, so XLA can
        # reuse the [nb, ngk] buffer in place (same convention as the fused
        # SCF carry in dft/fused.py).
        from functools import partial

        @partial(jax.jit, donate_argnums=(1,))
        def one_iter(ps, x):
            ev, x2, rn = davidson(
                apply_h_s_gamma, ps, x, hd, od, ps.mask_p,
                num_steps=num_steps,
            )
            mu, occ, ent = find_fermi(
                ev[None, None], kw, nel, 0.025, max_occupancy=2.0
            )
            rho = density_gamma(ps, x2, occ[0, 0] * kw[0])
            return ev, rn, rho, x2

        x0 = pack(gm, psi[0, 0]).astype(np.float32)
        args = (gparams, jnp.asarray(x0))
        label = (
            "SCF-iteration wall time (20-step Gamma real-storage band solve "
            "+ Fermi + density)"
            if tier == "full"
            else "large-tier SCF-iteration wall time (10-step Gamma "
                 "real-storage band solve + Fermi + density, 54-atom Si "
                 "supercell, 512 bands)"
        )
    elif tier == "micro":
        num_steps = 4
        from functools import partial

        @partial(jax.jit, donate_argnums=(1, 2))
        def one_iter(ps, pr, pi):
            ev, pr2, pi2, rn = davidson_kset(ps, pr, pi, num_steps=num_steps)
            mu, occ, ent = find_fermi(ev, kw, 8.0, 0.025, max_occupancy=2.0)
            rho = density_kset(ps, pr2, pi2, occ * kw[:, None, None])
            return ev, rn, rho, pr2, pi2

        args = (
            params,
            jnp.asarray(np.real(psi), jnp.float32),
            jnp.asarray(np.imag(psi), jnp.float32),
        )
        label = "micro SCF-iteration wall time (4-step band solve + Fermi + density, gk=4 nb=8)"
    else:  # "hpsi": raw Hamiltonian application throughput
        from functools import partial

        from sirius_tpu.ops.hamiltonian import apply_h_s
        from sirius_tpu.parallel.batched import hk_complex, hkset_slice_r

        slc = hkset_slice_r(params)

        @partial(jax.jit, donate_argnums=(1, 2))
        def one_iter(ps, pr, pi):
            pk = hk_complex(ps)
            def body(c, _):
                h, s = apply_h_s(pk, c)
                return h / jnp.linalg.norm(h), None

            out, _ = jax.lax.scan(
                body, (pr + 1j * pi).astype(jnp.complex64), None, length=62
            )
            return jnp.real(out), jnp.imag(out)

        args = (
            slc,
            jnp.asarray(np.real(psi[0, 0]), jnp.float32),
            jnp.asarray(np.imag(psi[0, 0]), jnp.float32),
        )
        label = "62x H*psi application wall time (local+nonlocal, 26 bands)"

    n_carry = len(args) - 1
    t_c0 = time.perf_counter()
    out = one_iter(*args)
    jax.block_until_ready(out)
    sys.stderr.write(f"[bench] compile+first run: {time.perf_counter()-t_c0:.1f}s\n")
    # the psi carry was donated: args' input buffers are dead — the chain
    # state lives in `cur` from here on
    cur = (args[0], *out[-n_carry:])

    def timed_block(reps: int) -> float:
        """reps chained one_iter calls (outputs feed the next call's psi),
        timed to block_until_ready of the last."""
        nonlocal cur
        a = cur
        t0 = time.perf_counter()
        o = None
        for _ in range(reps):
            o = one_iter(*a)
            a = (a[0], *o[-n_carry:])
        jax.block_until_ready(o)
        cur = a
        return (time.perf_counter() - t0) / reps

    timed_block(1)  # warm the dispatch path
    reps = 5 if tier != "large" else 2
    times = [timed_block(reps) for _ in range(3)]
    for i, t in enumerate(times):
        sys.stderr.write(f"[bench] block {i}: {t:.4f}s/iter\n")
    iter_time = float(np.median(times))
    # full tier: the reference's own test08 CPU run; large tier: the
    # published Si511Ge node-seconds scaled to the bench cell (see
    # LARGE_ANCHOR_S). The micro/hpsi tiers have no comparable anchor.
    if tier == "full":
        vs = round(REF_ITER_TIME_S / iter_time, 3)
    elif tier == "large":
        vs = round(LARGE_ANCHOR_S / iter_time, 4)
    else:
        vs = 0.0
    shapes = {
        "micro": "Si-2atom US gk=4/pw=12 nb=8 c64",
        "large": "Si-54atom US gk=5/pw=15 nb=512 f32-packed",
    }.get(tier, "Si-2atom US gk=6/pw=20 nb=26 f32-packed")
    # H*psi GFLOPS/chip from the flops model (the reference self-reports
    # this counter; BASELINE.md asks for it alongside the wall time)
    nbeta = ctx.beta.num_beta_total
    box = ctx.fft_coarse.dims
    if tier == "hpsi":
        n_band_applies = 62.0 * nb
    else:
        from sirius_tpu.solvers.davidson import num_applies

        # num_applies counts in band rows already (the reference's
        # num_loc_op_applied convention)
        n_band_applies = float(num_applies(num_steps, nb)) * nk * ns
    gflops = (
        _hpsi_flops(1, ngk, nbeta, box) * n_band_applies / iter_time / 1e9
    )
    peak = _peak_gflops(jax.devices()[0].device_kind)
    extra = {}
    if tier == "large":
        extra["baseline_anchor"] = (
            f"Si511Ge 9-node GPU {SI511GE_NODE_S:.0f} node*s / "
            f"{SI511GE_ASSUMED_ITERS:.0f} assumed iters * (54/512)^3 = "
            f"{LARGE_ANCHOR_S:.4f} s (BASELINE.md)"
        )
    print(
        json.dumps(
            {
                "metric": f"{label}, {shapes} on {plat}",
                "value": round(iter_time, 6),
                "unit": "s/iteration",
                "vs_baseline": vs,
                "hpsi_gflops_per_chip": round(gflops, 2),
                # model-flop utilization against the (nominal, overridable)
                # chip peak — the honest-perf figure VERDICT r5 asked for
                "mfu": round(gflops / peak, 5),
                "peak_gflops_assumed": peak,
                **extra,
                "flops_model": "per-apply: 10 N log2 N + 7N + 8 ngk + "
                               "8 nb(3 nbeta ngk + 2 nbeta^2), N=coarse box",
                "device_kind": jax.devices()[0].device_kind,
                "host_ncpu": os.cpu_count(),
            }
        )
    )


def _run_sub(argv: list[str], tmo: int):
    try:
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + argv,
            capture_output=True, text=True, timeout=tmo,
        )
    except subprocess.TimeoutExpired:
        return None


TIERS = (("large", 1200), ("micro", 300), ("hpsi", 600), ("full", 900))


def main() -> int:
    if len(sys.argv) == 3 and sys.argv[1] == "--tier":
        _workload(sys.argv[2])
        return 0
    # the parent stays off JAX (a chip belongs to one process); each tier
    # is a child with a hard timeout. Every line is a measurement on the
    # local accelerator or there is no line: a tier that fails prints its
    # stderr and the exit code is non-zero.
    failed = []
    for tier, tmo in TIERS:
        r = _run_sub(["--tier", tier], tmo)
        lines = [] if r is None else [
            l for l in r.stdout.strip().splitlines() if l.startswith("{")]
        if r is not None and r.returncode == 0 and lines:
            # the anchored full tier runs last: its line is the last one
            print(lines[-1], flush=True)
            continue
        failed.append(tier)
        sys.stderr.write(
            f"bench tier {tier} timed out after {tmo}s\n" if r is None else
            f"bench tier {tier} failed (rc={r.returncode}):\n"
            f"{r.stderr[-800:]}\n")
    if failed:
        sys.stderr.write(f"bench: tiers without a measurement: {failed}\n")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
