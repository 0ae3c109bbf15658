"""Numerics observatory: per-stage precision-headroom probes and the
on-device numerics ledger (`sirius-numerics` CLI, ISSUE 14).

The mixed-precision SCF ladder needs a measurement, not a guess, of which
SCF stages tolerate reduced precision. This module answers it two ways:

**Shadow probes** (`probe_stages`) re-evaluate individual SCF stages at a
converged-enough iterate with inputs degraded to fp32/bf16 and score the
result against the fp64 reference in the one currency that matters: the
first-order total-energy impact in Hartree. Stages are keyed by the same
span names as ``obs/costs.py::scf_stage_costs()`` so headroom tables join
against cost tables. Two probe modes, stated per stage below: the band
solve re-runs the REAL kernel in complex64 (true reduced arithmetic);
every other stage round-trips its inputs through the target precision and
re-runs in fp64 (input-representation sensitivity — a lower bound on the
true-arithmetic error, and the part that is independent of any particular
kernel rewrite).

**Ledger helpers**: the fused step appends four cheap invariants
(S-orthonormality, mixer charge drift, symmetrization idempotency,
subspace-H hermiticity) to its per-iteration scalar record (dft/fused.py
S_ORTHO..S_HERM — same single readback). ``ledger_from_scalars`` names
them for events/metrics and ``ledger_host`` is the numpy twin the host
debug path emits, pinned to the device values to <=1e-12 by
tests/test_fused_scf.py.

The headroom table is gated by a checked-in ``NUMERICS_BASELINE.json``
(same time-series idiom as obs/perf.py): ``sirius-numerics report
--compare NUMERICS_BASELINE.json`` exits nonzero when a stage's
clears-the-bound verdict flips or its error grows by more than a decade.
"""

from __future__ import annotations

import argparse
import json
import os
import platform as _platform
import sys
import tempfile
import time

import numpy as np

from sirius_tpu.obs import events as obs_events
from sirius_tpu.obs import metrics as obs_metrics

SCHEMA = 1
# energy-impact bar a stage must clear to be a mixed-precision candidate
BOUND_HA = 1e-8
# errors below this are indistinguishable accumulation noise: two runs of
# the same binary differ at this level, so the gate treats them as equal
NOISE_FLOOR = 1e-14
# compare gate: error growth beyond this many decades (log10) is a
# regression even when the clears verdict did not flip
TOL_DECADES = 1.0

# probed stages, keyed like obs/costs.py::scf_stage_costs(); scf.d_matrix
# is skipped on decks without augmentation
PROBE_STAGES = (
    "scf.density",
    "scf.mixing",
    "scf.potential",
    "scf.occupations",
    "scf.band_solve",
    "scf.d_matrix",
)

PRECISIONS = ("fp32", "bf16")

# the four on-device ledger invariants, in scalar-record order
# (dft/fused.py S_ORTHO, S_CHG, S_SYM, S_HERM)
LEDGER_KEYS = ("ortho", "charge", "sym", "herm")

_PROBE_IMPACT = obs_metrics.REGISTRY.gauge(
    "numerics_probe_energy_impact_ha",
    "shadow-probe first-order energy impact of reduced precision (Ha)")
_PROBE_REL = obs_metrics.REGISTRY.gauge(
    "numerics_probe_rel_err",
    "shadow-probe relative output error of reduced precision")
_LEDGER = obs_metrics.REGISTRY.gauge(
    "scf_numerics_ledger",
    "per-iteration on-device numerical invariants, by invariant")


# ---- ledger ------------------------------------------------------------


def ledger_from_scalars(scalars) -> dict:
    """Name the ledger slice of a fused per-iteration scalar record."""
    from sirius_tpu.dft.fused import S_CHG, S_HERM, S_ORTHO, S_SYM

    s = np.asarray(scalars, dtype=np.float64)
    return {
        "ortho": float(s[S_ORTHO]),
        "charge": float(s[S_CHG]),
        "sym": float(s[S_SYM]),
        "herm": float(s[S_HERM]),
    }


def ledger_host(psi, beta_gk, qmat, dion, gmask, x_mixed, x_new,
                omega: float, sym_resid: float = 0.0) -> dict:
    """numpy twin of the fused step's ledger block (dft/fused.py).

    Must compute the IDENTICAL quantities: psi masked by gmask, the
    S-metric Gram with the bare augmentation qmat, the mixer G=0 charge
    drift against the packed vectors, and the chained-GEMM subspace
    nonlocal H against the BARE dion (not the screened per-iteration D,
    whose refresh timing differs between the host and fused paths).
    """
    psi = np.asarray(psi, dtype=np.complex128) * np.asarray(
        gmask, dtype=np.float64)[:, None, None, :]
    nk, ns, nb, _ = psi.shape
    if beta_gk is not None and np.asarray(beta_gk).shape[1]:
        beta = np.asarray(beta_gk, dtype=np.complex128)
        bp = np.einsum("kxg,ksbg->ksbx", np.conj(beta), psi)
    else:
        bp = np.zeros((nk, ns, nb, 0), dtype=np.complex128)
    qm = np.asarray(qmat, dtype=np.float64) if qmat is not None \
        else np.zeros((bp.shape[-1], bp.shape[-1]))
    gram = np.einsum("ksbg,kscg->ksbc", np.conj(psi), psi)
    gram = gram + np.einsum("ksbx,xy,kscy->ksbc", np.conj(bp), qm, bp)
    s_ortho = float(np.max(np.abs(gram - np.eye(nb))))
    s_chg = float(abs(np.real(x_mixed[0]) - np.real(x_new[0])) * omega)
    dn = np.real(np.asarray(dion, dtype=np.float64)) if dion is not None \
        else qm * 0.0
    h_nl = np.einsum("ksbx,xy,kscy->ksbc", np.conj(bp), dn, bp)
    s_herm = float(np.max(np.abs(
        h_nl - np.conj(np.swapaxes(h_nl, -1, -2)))))
    return {"ortho": s_ortho, "charge": s_chg, "sym": float(sym_resid),
            "herm": s_herm}


def record_ledger(ledger: dict, it: int, path: str) -> None:
    """Push one iteration's ledger to /metrics (per-invariant gauge)."""
    for k, v in ledger.items():
        _LEDGER.set(v, invariant=k, path=path)


# ---- precision degradation ---------------------------------------------


def _rt(a, prec: str):
    """Round-trip an array through the target precision back to fp64
    (complex arrays component-wise: there is no complex bf16 anywhere)."""
    if a is None:
        return None
    a = np.asarray(a)
    if prec == "fp32":
        def r(x):
            return x.astype(np.float32).astype(np.float64)
    elif prec == "bf16":
        import jax.numpy as jnp

        def r(x):
            return np.asarray(
                jnp.asarray(x).astype(jnp.bfloat16)).astype(np.float64)
    else:
        raise ValueError(f"unknown precision '{prec}'")
    if np.iscomplexobj(a):
        return r(np.real(a)) + 1j * r(np.imag(a))
    return r(np.asarray(a, dtype=np.float64))


def _rel(delta, ref) -> float:
    nref = float(np.linalg.norm(np.ravel(ref)))
    return float(np.linalg.norm(np.ravel(delta))) / max(nref, 1e-300)


# ---- the probe harness -------------------------------------------------


def probe_stages(ctx, xc, psi, occ, evals, rho_g, mag_g=None,
                 bound_ha: float = BOUND_HA, mixer_beta: float = 0.7,
                 smearing: str = "gaussian",
                 smearing_width: float = 0.025) -> dict:
    """Shadow-evaluate each SCF stage at the given iterate in fp32/bf16
    against fp64 and score the first-order total-energy impact.

    Arguments are the host-side iterate run_scf exposes via
    ``keep_state=True``: psi [nk, ns, nb, ngk] complex, occ [nk, ns, nb],
    evals [nk, ns, nb], rho_g/mag_g fine-sphere densities. Returns
    {stage: {"fp32": {"energy_impact_ha", "rel_err"}, "bf16": {...},
    "clears_fp32": bool, "clears_bf16": bool}}.
    """
    import jax.numpy as jnp

    from sirius_tpu.dft.density import generate_density_g
    from sirius_tpu.dft.occupation import find_fermi
    from sirius_tpu.dft.potential import generate_potential
    from sirius_tpu.ops.augmentation import d_operator
    from sirius_tpu.ops.hamiltonian import apply_h_s, make_hk_params

    psi = np.asarray(psi, dtype=np.complex128)
    occ = np.asarray(occ, dtype=np.float64)
    evals = np.asarray(evals, dtype=np.float64)
    rho_g = np.asarray(rho_g)
    nk, ns, nb, _ = psi.shape
    omega = float(ctx.unit_cell.omega)
    kw = np.asarray(ctx.kweights, dtype=np.float64)
    occ_w = occ * kw[:, None, None]
    nel = float(ctx.unit_cell.num_valence_electrons)
    width = float(smearing_width)

    # fp64 references, computed once
    pot = generate_potential(ctx, rho_g, xc, mag_g)
    veff_g = np.asarray(pot.veff_g)

    def _epot(e) -> float:
        # the potential-derived part of the total-energy expression
        return float(-0.5 * e["vha"] + e["exc"] - e["vxc"] - e["bxc"])

    def _drho_impact(drho) -> float:
        # first-order energy change of a density perturbation: int drho veff
        return abs(float(np.real(np.sum(np.conj(drho) * veff_g))) * omega)

    rho_out = np.asarray(generate_density_g(ctx, psi, occ)).sum(axis=0)

    def _eval_sum(ev, oc) -> float:
        return float(np.sum(kw[:, None, None] * oc * ev))

    def _band_energy_ref() -> float:
        return _eval_sum(evals, occ)

    has_aug = ctx.aug is not None and ctx.beta.num_beta_total > 0
    if has_aug:
        d64 = np.asarray(
            d_operator(ctx.unit_cell, ctx.gvec, ctx.aug, veff_g, ctx.beta,
                       phases=ctx.phases))
        beta = np.asarray(ctx.beta.beta_gk, dtype=np.complex128)
        bp = np.einsum("kxg,ksbg->ksbx", np.conj(beta), psi)
        # first-order nonlocal-energy weight: dE = sum dD_xy M_xy
        dm_w = np.real(np.einsum("ksb,ksbx,ksby->xy", occ_w,
                                 np.conj(bp), bp))
    else:
        d64 = dm_w = None

    # hpsi fp64 reference (the true-arithmetic band-solve probe baseline);
    # veff_r_coarse is [ns, n1, n2, n3] — HkParams wants one spin's box
    veff_box = np.asarray(pot.veff_r_coarse)
    e_hpsi64 = 0.0
    for ik in range(nk):
        for s in range(ns):
            params = make_hk_params(ctx, ik, veff_box[s],
                                    dtype=jnp.complex128)
            hpsi, _ = apply_h_s(params, jnp.asarray(psi[ik, s]))
            hpsi = np.asarray(hpsi)
            e_hpsi64 += float(np.sum(
                occ_w[ik, s] * np.real(np.einsum(
                    "bg,bg->b", np.conj(psi[ik, s]), hpsi))))

    def _probe(prec: str) -> dict:
        out = {}
        # scf.density: |psi|^2 accumulation from a degraded band block
        rho_p = np.asarray(
            generate_density_g(ctx, _rt(psi, prec), occ)).sum(axis=0)
        out["scf.density"] = {
            "energy_impact_ha": _drho_impact(rho_p - rho_out),
            "rel_err": _rel(rho_p - rho_out, rho_out),
        }
        # scf.mixing: linear mixer apply on degraded vectors
        mix64 = (1.0 - mixer_beta) * rho_g + mixer_beta * rho_out
        mix_p = ((1.0 - mixer_beta) * _rt(rho_g, prec)
                 + mixer_beta * _rt(rho_out, prec))
        out["scf.mixing"] = {
            "energy_impact_ha": _drho_impact(mix_p - mix64),
            "rel_err": _rel(mix_p - mix64, mix64),
        }
        # scf.potential: Hartree+XC+local assembly from a degraded density
        pot_p = generate_potential(ctx, _rt(rho_g, prec), xc,
                                   _rt(mag_g, prec))
        out["scf.potential"] = {
            "energy_impact_ha": abs(_epot(pot_p.energies)
                                    - _epot(pot.energies)),
            "rel_err": _rel(np.asarray(pot_p.veff_g) - veff_g, veff_g),
        }
        # scf.occupations: fermi search over degraded eigenvalues
        _, occ_p, _ = find_fermi(
            jnp.asarray(_rt(evals, prec)), jnp.asarray(kw), nel, width,
            kind=smearing, max_occupancy=ctx.max_occupancy)
        occ_p = np.asarray(occ_p)
        out["scf.occupations"] = {
            "energy_impact_ha": abs(_eval_sum(evals, occ_p)
                                    - _band_energy_ref()),
            "rel_err": _rel(occ_p - occ, occ),
        }
        # scf.band_solve: H|psi>. fp32 runs the REAL kernel in complex64;
        # bf16 has no complex dtype, so inputs are degraded and applied
        # in fp64
        e_hpsi_p = 0.0
        if prec == "fp32":
            veff_p = veff_box
            psi_in = psi.astype(np.complex64)
        else:
            veff_p = _rt(veff_box, prec)
            psi_in = _rt(psi, prec)
        for ik in range(nk):
            for s in range(ns):
                params = make_hk_params(
                    ctx, ik, veff_p[s],
                    dtype=jnp.complex64 if prec == "fp32"
                    else jnp.complex128)
                hpsi, _ = apply_h_s(params, jnp.asarray(psi_in[ik, s]))
                hpsi = np.asarray(hpsi, dtype=np.complex128)
                e_hpsi_p += float(np.sum(
                    occ_w[ik, s] * np.real(np.einsum(
                        "bg,bg->b",
                        np.conj(psi_in[ik, s]).astype(np.complex128),
                        hpsi))))
        out["scf.band_solve"] = {
            "energy_impact_ha": abs(e_hpsi_p - e_hpsi64),
            "rel_err": abs(e_hpsi_p - e_hpsi64) / max(abs(e_hpsi64),
                                                      1e-300),
        }
        # scf.d_matrix: D-operator screening from a degraded potential
        if has_aug:
            d_p = np.asarray(d_operator(
                ctx.unit_cell, ctx.gvec, ctx.aug, _rt(veff_g, prec),
                ctx.beta, phases=ctx.phases))
            out["scf.d_matrix"] = {
                "energy_impact_ha": abs(float(np.sum(
                    (np.real(d_p) - np.real(d64)) * dm_w))),
                "rel_err": _rel(d_p - d64, d64),
            }
        return out

    by_prec = {prec: _probe(prec) for prec in PRECISIONS}
    stages: dict[str, dict] = {}
    for sname in PROBE_STAGES:
        if sname not in by_prec["fp32"]:
            continue
        ent = {prec: by_prec[prec][sname] for prec in PRECISIONS}
        for prec in PRECISIONS:
            ent[f"clears_{prec}"] = bool(
                ent[prec]["energy_impact_ha"] <= bound_ha)
        stages[sname] = ent
    return stages


def emit_probe_events(stages: dict, it: int | None = None,
                      tier: str | None = None) -> None:
    """One ``numerics_probe`` event + gauge set per (stage, precision)."""
    for sname, ent in stages.items():
        for prec in PRECISIONS:
            p = ent[prec]
            obs_events.emit(
                "numerics_probe", stage=sname, prec=prec,
                energy_impact_ha=p["energy_impact_ha"],
                rel_err=p["rel_err"], clears=ent[f"clears_{prec}"],
                **({"it": it} if it is not None else {}),
                **({"tier": tier} if tier is not None else {}),
            )
            _PROBE_IMPACT.set(p["energy_impact_ha"], stage=sname,
                              prec=prec)
            _PROBE_REL.set(p["rel_err"], stage=sname, prec=prec)


# ---- tiers / baseline / CLI (obs/perf.py idiom) ------------------------


def run_tier(name: str, spec: dict, bound_ha: float = BOUND_HA,
             base_dir: str | None = None) -> dict:
    """Run one pinned tier deck to its iteration budget, then probe every
    stage at the final iterate."""
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.dft.xc import XCFunctional
    from sirius_tpu.obs.perf import tier_deck
    from sirius_tpu.serve.scheduler import build_job_context

    tmp = base_dir or tempfile.mkdtemp(prefix=f"sirius_numerics_{name}_")
    cfg = load_config(tier_deck(spec))
    cfg.control.numerics_probe = False  # the harness probes explicitly
    ctx = build_job_context(cfg, tmp)
    obs_metrics.set_enabled(True)
    res = run_scf(cfg, base_dir=tmp, ctx=ctx, keep_state=True)
    st = res["_state"]
    xc = XCFunctional(cfg.parameters.xc_functionals)
    stages = probe_stages(
        ctx, xc, st["psi"],
        np.asarray(res["band_occupancies"]),
        np.asarray(res["band_energies"]),
        st["rho_g"], st.get("mag_g"),
        bound_ha=bound_ha,
        mixer_beta=float(cfg.mixer.beta),
        smearing=cfg.parameters.smearing,
        smearing_width=float(cfg.parameters.smearing_width),
    )
    emit_probe_events(stages, tier=name)
    return {
        "deck": {k: spec[k] for k in
                 ("gk_cutoff", "pw_cutoff", "num_bands", "num_dft_iter")},
        "iterations": res["num_scf_iterations"],
        "stages": stages,
    }


def measure(tiers: list[str], bound_ha: float = BOUND_HA) -> dict:
    from sirius_tpu.obs.costs import detect_platform
    from sirius_tpu.obs.perf import TIERS

    entry = {
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": _platform.node(),
        "platform": detect_platform(),
        "bound_ha": bound_ha,
        "tiers": {},
    }
    for t in tiers:
        if t not in TIERS:
            raise SystemExit(f"unknown tier '{t}' (have {sorted(TIERS)})")
        entry["tiers"][t] = run_tier(t, TIERS[t], bound_ha=bound_ha)
    return entry


def load_baseline(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != SCHEMA:
        raise SystemExit(
            f"{path}: schema {doc.get('schema')!r} != supported {SCHEMA}")
    if not doc.get("series"):
        raise SystemExit(f"{path}: empty series")
    return doc


def compare_entries(base_entry: dict, cur_entry: dict,
                    tol_decades: float = TOL_DECADES) -> list[dict]:
    """Noise-aware headroom regressions of `cur_entry` vs `base_entry`.

    A regression is: a stage/precision present in the baseline but absent
    now; a clears-the-bound verdict flipping pass -> fail; or the energy
    impact growing by more than `tol_decades` decades above the baseline
    (both sides floored at NOISE_FLOOR, so noise-level errors compare
    equal no matter how their last digits moved).
    """
    regs = []
    for tname, bt in base_entry["tiers"].items():
        ct = cur_entry["tiers"].get(tname)
        if ct is None:
            continue  # not re-measured this run
        for sname, b in bt["stages"].items():
            c = ct["stages"].get(sname)
            if c is None:
                regs.append({
                    "tier": tname, "stage": sname, "prec": "*",
                    "kind": "missing",
                    "detail": "stage present in baseline, absent now",
                })
                continue
            for prec in PRECISIONS:
                if prec not in b:
                    continue
                if prec not in c:
                    regs.append({
                        "tier": tname, "stage": sname, "prec": prec,
                        "kind": "missing",
                        "detail": "precision present in baseline, "
                        "absent now",
                    })
                    continue
                bkey, ckey = f"clears_{prec}", f"clears_{prec}"
                if b.get(bkey) and not c.get(ckey):
                    regs.append({
                        "tier": tname, "stage": sname, "prec": prec,
                        "kind": "clears_flip",
                        "baseline": b[prec]["energy_impact_ha"],
                        "current": c[prec]["energy_impact_ha"],
                    })
                    continue
                bv = max(float(b[prec]["energy_impact_ha"]), NOISE_FLOOR)
                cv = max(float(c[prec]["energy_impact_ha"]), NOISE_FLOOR)
                if np.log10(cv) - np.log10(bv) > tol_decades:
                    regs.append({
                        "tier": tname, "stage": sname, "prec": prec,
                        "kind": "error_growth",
                        "baseline": bv, "current": cv,
                        "decades": float(np.log10(cv) - np.log10(bv)),
                    })
    return regs


def _print_report(entry: dict) -> None:
    for tname, tier in entry["tiers"].items():
        print(f"[{tname}] headroom vs {entry['bound_ha']:.0e} Ha bound "
              f"({tier['iterations']} iterations)")
        print(f"  {'stage':<18} {'fp32 impact':>12} {'bf16 impact':>12}"
              f"   clears fp32/bf16")
        for sname, s in sorted(tier["stages"].items()):
            c32 = "yes" if s["clears_fp32"] else "NO"
            c16 = "yes" if s["clears_bf16"] else "NO"
            print(f"  {sname:<18} "
                  f"{s['fp32']['energy_impact_ha']:>12.3e} "
                  f"{s['bf16']['energy_impact_ha']:>12.3e}"
                  f"   {c32:>3} / {c16}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="sirius-numerics",
        description="per-stage precision-headroom probes + baseline gate")
    sub = ap.add_subparsers(dest="cmd", required=True)
    rp = sub.add_parser(
        "report", help="probe tiers, print the headroom table, "
        "optionally gate against / update a baseline")
    rp.add_argument("--tiers", default="small",
                    help="comma list of tiers to probe (small,large)")
    rp.add_argument("--bound", type=float, default=BOUND_HA,
                    help="energy-impact bound in Ha (default 1e-8)")
    rp.add_argument("--compare", metavar="BASELINE",
                    help="compare against the newest entry of this "
                    "NUMERICS_BASELINE.json; exit 1 on regression")
    rp.add_argument("--update", metavar="BASELINE",
                    help="append this run to the baseline series "
                    "(creates the file if missing)")
    rp.add_argument("--tol-decades", type=float, default=TOL_DECADES,
                    help="allowed error growth in decades before the "
                    "gate trips (default 1.0)")
    rp.add_argument("--out", metavar="PATH",
                    help="also write this run's entry as JSON")
    args = ap.parse_args(argv)

    tiers = [t.strip() for t in args.tiers.split(",") if t.strip()]
    entry = measure(tiers, bound_ha=args.bound)
    _print_report(entry)

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"schema": SCHEMA, "series": [entry]}, f, indent=1)
        print(f"wrote {args.out}")

    rc = 0
    if args.compare:
        doc = load_baseline(args.compare)
        regs = compare_entries(doc["series"][-1], entry,
                               tol_decades=args.tol_decades)
        if regs:
            rc = 1
            print(f"NUMERICS REGRESSION vs {args.compare} "
                  f"({doc['series'][-1]['created']}):", file=sys.stderr)
            for r in regs:
                if r["kind"] == "missing":
                    print(f"  {r['tier']}/{r['stage']}[{r['prec']}]: "
                          f"{r['detail']}", file=sys.stderr)
                elif r["kind"] == "clears_flip":
                    print(f"  {r['tier']}/{r['stage']}[{r['prec']}]: "
                          f"cleared the bound in baseline "
                          f"({r['baseline']:.3e} Ha), now fails "
                          f"({r['current']:.3e} Ha)", file=sys.stderr)
                else:
                    print(f"  {r['tier']}/{r['stage']}[{r['prec']}]: "
                          f"error grew {r['decades']:.2f} decades "
                          f"({r['baseline']:.3e} -> {r['current']:.3e} "
                          f"Ha)", file=sys.stderr)
        else:
            print(f"numerics gate OK vs {args.compare}")

    if args.update:
        if os.path.exists(args.update):
            doc = load_baseline(args.update)
        else:
            doc = {"schema": SCHEMA, "series": []}
        doc["series"].append(entry)
        with open(args.update, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"appended to {args.update} ({len(doc['series'])} entries)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
