#!/usr/bin/env python3
"""Write configs/<config>/refs.json of a collinear-spin configuration.

- `energy_total_ha`, the number `correct` is decided by, and
  `moment_total_ub` come from benchmark/plain_pwus_spin.py (numpy float64,
  dense H_sigma and S at every k-point of the whole mesh, no time reversal,
  no symmetry, imports nothing of sirius_tpu) from the deck's starting
  moment. About 40 minutes for the 4x4x4 mesh at gk 6 / pw 20 / 16 bands on
  eight cores.
- `nonmagnetic_run` is the same code from a zero moment (one channel
  solved): the state a deck that loses its moment ends in, and how far
  under it the ferromagnetic one lies.
- `witness_run_scf` is the program's own f64 run of the deck (CPU backend,
  the configuration's `reference.overrides`), with the moment it ends with.
  The script refuses to write where plain code and witness differ by more
  than 1e-6 Ha a cell or 1e-4 Bohr magnetons.

Ideal positions of the 2-atom cell only (what plain_pwus_spin knows).

  OPENBLAS_NUM_THREADS=1 python benchmark/make_refs_spin.py \\
      --config fm2-k444-us --workers 8
  python benchmark/make_refs_spin.py --config fm2-k444-us --block rehearse
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # the checkout: sirius_tpu, benchmark

WITNESS_TOL_HA_PER_CELL = 1e-6
WITNESS_TOL_UB = 1e-4
TERMS = ("kinetic", "nonlocal", "local", "hartree", "xc", "ewald")


def start_moment(deck: dict) -> float:
    """The deck's starting moment an atom (mu_B along z), the same on both
    atoms; anything else the plain code does not know."""
    m = deck["synthetic"].get("moments")
    rows = [m] if m and not isinstance(m[0], (list, tuple)) else (m or [])
    if not rows or any(list(r) != list(rows[0]) or r[0] or r[1] for r in rows):
        raise ValueError(f"plain_pwus_spin: one moment along z, equal on "
                         f"both atoms; the deck has {m}")
    return float(rows[0][2])


def plain_runs(deck: dict, workers: int = 1, log=None) -> tuple[dict, dict]:
    """The deck through benchmark/plain_pwus_spin.py, which knows one
    problem: the synthetic d-shell species, LSDA (X + PZ), Gaussian
    smearing. Anything else in the deck is refused, not approximated.
    Returns (the run from the deck's moment, the run from a zero moment)."""
    from benchmark import plain_pwus_spin

    p = deck["parameters"]
    if list(p["xc_functionals"]) != ["XC_LDA_X", "XC_LDA_C_PZ"]:
        raise ValueError(f"plain_pwus_spin has no {p['xc_functionals']}")
    syn = deck.get("synthetic", {})
    if (syn.get("ultrasoft") is not True or syn.get("species") != "dshell"
            or deck.get("unit_cell") or int(p.get("num_mag_dims", 0)) != 1
            or syn.get("positions") != [[0.0, 0.0, 0.0], [0.25, 0.25, 0.25]]
            or set(syn) - {"ultrasoft", "species", "moments", "a", "positions"}):
        raise ValueError("plain_pwus_spin knows the collinear 2-atom diamond "
                         "cell of the synthetic d-shell species only")
    if p.get("smearing", "gaussian") != "gaussian" or p.get("use_symmetry"):
        raise ValueError("plain_pwus_spin: Gaussian smearing, no symmetry")
    runs = []
    for moment in (start_moment(deck), 0.0):
        t0 = time.time()
        r = plain_pwus_spin.scf(
            ngridk=tuple(p["ngridk"]), gk_cutoff=float(p["gk_cutoff"]),
            pw_cutoff=float(p["pw_cutoff"]), num_bands=int(p["num_bands"]),
            smearing_width=float(p["smearing_width"]),
            lattice_constant=float(syn["a"]), start_moment=moment,
            density_tol=1e-10, workers=workers, log=log)
        if not r["converged"]:
            raise RuntimeError(f"plain_pwus_spin did not converge from "
                               f"{moment} mu_B an atom")
        r["wall_s"] = time.time() - t0
        runs.append(r)
    return runs[0], runs[1]


def witness_run(deck: dict) -> dict:
    """The same deck through the program: run_scf, f64, CPU backend."""
    import jax

    jax.config.update("jax_enable_x64", True)
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.serve.scheduler import build_job_context

    cfg = load_config(deck)
    ctx = build_job_context(cfg, ".")
    t0 = time.time()
    r = run_scf(cfg, ctx=ctx, devices=jax.devices("cpu")[:1])
    if not r["converged"]:
        raise RuntimeError("the program's run of the deck did not converge")
    return {"energy_per_cell_ha": float(r["energy"]["total"]),
            "moment_total_ub": float(r["magnetisation"]["total"][2]),
            "moment_atoms_ub": [a[2] for a in r["magnetisation"]["atoms"]],
            "scf_iterations": int(r["num_scf_iterations"]),
            "num_kpoints": int(ctx.gkvec.num_kpoints),
            "path": r["placement"]["path"],
            "wall_s_cpu": round(time.time() - t0, 1)}


def entry_of(deck: dict, plain: dict, nonmag: dict, witness: dict) -> dict:
    """The stored record of the one geometry; raises where the plain code
    and the program's own run of the deck disagree."""
    e_cell = float(plain["energy_total_ha"])
    moment = float(plain["moment_total_ub"])
    witness = dict(
        witness,
        minus_plain_ha_per_cell=witness["energy_per_cell_ha"] - e_cell,
        moment_minus_plain_ub=witness["moment_total_ub"] - moment)
    if not abs(witness["minus_plain_ha_per_cell"]) <= WITNESS_TOL_HA_PER_CELL:
        raise RuntimeError(
            f"plain_pwus_spin ({e_cell!r}) and the program's own run "
            f"({witness['energy_per_cell_ha']!r}) differ by more than "
            f"{WITNESS_TOL_HA_PER_CELL} Ha a cell: one of them is wrong")
    if not abs(witness["moment_minus_plain_ub"]) <= WITNESS_TOL_UB:
        raise RuntimeError(
            f"plain_pwus_spin ({moment!r} mu_B) and the program's own run "
            f"({witness['moment_total_ub']!r}) differ by more than "
            f"{WITNESS_TOL_UB} mu_B: one of them is wrong")
    p = deck["parameters"]
    return {
        "energy_total_ha": e_cell,
        "moment_total_ub": moment,
        "scf_iterations": int(plain["iterations"]),
        "wall_s_cpu": round(float(plain["wall_s"]), 1),
        "kmesh_run": {
            "by": "benchmark/plain_pwus_spin.py", "cells": 1,
            "energy_per_cell_ha": e_cell, "moment_total_ub": moment,
            "start_moment_ub_per_atom": start_moment(deck),
            "ngridk": p["ngridk"], "num_bands": p["num_bands"],
            "num_kpoints": int(plain["num_kpoints"]), "box": plain["box"],
            "efermi_ha": plain["efermi"],
            "bands_occupied": plain["bands_occupied"],
            "last_band_occupation": plain["last_band_occupation"],
            "terms_ha_per_cell": {k: plain[k] for k in TERMS}},
        "nonmagnetic_run": {
            "by": "benchmark/plain_pwus_spin.py, start_moment 0",
            "energy_per_cell_ha": float(nonmag["energy_total_ha"]),
            "scf_iterations": int(nonmag["iterations"]),
            "wall_s_cpu": round(float(nonmag["wall_s"]), 1),
            "e_fm_minus_e_nm_ha": e_cell - float(nonmag["energy_total_ha"])},
        "witness_run_scf": witness}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--block", default="deck", choices=["deck", "rehearse"])
    ap.add_argument("--workers", type=int, default=1,
                    help="k-points the plain code solves at a time; with "
                    "more than one set OPENBLAS_NUM_THREADS=1")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmark.harness import decks

    cdir = os.path.join(HERE, "configs", args.config)
    with open(os.path.join(cdir, "config.json")) as f:
        config = json.load(f)
    geometry = dict(config["geometry"], **config[args.block].get("geometry", {}))
    if (int(geometry.get("supercell", 1)) != 1
            or float(geometry["displacement_bohr"]) != 0.0):
        raise ValueError("the ideal 2-atom cell only")
    deck = decks.reference_deck(config, 0, args.block)
    plain, nonmag = plain_runs(
        deck, args.workers, log=lambda line: print(line, file=sys.stderr))
    entry = entry_of(deck, plain, nonmag, witness_run(deck))
    name = "refs.json" if args.block == "deck" else "refs_rehearse.json"
    refs = {"config": args.config, "how": config["reference"]["how"],
            "geometries": {"0": entry}}
    with open(os.path.join(cdir, name), "w") as f:
        json.dump(refs, f, indent=1)
        f.write("\n")
    print(json.dumps({"config": args.config, "block": args.block, **entry}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
