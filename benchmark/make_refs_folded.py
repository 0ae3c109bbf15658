#!/usr/bin/env python3
"""Write configs/<config>/refs.json of a Gamma supercell configuration by
folding: the undisplaced n x n x n supercell of the 2-atom cell, at Gamma, is
the 2-atom cell on the Gamma-centred n x n x n k-mesh (|G+k| < gk_cutoff of
the small cell is |G| < gk_cutoff of the large one, the density sphere and
the smearing are the same), so

  E_ref(supercell) = n^3 * E(2-atom cell, ngridk [n,n,n], num_bands / n^3).

The right side is computed twice, and the two are told apart in the file:

- `energy_total_ha`, the number `correct` is decided by, comes from
  benchmark/plain_pwus.py: a plain numpy float64 plane-wave ultrasoft SCF
  that imports nothing of sirius_tpu (dense H and S, LAPACK, its own
  transforms of the species, its own XC, Ewald sum, energy functional and
  mixer). A wrong functional in the program cannot cancel against it.
- `witness_run_scf` is the program's own k-mesh run of the same deck
  (run_scf on the CPU backend in f64, the batched k-set solve in complex128
  with the fused tail): a second witness only. The script refuses to write
  the file where the two differ by more than 1e-6 Ha a cell, a tenth of the
  bar of 1e-5 Ha a cell.

It holds for ideal positions only (`displacement_bohr` 0: atoms that move on
their own do not fold), so such a configuration has one geometry.

  python benchmark/make_refs_folded.py --config si54-gamma-us
  python benchmark/make_refs_folded.py --config si54-gamma-us --block rehearse
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # the checkout: sirius_tpu, benchmark


def folded_deck(config: dict, block: str = "deck") -> tuple[dict, int]:
    """The 2-atom k-mesh deck that the configuration's supercell folds onto,
    with the configuration's ``reference`` overrides, and n^3."""
    from benchmark.harness import decks

    geometry = dict(config["geometry"], **config[block].get("geometry", {}))
    n = int(geometry.get("supercell", 1))
    if float(geometry["displacement_bohr"]) != 0.0:
        raise ValueError("only the undisplaced supercell folds onto the "
                         "k-mesh of the 2-atom cell: displacement_bohr is "
                         f"{geometry['displacement_bohr']}")
    params = config[block]["parameters"]
    if list(params["ngridk"]) != [1, 1, 1]:
        raise ValueError(f"not a Gamma deck: ngridk {params['ngridk']}")
    if int(params["num_bands"]) % n ** 3:
        raise ValueError(f"num_bands {params['num_bands']} is not a multiple "
                         f"of {n ** 3}")
    small = copy.deepcopy(config)
    small["geometry"] = dict(geometry, supercell=1)
    small[block].pop("geometry", None)
    deck = decks.reference_deck(small, 0, block)
    deck["parameters"].update(ngridk=[n, n, n], use_symmetry=False,
                              num_bands=int(params["num_bands"]) // n ** 3)
    return deck, n ** 3


WITNESS_TOL_HA_PER_CELL = 1e-6


def plain_energy(deck: dict, log=None) -> dict:
    """The k-mesh deck through benchmark/plain_pwus.py, which knows one
    problem: the synthetic ultrasoft silicon, LDA (X + PZ), Gaussian
    smearing. Anything else in the deck is refused, not approximated."""
    from benchmark import plain_pwus

    p = deck["parameters"]
    if list(p["xc_functionals"]) != ["XC_LDA_X", "XC_LDA_C_PZ"]:
        raise ValueError(f"plain_pwus has no {p['xc_functionals']}")
    syn = deck.get("synthetic", {})
    if (syn.get("ultrasoft") is not True or deck.get("unit_cell")
            or syn.get("positions") != [[0.0, 0.0, 0.0], [0.25, 0.25, 0.25]]
            or set(syn) - {"ultrasoft", "a", "positions"}):
        raise ValueError("plain_pwus knows the 2-atom diamond cell of the "
                         "synthetic ultrasoft silicon only")
    if p.get("smearing", "gaussian") != "gaussian" or p.get("use_symmetry"):
        raise ValueError("plain_pwus: Gaussian smearing, no symmetry")
    return plain_pwus.scf(
        ngridk=tuple(p["ngridk"]), gk_cutoff=float(p["gk_cutoff"]),
        pw_cutoff=float(p["pw_cutoff"]), num_bands=int(p["num_bands"]),
        smearing_width=float(p["smearing_width"]),
        lattice_constant=float(syn["a"]), density_tol=1e-12, log=log)


def witness_energy(deck: dict) -> dict:
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
        raise RuntimeError("the program's k-mesh run did not converge")
    return {"energy_per_cell_ha": float(r["energy"]["total"]),
            "scf_iterations": int(r["num_scf_iterations"]),
            "num_kpoints": int(ctx.gkvec.num_kpoints),
            "path": r["placement"]["path"],
            "wall_s_cpu": round(time.time() - t0, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--block", default="deck", choices=["deck", "rehearse"])
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cdir = os.path.join(HERE, "configs", args.config)
    with open(os.path.join(cdir, "config.json")) as f:
        config = json.load(f)
    deck, cells = folded_deck(config, args.block)
    t0 = time.time()
    plain = plain_energy(deck, log=lambda line: print(line, file=sys.stderr))
    wall = time.time() - t0
    if not plain["converged"]:
        raise RuntimeError("plain_pwus did not converge")
    e_cell = float(plain["energy_total_ha"])
    witness = witness_energy(deck)
    witness["minus_plain_ha_per_cell"] = witness["energy_per_cell_ha"] - e_cell
    if abs(witness["minus_plain_ha_per_cell"]) > WITNESS_TOL_HA_PER_CELL:
        raise RuntimeError(
            f"plain_pwus ({e_cell!r}) and the program's own k-mesh run "
            f"({witness['energy_per_cell_ha']!r}) differ by more than "
            f"{WITNESS_TOL_HA_PER_CELL} Ha a cell: one of them is wrong")
    entry = {"energy_total_ha": cells * e_cell,
             "scf_iterations": int(plain["iterations"]),
             "wall_s_cpu": round(wall, 1),
             "kmesh_run": {"by": "benchmark/plain_pwus.py", "cells": cells,
                           "energy_per_cell_ha": e_cell,
                           "ngridk": deck["parameters"]["ngridk"],
                           "num_bands": deck["parameters"]["num_bands"],
                           "num_kpoints": int(plain["num_kpoints"]),
                           "box": plain["box"],
                           "terms_ha_per_cell": {
                               k: plain[k] for k in
                               ("kinetic", "nonlocal", "local", "hartree",
                                "xc", "ewald")}},
             "witness_run_scf": witness}
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
