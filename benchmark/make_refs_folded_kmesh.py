#!/usr/bin/env python3
"""Write configs/<config>/refs.json of a supercell-on-a-k-mesh configuration
by folding. make_refs_folded.py knows the Gamma-only supercell; this is the
same rule with a mesh on the supercell: the undisplaced n x n x n supercell
of the 2-atom cell on the Gamma-centred m x m x m k-mesh is the 2-atom cell
on the Gamma-centred nm x nm x nm k-mesh. The supercell's k-points
K = (i/m) b' (b' = b/n) and the n^3 images K + j b' of each that lie in the
small cell's zone are together the (nm)^3 points (i/(nm)) b, each once, so
the weights are equal; |G+k| < gk_cutoff is the same sphere, the density
sphere and the smearing are the same. So

  E_ref(supercell, mesh m) = n^3 * E(2-atom cell, ngridk [nm,nm,nm],
                                     num_bands / n^3).

As in make_refs_folded.py the right side is computed twice:
`energy_total_ha`, which decides `correct`, by benchmark/plain_pwus.py (numpy
float64, dense H and S at every k-point, imports nothing of sirius_tpu), and
`witness_run_scf` by the program's own f64 run of the folded 2-atom deck; the
script refuses to write where the two differ by more than 1e-6 Ha a cell.

Ideal positions and an unshifted mesh only: atoms that move on their own do
not fold, and a shifted mesh of the supercell is not a Gamma-centred mesh of
the small cell.

  python benchmark/make_refs_folded_kmesh.py --config si16-k222-us
  python benchmark/make_refs_folded_kmesh.py --config si16-k222-us --block rehearse
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

from benchmark.make_refs_folded import (  # noqa: E402
    WITNESS_TOL_HA_PER_CELL,
    plain_energy,
    witness_energy,
)


def folded_deck(config: dict, block: str = "deck") -> tuple[dict, int]:
    """The 2-atom deck on the mesh n*m that the configuration's supercell n
    on its mesh m folds onto, with the configuration's ``reference``
    overrides, and n^3."""
    from benchmark.harness import decks

    geometry = dict(config["geometry"], **config[block].get("geometry", {}))
    n = int(geometry.get("supercell", 1))
    if float(geometry["displacement_bohr"]) != 0.0:
        raise ValueError("only the undisplaced supercell folds onto the "
                         "k-mesh of the 2-atom cell: displacement_bohr is "
                         f"{geometry['displacement_bohr']}")
    params = config[block]["parameters"]
    mesh = [int(m) for m in params["ngridk"]]
    if any(int(s) for s in params.get("shiftk", [0, 0, 0])):
        raise ValueError(f"not a Gamma-centred mesh: shiftk {params['shiftk']}")
    if len(mesh) != 3 or min(mesh) < 1:
        raise ValueError(f"not a k-mesh: ngridk {params['ngridk']}")
    if int(params["num_bands"]) % n ** 3:
        raise ValueError(f"num_bands {params['num_bands']} is not a multiple "
                         f"of {n ** 3}")
    small = copy.deepcopy(config)
    small["geometry"] = dict(geometry, supercell=1)
    small[block].pop("geometry", None)
    deck = decks.reference_deck(small, 0, block)
    deck["parameters"].update(ngridk=[n * m for m in mesh], use_symmetry=False,
                              num_bands=int(params["num_bands"]) // n ** 3)
    return deck, n ** 3


def entry_of(deck: dict, cells: int, plain: dict, witness: dict,
             wall_s: float) -> dict:
    """The stored record of one geometry; raises where the plain code and
    the program's own run of the same folded deck disagree."""
    if not plain["converged"]:
        raise RuntimeError("plain_pwus did not converge")
    e_cell = float(plain["energy_total_ha"])
    witness = dict(witness)
    witness["minus_plain_ha_per_cell"] = witness["energy_per_cell_ha"] - e_cell
    if not abs(witness["minus_plain_ha_per_cell"]) <= WITNESS_TOL_HA_PER_CELL:
        raise RuntimeError(
            f"plain_pwus ({e_cell!r}) and the program's own k-mesh run "
            f"({witness['energy_per_cell_ha']!r}) differ by more than "
            f"{WITNESS_TOL_HA_PER_CELL} Ha a cell: one of them is wrong")
    return {"energy_total_ha": cells * e_cell,
            "scf_iterations": int(plain["iterations"]),
            "wall_s_cpu": round(wall_s, 1),
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
    entry = entry_of(deck, cells, plain, witness_energy(deck), wall)
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
