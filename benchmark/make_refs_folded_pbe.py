#!/usr/bin/env python3
"""Write configs/<config>/refs.json of a Gamma supercell configuration under
PBE by folding. make_refs_folded.py is this script for LDA (it is the
benchmark's, and its plain code knows LDA only); the rule and the folded deck
are its own (`folded_deck`, `witness_energy` are imported from it, the
stored record and its refusals from make_refs_folded_kmesh.py):

  E_ref(supercell) = n^3 * E(2-atom cell, ngridk [n,n,n], num_bands / n^3).

The right side is computed twice, and the two are told apart in the file:

- `energy_total_ha`, the number `correct` is decided by, comes from
  benchmark/plain_pwus_pbe.py: a plain numpy float64 plane-wave ultrasoft SCF
  with PBE exchange and correlation written out by hand, which imports
  nothing of sirius_tpu. A wrong functional, gradient or divergence in the
  program cannot cancel against it.
- `witness_run_scf` is the program's own f64 run of the folded deck: a second
  witness only. The script refuses to write where the two differ by more
  than 1e-6 Ha a cell, or where the plain run did not converge.

  python benchmark/make_refs_folded_pbe.py --config si16-gamma-us-pbe
  python benchmark/make_refs_folded_pbe.py --config si16-gamma-us-pbe --block rehearse
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # the checkout: sirius_tpu, benchmark

from benchmark.make_refs_folded import (  # noqa: E402
    folded_deck,
    witness_energy,
)
from benchmark.make_refs_folded_kmesh import (  # noqa: E402
    entry_of as kmesh_entry_of,
)

PLAIN = "benchmark/plain_pwus_pbe.py"
FUNCTIONALS = ["XC_GGA_X_PBE", "XC_GGA_C_PBE"]


def plain_energy(deck: dict, log=None) -> dict:
    """The folded deck through benchmark/plain_pwus_pbe.py, which knows one
    problem: the 2-atom cell of the synthetic ultrasoft silicon, PBE exchange
    + PBE correlation, Gaussian smearing, no symmetry. Anything else in the
    deck is refused, not approximated."""
    from benchmark import plain_pwus_pbe

    p = deck["parameters"]
    if list(p["xc_functionals"]) != FUNCTIONALS:
        raise ValueError(f"plain_pwus_pbe has no {p['xc_functionals']}")
    syn = deck.get("synthetic", {})
    if (syn.get("ultrasoft") is not True or deck.get("unit_cell")
            or syn.get("positions") != [[0.0, 0.0, 0.0], [0.25, 0.25, 0.25]]
            or set(syn) - {"ultrasoft", "a", "positions"}):
        raise ValueError("plain_pwus_pbe knows the 2-atom diamond cell of "
                         "the synthetic ultrasoft silicon only")
    if p.get("smearing", "gaussian") != "gaussian" or p.get("use_symmetry"):
        raise ValueError("plain_pwus_pbe: Gaussian smearing, no symmetry")
    return plain_pwus_pbe.scf(
        ngridk=tuple(p["ngridk"]), gk_cutoff=float(p["gk_cutoff"]),
        pw_cutoff=float(p["pw_cutoff"]), num_bands=int(p["num_bands"]),
        smearing_width=float(p["smearing_width"]),
        lattice_constant=float(syn["a"]), density_tol=1e-12, log=log)


def entry_of(deck: dict, cells: int, plain: dict, witness: dict,
             wall_s: float) -> dict:
    """The stored record of the one geometry, by make_refs_folded_kmesh's
    rules (it raises where the plain run did not converge or the plain code
    and the program's own run of the same folded deck disagree), naming this
    plain code and its functional."""
    entry = kmesh_entry_of(deck, cells, plain, witness, wall_s)
    entry["kmesh_run"].update(
        by=PLAIN, xc_functionals=list(deck["parameters"]["xc_functionals"]),
        rho_min=plain["rho_min"])
    return entry


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
