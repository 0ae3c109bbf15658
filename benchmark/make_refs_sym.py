#!/usr/bin/env python3
"""Write configs/<config>/refs.json of a configuration that runs with the
crystal's symmetry on: an exact symmetry changes no energy, so the answer of
the irreducible wedge with its weights is the answer of the whole mesh with
equal weights.

- `energy_total_ha`, the number `correct` is decided by, comes from
  benchmark/plain_pwus.py (numpy float64, dense H and S at every k-point,
  imports nothing of sirius_tpu) on ALL ngridk[0] x ngridk[1] x ngridk[2]
  k-points: no symmetry, no time reversal, equal weights. About 25 minutes
  for the 6x6x6 mesh at gk 6 / pw 20 / 26 bands.
- `witness_run_scf` is the program's own f64 run of the deck as it stands
  (use_symmetry true: the wedge, the fused step's symmetrisation), CPU
  backend. The script refuses to write where the two differ by more than
  1e-6 Ha a cell.

Ideal positions of the 2-atom cell only (what plain_pwus knows).

  python benchmark/make_refs_sym.py --config si2-k666-us-sym
  python benchmark/make_refs_sym.py --config si2-k666-us-sym --block rehearse
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

from benchmark.make_refs_folded import plain_energy, witness_energy  # noqa: E402
from benchmark.make_refs_folded_kmesh import entry_of  # noqa: E402


def decks_of(config: dict, block: str = "deck") -> tuple[dict, dict]:
    """(the deck as the witness runs it: the configuration's own, symmetry
    on, with its ``reference`` overrides; the same deck with symmetry off,
    which is what the plain code computes)."""
    from benchmark.harness import decks

    geometry = dict(config["geometry"], **config[block].get("geometry", {}))
    if int(geometry.get("supercell", 1)) != 1:
        raise ValueError("the 2-atom cell only")
    if float(geometry["displacement_bohr"]) != 0.0:
        raise ValueError("a displaced atom leaves no symmetry to check: "
                         f"displacement_bohr is {geometry['displacement_bohr']}")
    sym_deck = decks.reference_deck(config, 0, block)
    if not sym_deck["parameters"].get("use_symmetry", True):
        raise ValueError("the configuration runs without symmetry: its "
                         "reference is make_refs.py's")
    full_deck = copy.deepcopy(sym_deck)
    full_deck["parameters"]["use_symmetry"] = False
    return sym_deck, full_deck


def reference_entry(config: dict, block: str = "deck", plain: dict | None = None,
                    log=None) -> dict:
    """The stored record. ``plain``: plain_pwus.scf's result of the full
    mesh, where a caller has it already (the run takes 25 minutes)."""
    sym_deck, full_deck = decks_of(config, block)
    t0 = time.time()
    if plain is None:
        plain = plain_energy(full_deck, log=log)
        plain["wall_s"] = time.time() - t0
    witness = witness_energy(sym_deck)
    mesh = [int(m) for m in full_deck["parameters"]["ngridk"]]
    if int(plain["num_kpoints"]) != mesh[0] * mesh[1] * mesh[2]:
        raise RuntimeError(f"plain_pwus ran {plain['num_kpoints']} k-points, "
                           f"the mesh has {mesh[0] * mesh[1] * mesh[2]}")
    return entry_of(full_deck, 1, plain, witness, float(plain["wall_s"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--block", default="deck", choices=["deck", "rehearse"])
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cdir = os.path.join(HERE, "configs", args.config)
    with open(os.path.join(cdir, "config.json")) as f:
        config = json.load(f)
    entry = reference_entry(
        config, args.block,
        log=lambda line: print(line, file=sys.stderr))
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
