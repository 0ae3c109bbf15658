"""From a configuration file to the deck dictionary of one job.

A configuration's ``deck`` is the deck as `sirius-scf`/`ServeEngine` take it
(``parameters``, ``control``, ``synthetic``). Its ``geometry`` block is the
rule that makes geometry ``g``: the ideal positions of the (super)cell plus,
for every atom but the first, a displacement uniform in +-``displacement_bohr``
per Cartesian component from ``numpy.random.default_rng(rng_base + g)``.
The supercell is written out here (explicit positions, lattice constant
times n), so that each of its atoms moves on its own.
"""

from __future__ import annotations

import copy

import numpy as np

A_SI = 10.26  # bohr; build_job_context's default lattice constant


def ideal_positions(supercell: int) -> np.ndarray:
    """Fractional positions of the n x n x n supercell of 2-atom fcc Si, in
    the order serve/scheduler.build_job_context makes them."""
    base = np.array([[0.0, 0.0, 0.0], [0.25, 0.25, 0.25]])
    n = int(supercell)
    shifts = np.array([[i, j, k] for i in range(n) for j in range(n)
                       for k in range(n)], dtype=np.float64)
    return ((base[None, :, :] + shifts[:, None, :]) / n).reshape(-1, 3)


def geometry_positions(geometry: dict, g: int) -> np.ndarray:
    n = int(geometry.get("supercell", 1))
    a = float(geometry.get("a", A_SI)) * n
    lattice = a / 2 * np.array([[0.0, 1, 1], [1, 0, 1], [1, 1, 0]])
    pos = ideal_positions(n)
    rng = np.random.default_rng(int(geometry["rng_base"]) + int(g))
    amp = float(geometry["displacement_bohr"])
    d_cart = rng.uniform(-amp, amp, size=(len(pos) - 1, 3))
    # rows of `lattice` are the lattice vectors: cart = frac @ lattice
    pos[1:] += d_cart @ np.linalg.inv(lattice)
    return pos


def job_deck(config: dict, g: int, block: str = "deck") -> dict:
    """Deck of geometry ``g``. ``block`` is "deck" (the timed one) or
    "rehearse" (the tiny CPU stand-in); ``reference`` overrides are applied
    by reference_deck()."""
    src = config[block]
    geometry = dict(config["geometry"], **src.get("geometry", {}))
    deck = {k: copy.deepcopy(v) for k, v in src.items() if k != "geometry"}
    n = int(geometry.get("supercell", 1))
    syn = dict(deck.get("synthetic", {}))
    syn.pop("supercell", None)
    syn["a"] = float(geometry.get("a", A_SI)) * n
    syn["positions"] = geometry_positions(geometry, g).tolist()
    deck["synthetic"] = syn
    return deck


def reference_deck(config: dict, g: int, block: str = "deck") -> dict:
    """The plain path's deck: the same geometry with the configuration's
    ``reference`` overrides (f64, host path, tight tolerances)."""
    deck = job_deck(config, g, block)
    for section, over in config["reference"]["overrides"].items():
        deck.setdefault(section, {}).update(over)
    return deck


def atoms(config: dict, block: str = "deck") -> int:
    geometry = dict(config["geometry"], **config[block].get("geometry", {}))
    return 2 * int(geometry.get("supercell", 1)) ** 3
