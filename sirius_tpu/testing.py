"""Self-contained synthetic systems for benchmarks, compile checks and the
multi-chip dry run — no species files needed: an analytic erf-Coulomb local
potential plus Gaussian beta projectors with a small augmentation channel,
shaped like a real ultrasoft silicon run."""

from __future__ import annotations

import numpy as np

from sirius_tpu.config.schema import Config
from sirius_tpu.context import SimulationContext
from sirius_tpu.crystal.atom_type import (
    AtomType,
    AtomicWf,
    AugmentationChannel,
    BetaProjector,
)


def synthetic_silicon_type(zn: float = 4.0, ultrasoft: bool = True,
                           nr: int = 700) -> AtomType:
    from scipy.special import erf

    r = np.geomspace(1e-6, 12.0, nr)
    vloc = -zn * erf(r) / r
    # two beta channels (l=0, l=1), smooth nodeless shapes (r*beta(r))
    rb0 = r * np.exp(-(r**2)) * 2.0
    rb1 = r * r * np.exp(-(r**2)) * 1.5
    betas = [BetaProjector(l=0, rbeta=rb0, nr=len(r)), BetaProjector(l=1, rbeta=rb1, nr=len(r))]
    d_ion = np.array([[0.8, 0.0], [0.0, 0.4]])
    aug = []
    if ultrasoft:
        # one l=0 augmentation channel per radial pair (r^2-weighted Gaussians)
        q00 = 0.05 * r**2 * np.exp(-2.0 * r**2)
        q11 = 0.03 * r**2 * np.exp(-2.0 * r**2)
        aug = [
            AugmentationChannel(i=0, j=0, l=0, qr=q00),
            AugmentationChannel(i=1, j=1, l=0, qr=q11),
        ]
    wfs = [
        AtomicWf(l=0, occupation=2.0, chi=r * np.exp(-0.8 * r), label="3S"),
        AtomicWf(l=1, occupation=2.0, chi=r * r * np.exp(-0.8 * r), label="3P"),
    ]
    rho = 4.0 * np.pi * r**2 * (zn * 0.4**3 / np.pi) * np.exp(-0.8 * r) * 0.5
    return AtomType(
        label="Si", symbol="Si", zn=zn, pseudo_type="US" if ultrasoft else "NC",
        r=r, vloc=vloc, beta=betas, d_ion=d_ion, augmentation=aug,
        atomic_wfs=wfs, rho_total=rho, rho_core=None, core_correction=False,
    )


# the d-shell species' numbers, fixed by f64 runs of the deck fm2-k444-us
# (benchmark/configs/fm2-k444-us/config.json has the readings)
DSHELL_ZN = 8.0
DSHELL_D_ION = (2.0, 3.0, -6.0)
DSHELL_WIDTH = 0.4
# radial points: on the silicon type's 700 the spline integrals of the narrow
# channel leave 7e-7 Ha a cell against its closed-form transforms, on 1400
# 1e-7 (f64 CPU runs of the deck on the 2x2x2 mesh, PR 45)
DSHELL_NR = 1400


def synthetic_dshell_type(ultrasoft: bool = True) -> AtomType:
    """A synthetic species with an open d-like shell: the silicon type's grid
    (at DSHELL_NR points), erf-Coulomb local potential at DSHELL_ZN, l = 0 and
    l = 1 Gaussian channels and two l = 0 augmentation channels, plus one
    narrow attractive l = 2 channel
    r*beta_2(r) ~ r^3 exp(-r^2 / (2 w^2)), normalised to int (r beta)^2 dr =
    1. In the 2-atom diamond cell it holds a ferromagnetic ground state
    several 1e-2 Ha under the non-magnetic one. No real material: every
    radial function is a Gaussian times a power, so its Bessel transforms
    have closed forms (benchmark/plain_pwus_spin.py restates them)."""
    zn = DSHELL_ZN
    si = synthetic_silicon_type(zn=zn, ultrasoft=ultrasoft, nr=DSHELL_NR)
    r = si.r
    w = DSHELL_WIDTH
    # int r^6 exp(-r^2 / w^2) dr = 15 sqrt(pi) w^7 / 16
    rb2 = r**3 * np.exp(-(r**2) / (2 * w * w))
    rb2 /= np.sqrt(15.0 * np.sqrt(np.pi) * w**7 / 16.0)
    wfs = [
        AtomicWf(l=0, occupation=2.0, chi=r * np.exp(-0.8 * r), label="S"),
        AtomicWf(l=1, occupation=0.0, chi=r * r * np.exp(-0.8 * r), label="P"),
        AtomicWf(l=2, occupation=zn - 2.0, chi=r**3 * np.exp(-1.2 * r), label="D"),
    ]
    return AtomType(
        label="Xd", symbol="Xd", zn=zn, pseudo_type=si.pseudo_type,
        r=r, vloc=si.vloc,
        beta=si.beta + [BetaProjector(l=2, rbeta=rb2, nr=len(r))],
        d_ion=np.diag(DSHELL_D_ION), augmentation=si.augmentation,
        atomic_wfs=wfs, rho_total=si.rho_total, rho_core=None,
        core_correction=False, mass=50.0,
    )


SYNTHETIC_SPECIES = {"si": synthetic_silicon_type, "dshell": synthetic_dshell_type}


def synthetic_cell(species: str = "si", ultrasoft: bool = True, a: float = 10.26,
                   positions=None, supercell: int = 1, moments=None):
    """The diamond-like cell of a synthetic species as a UnitCell: the
    2-atom fcc cell of lattice constant ``a``, or ``positions`` (fractional)
    in it, tiled ``supercell`` times a direction. ``moments``: one starting
    moment vector for every atom, or one vector an atom of the tiled cell."""
    import sirius_tpu.crystal.unit_cell as ucm

    if species not in SYNTHETIC_SPECIES:
        raise ValueError(f"unknown synthetic species {species!r} "
                         f"(have {sorted(SYNTHETIC_SPECIES)})")
    t = SYNTHETIC_SPECIES[species](ultrasoft=ultrasoft)
    lattice = a / 2 * np.array([[0.0, 1, 1], [1, 0, 1], [1, 1, 0]])
    if positions is None:
        positions = [[0.0, 0, 0], [0.25, 0.25, 0.25]]
    positions = np.asarray(positions, dtype=np.float64)
    n = int(supercell)
    if n > 1:
        shifts = np.array(
            [[i, j, k] for i in range(n) for j in range(n) for k in range(n)],
            dtype=np.float64,
        )
        positions = (
            (positions[None, :, :] + shifts[:, None, :]) / n
        ).reshape(-1, 3)
        lattice = lattice * n
    nat = len(positions)
    if moments is None:
        moments = np.zeros((nat, 3))
    moments = np.asarray(moments, dtype=np.float64)
    if moments.shape == (3,):
        moments = np.tile(moments, (nat, 1))
    if moments.shape != (nat, 3):
        raise ValueError(f"moments of shape {moments.shape}: one [mx, my, mz] "
                         f"for all atoms or one for each of the {nat}")
    return ucm.UnitCell(
        lattice=lattice, atom_types=[t],
        type_of_atom=np.zeros(nat, dtype=np.int32),
        positions=positions, moments=moments,
    )


def context_of_cell(cfg, uc, base_dir: str = ".") -> SimulationContext:
    """SimulationContext.create reads species from files; hand it a built
    cell instead (same code path below the unit-cell level). Not
    thread-safe: serve/scheduler.py holds its lock around it."""
    import sirius_tpu.crystal.unit_cell as ucm

    orig = ucm.UnitCell.from_config
    try:
        ucm.UnitCell.from_config = staticmethod(lambda c, b=".": uc)
        return SimulationContext.create(cfg, base_dir)
    finally:
        ucm.UnitCell.from_config = orig


def synthetic_silicon_context(
    gk_cutoff: float = 6.0,
    pw_cutoff: float = 20.0,
    ngridk=(2, 2, 2),
    num_bands: int | None = None,
    ultrasoft: bool = True,
    use_symmetry: bool = True,
    positions: np.ndarray | None = None,
    extra_params: dict | None = None,
    moments: np.ndarray | None = None,
    supercell: int = 1,
) -> SimulationContext:
    """Diamond-Si-like 2-atom cell with the synthetic species.

    supercell=n replicates the cell n x n x n (2 n^3 atoms) — the
    Si-supercell-class bench tier (BASELINE.md flagship regime).
    ``moments``: one vector for all atoms or one an atom of the tiled
    cell (synthetic_cell)."""
    params = {
        "gk_cutoff": gk_cutoff,
        "pw_cutoff": pw_cutoff,
        "ngridk": list(ngridk),
        "use_symmetry": use_symmetry,
        "num_bands": num_bands if num_bands else -1,
        "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"],
        "smearing_width": 0.025,
    }
    if extra_params:
        params.update(extra_params)
    cfg = Config.from_dict({"parameters": params})
    uc = synthetic_cell("si", ultrasoft=ultrasoft, positions=positions,
                        supercell=supercell, moments=moments)
    return context_of_cell(cfg, uc)


# --------------------------------------------------------------------------
# Runtime lock-order monitor (sirius-lint's dynamic counterpart)
#
# The static lock rules in sirius_tpu.analysis.lockrules prove the absence
# of ordering cycles over the *declared* call graph; this shim checks the
# orders that actually happen at runtime, including paths the static model
# cannot resolve (dynamic dispatch, callbacks crossing threads).  Within a
# monitoring window every threading.Lock/RLock *created* in a matching
# source file is wrapped; each acquisition while other monitored locks are
# held records a directed edge (held -> acquired).  Seeing both A->B and
# B->A — or any longer cycle — is a latent deadlock even if this particular
# run never interleaved badly.

import sys as _sys
import threading as _threading


class _MonitoredLock:
    """Wraps a real Lock/RLock; delegates Condition's private protocol."""

    def __init__(self, inner, name, monitor, reentrant):
        self._sl_inner = inner
        self._sl_name = name
        self._sl_mon = monitor
        self._sl_reentrant = reentrant

    def acquire(self, blocking=True, timeout=-1):
        ok = self._sl_inner.acquire(blocking, timeout)
        if ok:
            self._sl_mon._note_acquire(self)
        return ok

    def release(self):
        self._sl_mon._note_release(self)
        self._sl_inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()

    def locked(self):
        return self._sl_inner.locked()

    # Condition(lock) probes for these via hasattr and, finding them here,
    # uses them for wait()'s release/reacquire — keep the held-stack honest.
    def _release_save(self):
        self._sl_mon._note_release(self, all_recursion=True)
        inner = self._sl_inner
        if hasattr(inner, "_release_save"):
            return inner._release_save()
        inner.release()
        return None

    def _acquire_restore(self, state):
        inner = self._sl_inner
        if hasattr(inner, "_acquire_restore"):
            inner._acquire_restore(state)
        else:
            inner.acquire()
        self._sl_mon._note_acquire(self)

    def _is_owned(self):
        inner = self._sl_inner
        if hasattr(inner, "_is_owned"):
            return inner._is_owned()
        # plain Lock: non-blocking probe on the raw lock (not monitored)
        if inner.acquire(False):
            inner.release()
            return False
        return True

    def __repr__(self):
        return f"<MonitoredLock {self._sl_name}>"


class LockOrderMonitor:
    """Patch threading.Lock/RLock in a window and record acquisition order.

    Usage::

        with LockOrderMonitor(scope="sirius_tpu/serve") as mon:
            ...exercise the code...
        mon.assert_clean()

    Only locks whose creation site's filename contains ``scope`` are
    wrapped; everything else gets the real lock, so third-party code in
    the window is unaffected.  Edges and violations survive ``__exit__``
    (wrapped locks keep reporting), so a module-scoped pytest fixture can
    assert once at teardown.
    """

    def __init__(self, scope: str = "sirius_tpu/serve"):
        self.scope = scope
        self.edges: dict[tuple[str, str], tuple[str, str]] = {}
        self.violations: list[str] = []
        self._tls = _threading.local()
        self._state = _threading.Lock()  # guards edges/violations
        self._orig_lock = None
        self._orig_rlock = None

    # -- patch window ------------------------------------------------------

    def _creation_site(self):
        f = _sys._getframe(2)
        while f is not None:
            fn = f.f_code.co_filename.replace("\\", "/")
            if __file__.replace("\\", "/") != fn and "threading" not in fn:
                return fn, f.f_lineno
            f = f.f_back
        return "<unknown>", 0

    def _factory(self, orig, reentrant):
        def make(*a, **kw):
            inner = orig(*a, **kw)
            fn, line = self._creation_site()
            if self.scope not in fn:
                return inner
            name = f"{fn.rsplit('/sirius_tpu/', 1)[-1]}:{line}"
            return _MonitoredLock(inner, name, self, reentrant)
        return make

    def __enter__(self):
        self._orig_lock = _threading.Lock
        self._orig_rlock = _threading.RLock
        _threading.Lock = self._factory(self._orig_lock, reentrant=False)
        _threading.RLock = self._factory(self._orig_rlock, reentrant=True)
        return self

    def __exit__(self, *exc):
        _threading.Lock = self._orig_lock
        _threading.RLock = self._orig_rlock
        return False

    # -- recording ---------------------------------------------------------

    def _held(self):
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _note_acquire(self, lock):
        stack = self._held()
        tname = _threading.current_thread().name
        new = lock._sl_name
        with self._state:
            for held in stack:
                if held is lock:
                    continue  # RLock reentry: not an ordering edge
                a, b = held._sl_name, new
                if a == b:
                    continue
                self.edges.setdefault((a, b), (tname, ""))
                if (b, a) in self.edges:
                    other = self.edges[(b, a)][0]
                    self.violations.append(
                        f"lock-order inversion: {a} -> {b} (thread {tname})"
                        f" vs {b} -> {a} (thread {other})"
                    )
        stack.append(lock)

    def _note_release(self, lock, all_recursion=False):
        stack = self._held()
        if all_recursion:
            self._tls.stack = [h for h in stack if h is not lock]
            return
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is lock:
                del stack[i]
                return

    # -- verdict -----------------------------------------------------------

    def _cycles(self):
        graph: dict[str, set[str]] = {}
        for a, b in self.edges:
            graph.setdefault(a, set()).add(b)
        cycles, done = [], set()
        def dfs(node, path, on_path):
            if node in on_path:
                cycles.append(path[path.index(node):])
                return
            if node in done:
                return
            on_path.add(node)
            for nxt in graph.get(node, ()):
                dfs(nxt, path + [nxt], on_path)
            on_path.discard(node)
            done.add(node)
        for start in list(graph):
            dfs(start, [start], set())
        return cycles

    def assert_clean(self):
        problems = list(self.violations)
        for cyc in self._cycles():
            problems.append("lock-order cycle: " + " -> ".join(cyc))
        if problems:
            raise AssertionError(
                "LockOrderMonitor found %d problem(s):\n  %s"
                % (len(problems), "\n  ".join(sorted(set(problems))))
            )
