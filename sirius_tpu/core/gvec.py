"""G-vector engine: plane-wave sphere enumeration, shells, index maps.

Replaces the reference's fft::Gvec machinery (src/core/fft/gvec.hpp:124-1000).
The reference distributes G-vectors by z-columns for slab FFTs over MPI; on
TPU there is no slab decomposition — G-vectors live in a flat, |G|-sorted
array with a Miller->FFT-box index map, and distribution is handled by array
sharding over the mesh "g" axis (sirius_tpu.parallel).

All enumeration happens host-side in numpy at setup; the arrays consumed by
jitted code (cartesian G, |G|^2, FFT scatter indices, shell indices) are
uploaded once as device constants.

Conventions (matching the reference):
  - lattice: rows are lattice vectors a_i in bohr;
  - reciprocal: B = 2*pi*inv(A)^T, rows b_i;  G = h b1 + k b2 + l b3;
  - cutoffs are on |G| in bohr^-1 (pw_cutoff for the density/potential sphere,
    gk_cutoff for |G+k| wave-function spheres);
  - G-vectors sorted by (|G|^2, h, k, l); index 0 is G=0 for the density set.
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np

from sirius_tpu.core.fftgrid import FFTGrid

_SHELL_TOL = 1e-8


def reciprocal_lattice(lattice: np.ndarray) -> np.ndarray:
    """B with rows b_i such that a_i . b_j = 2 pi delta_ij."""
    a = np.asarray(lattice, dtype=np.float64)
    return 2.0 * np.pi * np.linalg.inv(a).T


def _enumerate_sphere(
    recip: np.ndarray, center: np.ndarray, gmax: float, fft: FFTGrid
) -> np.ndarray:
    """Miller indices h with |(h + center) . B| <= gmax, sorted by length then
    lexicographically. center is a fractional k-point (zero for the G set)."""
    # Sphere Miller bound along axis i: |h_i + c_i| <= gmax |a_i| / (2 pi),
    # so the box half-dims must cover t_i + |c_i|.
    a = 2.0 * np.pi * np.linalg.inv(recip).T  # rows a_i (recip = 2pi inv(A)^T)
    t = gmax * np.linalg.norm(a, axis=1) / (2.0 * np.pi)
    # enumeration covers h_i in [-(n_i//2), (n_i-1)//2]; the sphere needs
    # h_i in [ceil(-t_i - c_i), floor(t_i - c_i)] (asymmetric for even dims)
    dims = np.asarray(fft.dims)
    hi_need = np.floor(t - center + 1e-9).astype(int)
    lo_need = np.ceil(-t - center - 1e-9).astype(int)
    if np.any(hi_need > (dims - 1) // 2) or np.any(lo_need < -(dims // 2)):
        raise ValueError(
            f"FFT box {fft.dims} too small for |G+k| <= {gmax} sphere at "
            f"k={center}: need Miller range [{lo_need}, {hi_need}], have "
            f"[{-(dims // 2)}, {(dims - 1) // 2}]"
        )
    n1, n2, n3 = fft.dims
    h = np.arange(-(n1 // 2), (n1 - 1) // 2 + 1)
    k = np.arange(-(n2 // 2), (n2 - 1) // 2 + 1)
    l = np.arange(-(n3 // 2), (n3 - 1) // 2 + 1)
    hh, kk, ll = np.meshgrid(h, k, l, indexing="ij")
    millers = np.stack([hh.ravel(), kk.ravel(), ll.ravel()], axis=1)
    gc = (millers + center[None, :]) @ recip
    g2 = np.sum(gc * gc, axis=1)
    sel = g2 <= gmax * gmax + _SHELL_TOL
    millers = millers[sel]
    g2 = g2[sel]
    order = np.lexsort((millers[:, 2], millers[:, 1], millers[:, 0], np.round(g2, 10)))
    return millers[order]


_PHASE_ROWS = 1 << 16  # rows of the angle table one task of phase_factors takes
_SUM_ROWS = 1 << 13  # rows of a phase table one task of atom_sum transposes


def _on_row_blocks(fill, num_rows: int, rows: int) -> None:
    """``fill(lo)`` for every block of ``rows`` rows, on as many threads as
    the host has cores (numpy's loops release the interpreter lock)."""
    starts = range(0, num_rows, rows)
    workers = min(len(starts), os.cpu_count() or 1)
    if workers <= 1:
        for lo in starts:
            fill(lo)
    else:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(fill, starts))


def phase_factors(millers: np.ndarray, positions: np.ndarray,
                  sign: float = 1.0) -> np.ndarray:
    """exp(sign 2 pi i m . x) for integer Miller rows m [ng, 3] and
    fractional positions x [na, 3], shape [ng, na]: cos + i sin of the real
    angle, bit for bit what np.exp(sign * 2j * np.pi * (m @ x.T)) gives,
    row blocks on threads. A 54-atom cell has 53 million pairs: a context
    builds this table once for all its atoms (`AtomPhases`) and everything
    of a job that reads the fine G set takes it from there."""
    x = np.atleast_2d(np.asarray(positions, dtype=np.float64))
    dots = np.asarray(millers).reshape(-1, 3) @ x.T
    out = np.empty(dots.shape, dtype=np.complex128)

    def fill(lo):
        theta = (sign * 2.0 * np.pi) * dots[lo:lo + _PHASE_ROWS]
        out.real[lo:lo + _PHASE_ROWS] = np.cos(theta)
        out.imag[lo:lo + _PHASE_ROWS] = np.sin(theta)

    _on_row_blocks(fill, len(dots), _PHASE_ROWS)
    return out


def atom_sum(table: np.ndarray) -> np.ndarray:
    """Sum over the atoms (axis 1) of a phase table [ng, na], bit for bit
    what ``table[:, sel].sum(axis=1)`` gives under a ``sel`` of every atom,
    without that copy: numpy lays an indexed copy out atom by atom and so
    adds whole columns one after the other, where a sum along the rows of
    the table itself goes pairwise and rounds otherwise. Here a block of
    rows is transposed and its columns added in that order, on threads."""
    out = np.empty(len(table), dtype=table.dtype)

    def fill(lo):
        out[lo:lo + _SUM_ROWS] = np.asfortranarray(
            table[lo:lo + _SUM_ROWS]).sum(axis=1)

    _on_row_blocks(fill, len(table), _SUM_ROWS)
    return out


class AtomPhases:
    """e^{-2 pi i G.x_a} of every atom of a cell on a G set, ``table``
    [ng, natoms] complex128, read-only: built once a context
    (context._position_stage) and read by everything of the job that puts an
    atom on the fine G set (structure factors, Ewald sum, augmentation
    charge and D matrix on the host and the fused step's tables of them).
    The sign is the one the augmentation's contractions read as it stands;
    e^{+2 pi i G.x} is its conjugate, to the bit. It lives as long as its
    context and is in no memo: it is a function of the positions.
    What multiplies the Miller indices by ONE atom's position at a time
    (the starting magnetisation and the atomic moments of dft/density.py,
    the form-factor forces) rounds the angle through another BLAS routine
    and keeps its own np.exp: a column of this table is not that number.

    ``reads`` counts the requests the table served (counters.
    phase_table_reads of a job)."""

    def __init__(self, millers: np.ndarray, positions: np.ndarray):
        self.millers = millers
        self.positions = np.array(positions, dtype=np.float64)
        self.table = phase_factors(millers, self.positions, -1.0)
        self.table.setflags(write=False)
        self.reads = 0

    def minus(self, millers: np.ndarray, positions: np.ndarray,
              atoms=None) -> np.ndarray:
        """e^{-2 pi i G.x} of ``atoms`` (all of them when None) as columns,
        equal to the bit to ``phase_factors(millers, positions[atoms],
        -1.0)``. ``millers`` and ``positions`` are the caller's own and
        must be the table's."""
        if (len(millers) != len(self.table)
                or not np.array_equal(positions, self.positions)):
            raise ValueError("atom phases of another G set or of other "
                             "positions than the caller's")
        natoms = self.table.shape[1]
        if atoms is None:
            atoms = np.arange(natoms)
        atoms = np.asarray(atoms)
        if len(atoms) == 1 and natoms > 1:
            # numpy multiplies a matrix by ONE column through another BLAS
            # routine than by several, and the angle's last bit differs:
            # a lone atom of a type keeps the call it had
            return phase_factors(self.millers, self.positions[atoms], -1.0)
        self.reads += 1
        if np.array_equal(atoms, np.arange(natoms)):
            return self.table
        return self.table[:, atoms]


def minus_phases(phases: AtomPhases | None, millers: np.ndarray,
                 positions: np.ndarray, atoms=None) -> np.ndarray:
    """e^{-2 pi i m.x} [ng, len(atoms)] for a reader on the fine G set: from
    the job's table where its caller holds one, built here where not (a
    strained lattice of the stress calculator, a cell outside a context)."""
    if phases is not None:
        return phases.minus(millers, positions, atoms)
    return phase_factors(
        millers, positions if atoms is None else positions[atoms], -1.0)


def _shells(glen2: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Group |G|^2 values into shells within tolerance. Returns
    (shell_index per G, shell |G|^2 values)."""
    glen2 = np.asarray(glen2, dtype=np.float64)
    if len(glen2) == 0:
        return np.zeros(0, dtype=np.int32), np.zeros(0)
    # a shell opens where |G|^2 leaves the shell's first value by more than
    # the tolerance. In one pass over the steps between neighbours, kept
    # where it provably is that rule (every member within the tolerance of
    # its shell's first); else the rule itself, value by value.
    tol = _SHELL_TOL * np.maximum(1.0, glen2)
    opens = np.concatenate([[True], np.diff(glen2) > tol[1:]])
    shell_idx = (np.cumsum(opens) - 1).astype(np.int32)
    shell_g2 = glen2[opens]
    if np.all(np.abs(glen2 - shell_g2[shell_idx]) <= tol):
        return shell_idx, shell_g2
    shell_idx = np.zeros(len(glen2), dtype=np.int32)
    shell_g2 = []
    cur = -1.0
    ns = -1
    for i, g2 in enumerate(glen2):
        if ns < 0 or g2 - cur > _SHELL_TOL * max(1.0, g2):
            ns += 1
            cur = g2
            shell_g2.append(g2)
        shell_idx[i] = ns
    return shell_idx, np.asarray(shell_g2)


@dataclasses.dataclass(frozen=True)
class Gvec:
    """The |G| <= gmax plane-wave set of a lattice (density/potential basis).

    Host-side numpy arrays; `.device()` returns the jnp tables used inside jit.
    """

    lattice: np.ndarray  # (3,3) rows a_i [bohr]
    recip: np.ndarray  # (3,3) rows b_i [bohr^-1]
    omega: float  # unit cell volume [bohr^3]
    gmax: float
    fft: FFTGrid
    millers: np.ndarray  # (ng, 3) int64
    gcart: np.ndarray  # (ng, 3) f64
    glen2: np.ndarray  # (ng,)
    shell_idx: np.ndarray  # (ng,) int32
    shell_g2: np.ndarray  # (nshell,)
    fft_index: np.ndarray  # (ng,) int32 scatter index into flattened box

    @staticmethod
    def build(lattice: np.ndarray, gmax: float, fft: FFTGrid | None = None) -> "Gvec":
        if gmax <= 0:
            raise ValueError(f"gmax must be positive, got {gmax}")
        a = np.asarray(lattice, dtype=np.float64)
        recip = reciprocal_lattice(a)
        if fft is None:
            fft = FFTGrid.for_cutoff(a, 2.0 * gmax)  # box holds G1-G2 products
        millers = _enumerate_sphere(recip, np.zeros(3), gmax, fft)
        gcart = millers @ recip
        glen2 = np.sum(gcart * gcart, axis=1)
        shell_idx, shell_g2 = _shells(glen2)
        return Gvec(
            lattice=a,
            recip=recip,
            omega=float(abs(np.linalg.det(a))),
            gmax=float(gmax),
            fft=fft,
            millers=millers,
            gcart=gcart,
            glen2=glen2,
            shell_idx=shell_idx,
            shell_g2=shell_g2,
            fft_index=fft.miller_to_linear(millers),
        )

    @property
    def num_gvec(self) -> int:
        return len(self.millers)

    @property
    def num_shells(self) -> int:
        return len(self.shell_g2)

    def index_of_millers(self, millers: np.ndarray) -> np.ndarray:
        """Index of each (h,k,l) row in this set, -1 if absent.

        Used to map coefficient arrays between G-sets (coarse <-> fine grid,
        reference: Simulation_context gvec mappings)."""
        millers = np.asarray(millers).reshape(-1, 3)
        dims = np.asarray(self.fft.dims)
        lut = np.full(self.fft.num_points, -1, dtype=np.int64)
        lut[self.fft_index] = np.arange(self.num_gvec)
        # a Miller index outside the box's own range wraps onto another
        # vector's slot: it is absent, not that vector
        inside = np.all((millers >= -(dims // 2))
                        & (millers <= (dims - 1) // 2), axis=1)
        return np.where(inside, lut[self.fft.miller_to_linear(millers)], -1)


@dataclasses.dataclass(frozen=True)
class GkVec:
    """Batched |G+k| <= gk_cutoff spheres for a set of k-points.

    The reference gives each K_point its own ragged Gvec (k_point.hpp:52-61);
    on TPU we pad every sphere to the common max size so that all per-k arrays
    have static shape [nk, ngk_max] and the whole k-set can be vmapped /
    sharded over the mesh "k" axis. Padded slots carry mask=0 and scatter to
    the FFT box with zero amplitude (g_to_r uses additive scatter).
    """

    kpoints: np.ndarray  # (nk, 3) fractional
    weights: np.ndarray  # (nk,) IBZ weights, sum = 1
    gk_cutoff: float
    fft: FFTGrid  # coarse box (wave-function grid)
    num_gk: np.ndarray  # (nk,) true sphere sizes
    millers: np.ndarray  # (nk, ngk_max, 3)
    gkcart: np.ndarray  # (nk, ngk_max, 3) cartesian G+k
    mask: np.ndarray  # (nk, ngk_max) 1.0 valid / 0.0 padding
    fft_index: np.ndarray  # (nk, ngk_max) int32

    @staticmethod
    def build(
        gvec: Gvec,
        kpoints: np.ndarray,
        gk_cutoff: float,
        fft: FFTGrid,
        weights: np.ndarray | None = None,
    ) -> "GkVec":
        kpts = np.atleast_2d(np.asarray(kpoints, dtype=np.float64))
        nk = len(kpts)
        if weights is None:
            weights = np.full(nk, 1.0 / nk)
        per_k = [
            _enumerate_sphere(gvec.recip, kpts[ik], gk_cutoff, fft)
            for ik in range(nk)
        ]
        num_gk = np.asarray([len(m) for m in per_k], dtype=np.int32)
        ngk_max = int(num_gk.max())
        millers = np.zeros((nk, ngk_max, 3), dtype=np.int64)
        mask = np.zeros((nk, ngk_max))
        fft_index = np.zeros((nk, ngk_max), dtype=np.int32)
        gkcart = np.zeros((nk, ngk_max, 3))
        for ik, m in enumerate(per_k):
            n = len(m)
            millers[ik, :n] = m
            mask[ik, :n] = 1.0
            fft_index[ik, :n] = fft.miller_to_linear(m)
            gkcart[ik, :n] = (m + kpts[ik][None, :]) @ gvec.recip
        return GkVec(
            kpoints=kpts,
            weights=np.asarray(weights, dtype=np.float64),
            gk_cutoff=float(gk_cutoff),
            fft=fft,
            num_gk=num_gk,
            millers=millers,
            gkcart=gkcart,
            mask=mask,
            fft_index=fft_index,
        )

    @property
    def num_kpoints(self) -> int:
        return len(self.kpoints)

    @property
    def ngk_max(self) -> int:
        return self.millers.shape[1]

    def pad_to(self, ngk: int) -> "GkVec":
        """Widen every sphere to ``ngk`` columns (mask=0 padding).

        Padding columns behave exactly like the existing ragged-sphere
        padding (zero millers/gkcart, fft_index 0, kinetic() -> 1e4), so
        the result is valid for every solver path. Used by the serving
        engine to round ngk_max up to a shape quantum so near-identical
        decks share compiled executables.
        """
        cur = self.ngk_max
        if ngk <= cur:
            return self
        nk = self.num_kpoints
        extra = ngk - cur
        pad3 = lambda a: np.concatenate(  # noqa: E731
            [a, np.zeros((nk, extra, 3), dtype=a.dtype)], axis=1)
        pad2 = lambda a: np.concatenate(  # noqa: E731
            [a, np.zeros((nk, extra), dtype=a.dtype)], axis=1)
        return dataclasses.replace(
            self,
            millers=pad3(self.millers),
            gkcart=pad3(self.gkcart),
            mask=pad2(self.mask),
            fft_index=pad2(self.fft_index),
        )

    def kinetic(self) -> np.ndarray:
        """|G+k|^2 / 2 per (k, g); padded slots get a large value so they stay
        out of the low eigenspace in padded diagonalizations."""
        ekin = 0.5 * np.sum(self.gkcart * self.gkcart, axis=-1)
        return np.where(self.mask > 0, ekin, 1e4)
