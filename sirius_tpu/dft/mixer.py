"""Density mixers (reference: src/mixer/ — Linear, Anderson, Anderson_stable,
Broyden2 over a tuple of function spaces with configurable inner products,
mixer.hpp:37-63, mixer_factory.hpp:40-47 where "broyden1" is a
backward-compatibility alias of Anderson).

The mixed vector is rho(G) on the fine set (complex) plus optional trailing
components, with either the plain l2 inner product or the Hartree-weighted
G-space metric (4 pi / G^2, reference mixer_functions.cpp use_hartree) which
preconditions long-wavelength charge sloshing.

Algorithms (all limited-memory quasi-Newton on x_{n+1} = x_n - G_n f_n):
  linear           G_n = -beta I
  anderson         type-II multisecant, normal-equations least squares
                   (reference anderson_mixer.hpp; "broyden1" aliases here)
  anderson_stable  same least-squares problem solved through a
                   metric-weighted QR of the residual-difference block
                   (reference anderson_stable_mixer.hpp, Fang & Saad 2009)
  broyden2         recursive rank-1 inverse-Jacobian updates; the alpha_i
                   recursion of broyden2_mixer.hpp:63-80
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Mixer:
    KNOWN = ("linear", "anderson", "anderson_stable", "broyden1", "broyden2")

    def __init__(
        self,
        cfg,
        glen2: np.ndarray | None = None,
        num_components: int = 1,
        extra_len: int = 0,
        omega: float | None = None,
        weight: np.ndarray | None = None,
        rms_weight: np.ndarray | None = None,
    ):
        """num_components: G-sized components (charge first, then
        magnetization); extra_len: trailing flat entries (occupation/density
        matrices, PAW) that are mixed passively — the reference gives them a
        ZERO inner product (mixer_functions.cpp density_function_property
        "do not contribute to mixing"), so they never steer the Anderson/
        Broyden coefficients or the rms.

        Channel metrics (reference mixer_functions.cpp): the plain inner
        product of two periodic functions is the real-space integral
        int f g dr = Omega sum_G f*(G) g(G); with use_hartree the CHARGE
        channel instead gets 4 pi sum_{G!=0} f* g / G^2. Both the metric and
        the rms normalization (inner / Omega per channel,
        mixer.hpp update_rms) need Omega — pass it with glen2. Without glen2
        (FP-LAPW mixed vector) a plain unweighted l2 over the whole vector
        is used.
        """
        if cfg.type not in self.KNOWN:
            raise ValueError(
                f"unknown mixer type '{cfg.type}' (supported: {self.KNOWN})"
            )
        self.beta = cfg.beta
        self.max_history = cfg.max_history
        self.kind = "anderson" if cfg.type == "broyden1" else cfg.type
        self.use_hartree = bool(cfg.use_hartree)
        self.weight = None
        self.rms_weight = None  # per-coefficient weight of the normalized rms
        self._eha_w = None  # 2 pi Omega / G^2 over the charge channel
        if glen2 is not None:
            if omega is None:
                raise ValueError("Mixer needs omega together with glen2")
            ng = len(glen2)
            g2 = np.where(glen2 > 1e-12, glen2, np.inf)
            self._eha_w = 2.0 * np.pi * omega / g2
            if cfg.use_hartree:
                w_charge = 4.0 * np.pi / g2
                # normalized by size = 1/Omega (mixer_functions.cpp
                # periodic_function_property_modified) -> MULTIPLIED by Omega
                rms_charge = omega * w_charge
            else:
                w_charge = np.full(ng, omega)
                rms_charge = np.ones(ng)
            self.weight = np.concatenate(
                [w_charge]
                + [np.full(ng, omega)] * (num_components - 1)
                + [np.zeros(extra_len)]
            )
            # plain channels: inner = Omega sum|d_G|^2, size = Omega -> 1/coeff
            self.rms_weight = np.concatenate(
                [rms_charge]
                + [np.ones(ng)] * (num_components - 1)
                + [np.zeros(extra_len)]
            )
        if weight is not None:
            # explicit metric (FP-LAPW mixed vector: real integration
            # measures per coefficient instead of the G-space construction)
            self.weight = np.asarray(weight)
            self.rms_weight = (
                self.weight if rms_weight is None else np.asarray(rms_weight)
            )
        self._x: list[np.ndarray] = []  # input history
        self._f: list[np.ndarray] = []  # residual history f = x_out - x_in
        # transferred secant pairs (import_secants), materialized into
        # (_x, _f) at the next mix() once the first residual is known
        self._sx: list[np.ndarray] = []
        self._sf: list[np.ndarray] = []

    def _inner(self, a: np.ndarray, b: np.ndarray) -> float:
        w = self.weight if self.weight is not None else 1.0
        return float(np.real(np.sum(w * np.conj(a) * b)))

    def residual_hartree_energy(self, x_mixed: np.ndarray, x_new: np.ndarray):
        """Hartree energy of the charge-channel residual (mixed - new):
        2 pi Omega sum_{G!=0} |drho_G|^2 / G^2 — the quantity the reference
        tests against density_tol when use_hartree is on (poisson.cpp
        density_residual_hartree_energy, dft_ground_state.cpp:251,353).
        None when the mixer has no G-space charge channel (FP-LAPW vector)."""
        if self._eha_w is None:
            return None
        n = len(self._eha_w)
        d = x_mixed[:n] - x_new[:n]
        return float(np.real(np.sum(self._eha_w * np.conj(d) * d)))

    def rms(self, x_in: np.ndarray, x_out: np.ndarray) -> float:
        """sqrt of the sum over channels of inner(d,d)/size (reference
        mixer.hpp update_rms with normalize=true)."""
        d = x_out - x_in
        if self.rms_weight is None:
            return float(np.sqrt(np.real(np.vdot(d, d)) / d.size))
        return float(
            np.sqrt(max(np.real(np.sum(self.rms_weight * np.conj(d) * d)), 0.0))
        )

    def _mix_anderson(self, x_in, f):
        # type-II Anderson: minimize ||f - sum g_j df_j|| in the metric,
        # df_j/dx_j spanned against the current point. Solved through a
        # truncated eigendecomposition of the Gram matrix: near machine-
        # precision residuals the df_j become numerically collinear and the
        # raw normal equations produce huge coefficients that extrapolate
        # to negative densities (NaN in GGA) — the reference guards the
        # same way with its `invertible` sysolve check
        # (anderson_mixer.hpp:137-140, skip the correction when singular).
        m = len(self._x)
        dfs = [f - self._f[j] for j in range(m)]
        dxs = [x_in - self._x[j] for j in range(m)]
        a = np.array([[self._inner(dfs[i], dfs[j]) for j in range(m)] for i in range(m)])
        b = np.array([self._inner(dfs[i], f) for i in range(m)])
        g = np.zeros(m)
        if np.all(np.isfinite(a)) and np.all(np.isfinite(b)):
            try:
                w, v = np.linalg.eigh(0.5 * (a + a.conj().T))
            except np.linalg.LinAlgError:
                w = v = None
            if w is not None:
                keep = w > 1e-12 * max(float(w[-1]), 0.0)
                if np.any(keep):
                    g = np.real(
                        v[:, keep] @ ((v[:, keep].conj().T @ b) / w[keep])
                    )
        x_opt = x_in - sum(gi * dxi for gi, dxi in zip(g, dxs))
        f_opt = f - sum(gi * dfi for gi, dfi in zip(g, dfs))
        out = x_opt + self.beta * f_opt
        if not np.all(np.isfinite(out)):
            return x_in + self.beta * f  # plain damped step
        return out

    def _diff_blocks(self, x_in, f):
        """Successive-difference blocks DF[:,i] = f_{i+1}-f_i etc. including
        the current point as the newest history entry."""
        xs = self._x + [x_in]
        fs = self._f + [f]
        n = len(xs)
        dfs = np.stack([fs[i + 1] - fs[i] for i in range(n - 1)], axis=1)
        dxs = np.stack([xs[i + 1] - xs[i] for i in range(n - 1)], axis=1)
        return dfs, dxs

    def _mix_anderson_stable(self, x_in, f):
        # Solve the same least-squares problem through a metric-weighted QR
        # of DF (reference anderson_stable_mixer.hpp):
        #   x+ = x + beta (f - DF k) - DX k,   k = R^{-1} Q^H W^{1/2} f
        # The projection DF k equals the weighted-space Q Q^H f backmapped,
        # but is formed in UNWEIGHTED space: components with zero metric
        # weight (the G=0 charge row under the Hartree metric) must not be
        # divided back by W^{-1/2}.
        dfs, dxs = self._diff_blocks(x_in, f)
        sw = np.sqrt(self.weight)[:, None] if self.weight is not None else 1.0
        q, r = np.linalg.qr(sw * dfs, mode="reduced")
        # guard rank deficiency: drop near-dependent directions, then
        # re-factorize the kept columns (subsetting Q/R of the original QR
        # would not factor the kept block unless only trailing columns drop)
        diag = np.abs(np.diag(r))
        keep = diag > 1e-12 * max(diag.max(), 1e-300)
        if not np.all(keep):
            dfs, dxs = dfs[:, keep], dxs[:, keep]
            if dfs.shape[1] == 0:
                return x_in + self.beta * f
            q, r = np.linalg.qr(sw * dfs, mode="reduced")
        h = q.conj().T @ (np.ravel(sw) * f if self.weight is not None else f)
        try:
            k = np.linalg.solve(r, h)
        except np.linalg.LinAlgError:
            return x_in + self.beta * f
        return x_in + self.beta * (f - dfs @ k) - dxs @ k

    def _mix_broyden2(self, x_in, f):
        # Recursive rank-1 inverse-Jacobian update, G_1 = -beta I
        # (reference broyden2_mixer.hpp:63-80):
        #   alpha_i = [<df_i, f_n> - sum_{j>i} alpha_j <df_i, df_j>] / <df_i, df_i>
        #   x+ = x + beta f - sum_i alpha_i (beta df_i + dx_i)
        dfs, dxs = self._diff_blocks(x_in, f)
        m = dfs.shape[1]
        gram = np.array(
            [[self._inner(dfs[:, i], dfs[:, j]) for j in range(m)] for i in range(m)]
        )
        rhs = np.array([self._inner(dfs[:, i], f) for i in range(m)])
        alpha = np.zeros(m)
        for i in range(m - 1, -1, -1):
            num = rhs[i] - sum(alpha[j] * gram[i, j] for j in range(i + 1, m))
            alpha[i] = num / gram[i, i] if gram[i, i] > 1e-300 else 0.0
        return x_in + self.beta * f - dfs @ (self.beta * alpha) - dxs @ alpha

    def mix(self, x_in: np.ndarray, x_out: np.ndarray) -> np.ndarray:
        f = x_out - x_in
        if self._sx and not self._x:
            # materialize transferred secants against the FIRST actual
            # residual: the pair (x_in - dx_j, f - df_j) makes the
            # difference-to-current blocks of every scheme below exactly
            # (dx_j, df_j) — the donor's Jacobian model enters without any
            # absolute residual claim (see import_secants)
            self._x = [x_in - dx for dx in self._sx]
            self._f = [f - df for df in self._sf]
        self._sx = []
        self._sf = []
        if self.kind == "linear" or not self._x:
            nxt = x_in + self.beta * f
        elif self.kind == "anderson":
            nxt = self._mix_anderson(x_in, f)
        elif self.kind == "anderson_stable":
            nxt = self._mix_anderson_stable(x_in, f)
        elif self.kind == "broyden2":
            nxt = self._mix_broyden2(x_in, f)
        else:
            raise ValueError(f"unknown mixer type '{self.kind}'")
        self._x.append(x_in.copy())
        self._f.append(f.copy())
        if len(self._x) > self.max_history:
            self._x.pop(0)
            self._f.pop(0)
        return nxt

    def flush_history(self) -> None:
        """Drop the quasi-Newton history. Rung 0 of the recovery ladder
        (dft/recovery.py): a history poisoned by a diverging trajectory is
        the most common Anderson/Broyden divergence amplifier, and the next
        mix() degrades gracefully to a plain damped step."""
        self._x = []
        self._f = []
        self._sx = []
        self._sf = []

    def export_history(self) -> dict:
        """(x, f) history as stacked arrays for checkpointing; empty dict
        when there is no history yet. Restoring via import_history makes a
        resumed host-path SCF bit-reproducible."""
        if not self._x:
            return {}
        return {"mix_x": np.stack(self._x), "mix_f": np.stack(self._f)}

    def import_history(self, hist: dict) -> None:
        if "mix_x" not in hist:
            self._x = []
            self._f = []
            return
        self._x = [np.asarray(r) for r in hist["mix_x"]]
        self._f = [np.asarray(r) for r in hist["mix_f"]]

    def import_secants(self, dxs, dfs) -> None:
        """Seed the quasi-Newton model with secant pairs (dx_j, df_j) from
        ANOTHER SCF run at a nearby geometry (cross-job warm start,
        campaigns/handoff.py). Absolute (x, f) pairs must not be imported
        across problems: they assert "the residual at the donor's fixed
        point is zero", which is false by O(h) for the child, and the
        least-squares solve then parks the trajectory there — a stall
        lasting until the stale rows age out of max_history. Differences
        carry only the Jacobian action (and are invariant under the
        delta-density translation of the guess), so they stay valid. The
        pairs are held pending and anchored at the child's first actual
        (x_in, f) inside mix(); flush_history drops pending pairs too, so
        the recovery ladder also clears a poisoned transfer."""
        keep = max(self.max_history - 1, 0)
        self._sx = [np.asarray(r) for r in dxs][-keep:] if keep else []
        self._sf = [np.asarray(r) for r in dfs][-keep:] if keep else []


# ---------------------------------------------------------------------------
# Device-resident mixer (the jitted twin of Mixer for the fused SCF step).
#
# The host Mixer above keeps python-list history and runs numpy eigh per
# call; inside a compiled SCF iteration the history must be fixed-shape
# device state instead. DeviceMixerState holds a fixed max_history block of
# (x_in, f) pairs as (re, im) leaves — real leaves only, per the
# real-boundary contract of parallel/batched.py — plus a fill counter.
# Unfilled slots stay exactly zero, which makes their residual-difference
# directions zero vectors: the Gram matrix rows vanish and the same
# 1e-12 * w_max eigenvalue cut the host _mix_anderson applies drops them,
# so the masked fixed-shape solve is numerically identical to the host
# variable-length one (tested in tests/test_fused_scf.py).
# ---------------------------------------------------------------------------


# relative eigenvalue cut of the device Anderson solve per working
# precision: the host threshold in f64, ~200 eps in f32
_ANDERSON_CUT = {"float64": 1e-12, "float32": 2.4e-5}


class DeviceMixerState(NamedTuple):
    """Fixed-shape mixing history: [max_history, nx] real leaves."""

    hx_re: jnp.ndarray
    hx_im: jnp.ndarray
    hf_re: jnp.ndarray
    hf_im: jnp.ndarray
    count: jnp.ndarray  # int32 scalar, number of valid history rows


def device_mixer_init(nx: int, max_history: int,
                      dtype=jnp.float64) -> DeviceMixerState:
    # distinct buffers per leaf: the fused carry donates them, and donating
    # one buffer under several leaves is an XLA error
    def z():
        return jnp.zeros((max_history, nx), dtype=dtype)

    return DeviceMixerState(z(), z(), z(), z(), jnp.zeros((), jnp.int32))


def device_mixer_weights(mixer: Mixer):
    """The (weight, rms_weight, eha_weight) triple of a host Mixer as a
    dict of device arrays, so the fused step mixes in the exact metric the
    host path uses."""
    if mixer.weight is None or mixer._eha_w is None:
        raise ValueError("device mixer needs the G-space metric "
                         "(construct the host Mixer with glen2/omega)")
    return {
        "w": jnp.asarray(mixer.weight),
        "rms_w": jnp.asarray(mixer.rms_weight),
        "eha_w": jnp.asarray(np.where(np.isfinite(mixer._eha_w),
                                      mixer._eha_w, 0.0)),
    }


def device_mix(state: DeviceMixerState, x_in: jnp.ndarray, x_new: jnp.ndarray,
               weights: dict, beta: float, kind: str, max_history: int):
    """One mixer update inside jit. x_in/x_new are complex packed vectors
    (complex exists only inside the compiled program); returns
    (new_state, x_mixed, rms, eha_res) with rms/eha traced scalars.

    Semantics match the host sequence in run_scf exactly:
      rms     = Mixer.rms(x_in, x_new)        [before mixing]
      x_mixed = Mixer.mix(x_in, x_new)
      eha_res = Mixer.residual_hartree_energy(x_mixed, x_new)
    """
    if kind not in ("linear", "anderson"):
        raise ValueError(f"device mixer supports linear/anderson, got '{kind}'")
    w = weights["w"]
    rms_w = weights["rms_w"]
    eha_w = weights["eha_w"]
    f = x_new - x_in
    rms = jnp.sqrt(jnp.maximum(
        jnp.real(jnp.sum(rms_w * jnp.conj(f) * f)), 0.0))

    if kind == "linear":
        out = x_in + beta * f
    else:
        m = max_history
        valid = (jnp.arange(m, dtype=jnp.int32) < state.count)[:, None]
        hx = jnp.where(valid, jax.lax.complex(state.hx_re, state.hx_im), 0.0)
        hf = jnp.where(valid, jax.lax.complex(state.hf_re, state.hf_im), 0.0)
        dfs = jnp.where(valid, f[None, :] - hf, 0.0)
        dxs = jnp.where(valid, x_in[None, :] - hx, 0.0)
        a = jnp.real(jnp.einsum("ix,x,jx->ij", jnp.conj(dfs), w, dfs))
        b = jnp.real(jnp.einsum("ix,x,x->i", jnp.conj(dfs), w, f))
        ok = jnp.all(jnp.isfinite(a)) & jnp.all(jnp.isfinite(b))
        a = jnp.where(ok, a, jnp.eye(m, dtype=a.dtype))
        ew, v = jnp.linalg.eigh(0.5 * (a + a.T))
        # zero-padded history rows produce exactly-zero eigenvalues; the
        # host threshold (1e-12 * largest) removes them along with any
        # numerically collinear directions
        # (scaled to the working precision: below ~eps * largest an
        # eigenvalue of the f32 Gram matrix is rounding noise)
        cut = _ANDERSON_CUT[a.dtype.name]
        thresh = cut * jnp.maximum(ew[-1], 0.0)
        keep = ew > thresh
        ew_safe = jnp.where(keep, ew, 1.0)
        g = v @ (jnp.where(keep, 1.0 / ew_safe, 0.0) * (v.T @ b))
        g = jnp.where(ok & (state.count > 0), g, 0.0)
        x_opt = x_in - jnp.einsum("i,ix->x", g.astype(dxs.dtype), dxs)
        f_opt = f - jnp.einsum("i,ix->x", g.astype(dfs.dtype), dfs)
        out = x_opt + beta * f_opt
        out = jnp.where(jnp.all(jnp.isfinite(jnp.real(out))
                                & jnp.isfinite(jnp.imag(out))),
                        out, x_in + beta * f)

    # push (x_in, f) into the newest slot; roll the block once full
    def _push(h_re, h_im, val):
        full = state.count >= max_history
        h_re = jnp.where(full, jnp.roll(h_re, -1, axis=0), h_re)
        h_im = jnp.where(full, jnp.roll(h_im, -1, axis=0), h_im)
        slot = jnp.minimum(state.count, max_history - 1)
        return (h_re.at[slot].set(jnp.real(val)),
                h_im.at[slot].set(jnp.imag(val)))
    hx_re, hx_im = _push(state.hx_re, state.hx_im, x_in)
    hf_re, hf_im = _push(state.hf_re, state.hf_im, f)
    new_state = DeviceMixerState(
        hx_re, hx_im, hf_re, hf_im,
        jnp.minimum(state.count + 1, max_history).astype(jnp.int32))

    n = eha_w.shape[0]
    d = out[:n] - x_new[:n]
    eha = jnp.real(jnp.sum(eha_w * jnp.conj(d) * d))
    return new_state, out, rms, eha


def initial_res_tol(itsol) -> float:
    """The bar the band solve starts from, and what it bars: with
    converge_by_energy (the reference's default) a band's eigenvalue move in
    one Davidson step, from energy_tolerance; otherwise its residual norm,
    from residual_tolerance. A solve ends when every band is under the bar
    (solvers/davidson.py, THE TRIP COUNT)."""
    return float(itsol.energy_tolerance if itsol.converge_by_energy
                 else itsol.residual_tolerance)


def schedule_res_tol(itsol, res_tol: float, dens_metric: float, nel: float,
                     hartree_metric: bool) -> float:
    """Next iteration's band-solve bar (initial_res_tol) from the density residual
    (reference dft_ground_state.cpp:252-259): tol = min(scale0 * metric,
    scale1 * tol_prev), clamped at min_tolerance. With the Hartree metric
    the density bar is an energy — scale it per electron as the reference
    does before feeding the solver."""
    m = dens_metric / max(1.0, nel) if hartree_metric else dens_metric
    return max(
        itsol.min_tolerance,
        min(itsol.tolerance_scale[0] * m,
            itsol.tolerance_scale[1] * res_tol),
    )
