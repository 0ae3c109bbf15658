"""Device meshes and sharding for distributed SCF.

The reference's 3-level MPI product grid world = comm_k x (npr x npc)
(simulation_context.cpp:1300-1349) maps to one jax.sharding.Mesh with axes

  "k" — k-point parallelism (embarrassingly parallel band solves; only the
        density reduction and Fermi sync cross it -> psum over "k");
  "b" — band parallelism (batched FFTs are per-band independent; subspace
        Gram matrices contract over bands -> XLA inserts all-gathers);

G-vector sharding (the reference's z-column/SpFFT slab axis) composes with
these via sharded FFT boxes and is introduced when single-replica boxes stop
fitting; at the sizes of the verification suite k x b sharding saturates the
chips first.

Everything uses GSPMD through jit + NamedSharding: the solver code is the
same single-device code; collectives are inserted by XLA (SURVEY.md §2.8).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(num_k: int | None = None, num_b: int | None = None) -> Mesh:
    """Mesh over all available devices, factored as ("k", "b").

    By default puts as many devices on "k" as divide the device count."""
    devs = np.array(jax.devices())
    n = len(devs)
    if num_k is None:
        num_k = n
        num_b = 1
    if num_b is None:
        num_b = n // num_k
    assert num_k * num_b == n, f"{num_k}*{num_b} != {n} devices"
    return Mesh(devs.reshape(num_k, num_b), ("k", "b"))


def shard_kset(mesh: Mesh, psi):
    """Shard a [nk, ns, nb, ngk] wave-function array: k-points over "k",
    bands over "b"."""
    return jax.device_put(psi, NamedSharding(mesh, P("k", None, "b", None)))


def kset_spec() -> P:
    return P("k", None, "b", None)


def production_mesh(nk: int, nb: int, devices=None):
    """Mesh for the production SCF on however many devices are present.

    Chooses (num_k, num_b) with num_k | nk, num_b | nb and
    num_k * num_b <= ndev maximizing the used device count (k first on
    ties — band solves are embarrassingly parallel over k). The mesh may
    be PARTIAL (a subset of devices): real parallelism on fewer devices
    beats a full-device mesh with replicated axes. Returns
    (mesh, psi_spec) or (None, None) when no parallel factorization
    exists — callers keep the exact single-device path then.

    Multi-process (multi-host) runs require every process's devices in
    the mesh, so partial meshes are limited to single-process sessions;
    multi-host falls back to the full-device gcd factorization.

    devices: explicit device list to build the mesh from (a serving-engine
    slice); defaults to jax.devices()."""
    import math

    devices = list(devices) if devices is not None else jax.devices()
    ndev = len(devices)
    if ndev <= 1:
        return None, None
    nk = max(nk, 1)
    nb = max(nb, 1)
    multi_host = jax.process_count() > 1
    if multi_host:
        num_k = math.gcd(nk, ndev)
        # (multi-host ignores `devices`: every process's devices must be in
        # the mesh, so slice scheduling is a single-process feature)
        # full-device mesh (multi-host requires every device present); the
        # band axis is sized ndev//num_k and only USED when nb divides it —
        # otherwise the "b" axis replicates (spec None below) by design
        mesh = make_mesh(num_k=num_k, num_b=ndev // num_k)
        band_ax = "b" if (ndev // num_k > 1 and nb % (ndev // num_k) == 0) else None
        if num_k == 1 and band_ax is None:
            return None, None
        return mesh, P("k", None, band_ax, None)
    best = (1, 1)
    for dk in range(1, min(nk, ndev) + 1):
        if nk % dk:
            continue
        db = math.gcd(nb, ndev // dk)
        if dk * db > best[0] * best[1] or (
            dk * db == best[0] * best[1] and dk > best[0]
        ):
            best = (dk, db)
    num_k, num_b = best
    if num_k * num_b == 1:
        return None, None
    devs = np.array(devices[: num_k * num_b])
    mesh = Mesh(devs.reshape(num_k, num_b), ("k", "b"))
    band_ax = "b" if num_b > 1 else None
    return mesh, P("k", None, band_ax, None)


# natural sharding of every HkSetParams leaf on the ("k", "b") mesh:
# leading-nk leaves split over "k", spin/shared tables replicated
_K1, _K2 = P("k", None), P("k", None, None)
KSET_PARAM_SPECS = dict(
    veff_r=P(), ekin=_K1, mask=_K1, fft_index=_K1, beta_re=_K2, beta_im=_K2,
    dion=P(), qmat=P(), h_diag=_K2, o_diag=_K1, hub_re=_K2, hub_im=_K2,
    vhub_re=P(), vhub_im=P(), cube=P("k", None, None, None),
)


def place_kset_params(params, mesh: Mesh | None, device):
    """device_put every leaf of an HkSetParams with its natural sharding
    (KSET_PARAM_SPECS), or without a mesh onto the one compute `device`. A
    device_put onto an identical placement is a no-op, so calling this per
    SCF iteration only moves the refreshed potential-dependent leaves."""
    if mesh is None:
        return jax.device_put(params, device)
    return params._replace(**{
        name: jax.device_put(leaf, NamedSharding(mesh, KSET_PARAM_SPECS[name]))
        for name, leaf in params._asdict().items() if leaf is not None
    })
