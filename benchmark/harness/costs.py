"""Operations and bytes of the counted kernels, from shapes alone, and the
table of peaks (peaks.json). Copied from sirius_tpu/obs/costs.py (PR 7) so
that no later PR can move the yardstick; the original stays in the program.

Which bound applies: H*psi is two FFT round trips per band plus thin
projector GEMMs, 1-3 flops per byte in complex64, far under the v5e's ridge
(197e12 / 6 passes / 819e9 = 40 flops per byte even for f32 at `highest`), so
the bytes bound binds; roofline_seconds() returns both and says which.
"""

from __future__ import annotations

import json
import math
import os

# bf16 MXU passes one f32 x f32 product takes at each jax matmul precision
F32_PASSES = {"highest": 6, "high": 3, "default": 1}


def load_peaks(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {path}: add its row with "
            "its source; there is no default")
    return table[device_kind]


def _nbox(box) -> int:
    return int(box[0]) * int(box[1]) * int(box[2])


def fft_flops(box, batch: int = 1) -> float:
    """One complex FFT on ``box``: 5 N log2 N real flops."""
    n = _nbox(box)
    return float(batch) * 5.0 * n * math.log2(max(n, 2))


def fft_bytes(box, batch: int = 1, itemsize: int = 8) -> float:
    """Least traffic of one complex FFT: read and write the box once."""
    return float(batch) * 2.0 * itemsize * _nbox(box)


def hpsi_flops(nb: int, ngk: int, nbeta: int, box) -> float:
    """Flops of one H*psi + S*psi application on [nb, ngk]: per band two
    complex FFTs on the coarse box, the pointwise V multiply, the kinetic
    diagonal, and the beta-projector products (project, D/Q apply, expand
    for both H and S; 8 flops per complex multiply-add)."""
    n = _nbox(box)
    fft = 2 * 5.0 * n * math.log2(max(n, 2))
    local = 7.0 * n + 8.0 * ngk
    nl = 8.0 * (3.0 * nbeta * ngk + 2.0 * nbeta * nbeta)
    return nb * (fft + local + nl)


def hpsi_bytes(nb: int, ngk: int, nbeta: int, box, itemsize: int = 8) -> float:
    """Least traffic of one H*psi + S*psi: per band two FFT round trips, the
    potential read and psi read/write, plus one read of the projector table
    and the projection coefficients. ``itemsize`` 8 = complex64."""
    n = _nbox(box)
    per_band = 2 * 2.0 * itemsize * n + itemsize / 2.0 * n + 2.0 * itemsize * ngk
    return nb * per_band + itemsize * (nbeta * ngk + 2.0 * nb * nbeta)


def roofline_seconds(flops: float, bytes_: float, peaks: dict,
                     precision: str = "highest") -> dict:
    """Least time the chip could take: the larger of f32 flops over the bf16
    peak divided by the passes of ``precision``, and bytes over HBM rate."""
    t_flops = flops / (peaks["bf16_tflops"] * 1e12 / F32_PASSES[precision])
    t_bytes = bytes_ / (peaks["hbm_gbps"] * 1e9)
    return {"seconds": max(t_flops, t_bytes), "flops_s": t_flops,
            "bytes_s": t_bytes,
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
