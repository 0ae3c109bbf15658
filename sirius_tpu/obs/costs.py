"""Analytic cost model: FLOPs / bytes-moved per SCF stage from deck
shapes, the shared accelerator peak table, and roofline annotations.

This is the single source of truth for "how much work is that stage":

- `peak_gflops()` / `peak_gbps()`: the device peak table, keyed by
  ``device_kind`` as JAX reports it, with env overrides
  (``BENCH_PEAK_GFLOPS`` kept for compatibility, plus
  ``SIRIUS_TPU_PEAK_GFLOPS`` / ``SIRIUS_TPU_PEAK_GBPS``); an accelerator
  kind that is not in the table is an error, not a default;
- per-kernel FLOP formulas (`fft_flops`, `hpsi_flops`,
  `beta_gemm_flops`, ...) — the self-reported work counters of the
  reference (wave_functions.hpp:1790-1833) generalized to every hot
  stage; complex MACs count 8 flops, complex FFTs 5 N log2 N;
- `scf_stage_costs()`: one `StageCost` (flops + bytes) per span name of
  an SCF iteration, which bench_regress and the span layer use to
  annotate measured durations with achieved GFLOP/s, the roofline
  ceiling min(peak, intensity * bandwidth), and MFU;
- `xla_cost_analysis()`: the cross-check against what XLA itself counts
  via ``jitted.lower(...).compile().cost_analysis()`` — returns None
  (degrade, never raise) on backends that provide nothing.

The byte counts are a minimal-traffic model (each operand read once,
each result written once, complex128 = 16 B) — good enough to place a
stage on the roofline, not a cache simulation.
"""

from __future__ import annotations

import dataclasses
import math
import os

# published peaks per device kind (jax Device.device_kind). "TPU v5 lite"
# is one TPU v5e chip — Google Cloud documentation, "TPU v5e": 197 TFLOP/s
# in bf16, 16 GB of HBM at 819 GB/s. The flop figure is the bf16 MXU peak
# and is named as such: the SCF's f32 matmuls run as several bf16 passes
# (runtime.scf_scope), so an MFU against it is a lower bound by
# construction, not an f32 roofline.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_gflops": 197e3, "hbm_gbps": 819.0},
}
# the host CPU is modelled per core: ~76.8 GFLOPS (24 f32 FLOP/cycle @
# 3.2 GHz) and ~6.4 GB/s (shared DDR; deliberately coarse)
CPU_KIND = "cpu"
CPU_CORE_GFLOPS = 76.8
CPU_CORE_GBPS = 6.4

# Span names that deliberately have NO analytic flop model: wall-clock
# orchestration spans (queue wait, whole-iteration envelopes, MD step
# framing) where "achieved GFLOP/s" would be meaningless. sirius-lint's
# uncosted-span rule requires every scf.*/md.*/serve.*/campaign.* span
# wired into obs/spans.py to have a scf_stage_costs() key or entry here,
# so a new span is an explicit decision, not silent 0-FLOP noise in the
# attribution report.
UNCOSTED_SPANS = (
    # structure of one job's span tree (obs/spans.py): containers and
    # host bookkeeping, no stage of their own to count
    "scf.run",
    "scf.setup",
    # the symmetry group's host tables (dft/density.symmetry_tables) and,
    # inside a context build, the group search with the mesh's wedge
    "scf.setup.symmetry",
    "context.symmetry",
    # the LCAO start (dft/scf._initial_subspace): host numpy on kept tables
    "scf.setup.subspace",
    # the two host potentials of a job (dft/potential.generate_potential)
    "scf.setup.potential",
    "scf.finalize",
    "scf.finalize.potential",
    "scf.autosave",
    "md.integrate",
    "md.extrapolate",
    "md.scf",
    "serve.job",
    "serve.context_build",
    "serve.run",
    "serve.queue_wait",
    # a profiler capture, what stopping it cost and its table of device
    # seconds by named scope (obs/trace.py, obs/device_scopes.py)
    "trace.capture",
    "trace.stop",
    "trace.scopes",
    "campaign.finalize",
    # model-based compute/collective split of the G-sharded band solve
    # (probe-timed collectives x analytic apply counts, dft/scf.py)
    "scf.band_solve.compute",
    "scf.band_solve.collective",
    # fenced collective probes at deck shapes (parallel/dist_fft.py)
    "collective.all_to_all_x2y",
    "collective.all_to_all_y2x",
    "collective.fft_local",
    "collective.psum_beta",
    # timeline export work itself (obs/timeline.py)
    "trace.export",
    # precision-headroom shadow probes (obs/numerics.py): duplicate stage
    # evaluations at reduced precision — attribution would double-count
    # the real stages' FLOPs
    "scf.numerics_probe",
)


def detect_platform() -> str:
    """Platform of the default JAX device (initializes the backend; an
    error there is the caller's to see)."""
    import jax

    return jax.devices()[0].platform


def detect_device_kind() -> str:
    """``device_kind`` of the default JAX device — the peak table's key."""
    import jax

    return jax.devices()[0].device_kind


def _device_peak(device_kind: str | None, field: str, cpu_core: float) -> float:
    if device_kind is None:
        device_kind = detect_device_kind()
    if device_kind == CPU_KIND:
        return cpu_core * (os.cpu_count() or 1)
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}: add it to "
            "sirius_tpu/obs/costs.py DEVICE_PEAKS with its source (known: "
            f"{sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[device_kind][field]


def peak_gflops(device_kind: str | None = None,
                override: float | None = None) -> float:
    """Peak GFLOPS of a device kind (bf16 MXU peak for a TPU, see
    DEVICE_PEAKS). Resolution order: explicit ``override`` (config) >
    ``BENCH_PEAK_GFLOPS`` / ``SIRIUS_TPU_PEAK_GFLOPS`` env > the table;
    the per-core model for the CPU; KeyError for an unlisted accelerator."""
    if override:
        return float(override)
    env = (os.environ.get("BENCH_PEAK_GFLOPS")
           or os.environ.get("SIRIUS_TPU_PEAK_GFLOPS"))
    if env:
        return float(env)
    return _device_peak(device_kind, "bf16_gflops", CPU_CORE_GFLOPS)


def peak_gbps(device_kind: str | None = None,
              override: float | None = None) -> float:
    """Published memory bandwidth (GB/s) for the roofline ceiling."""
    if override:
        return float(override)
    env = os.environ.get("SIRIUS_TPU_PEAK_GBPS")
    if env:
        return float(env)
    return _device_peak(device_kind, "hbm_gbps", CPU_CORE_GBPS)


@dataclasses.dataclass(frozen=True)
class StageCost:
    """Analytic work of one stage: flops + bytes moved."""

    flops: float
    bytes: float = 0.0

    @property
    def intensity(self) -> float:
        """Arithmetic intensity flops/byte (inf for byte-free models)."""
        return self.flops / self.bytes if self.bytes > 0 else float("inf")

    def gflops(self, dur_s: float) -> float:
        return self.flops / dur_s / 1e9 if dur_s > 0 else 0.0

    def roofline_gflops(self, device_kind: str | None = None,
                        peak: float | None = None,
                        bw_gbps: float | None = None) -> float:
        """min(compute peak, intensity * bandwidth) — the ceiling this
        stage could reach on the given hardware."""
        pk = peak if peak is not None else peak_gflops(device_kind)
        bw = bw_gbps if bw_gbps is not None else peak_gbps(device_kind)
        if self.bytes <= 0:
            return pk
        return min(pk, self.intensity * bw)

    def mfu(self, dur_s: float, device_kind: str | None = None,
            peak: float | None = None) -> float:
        pk = peak if peak is not None else peak_gflops(device_kind)
        return self.gflops(dur_s) / pk if pk > 0 else 0.0


def annotate_span(dur_s: float, flops: float, bytes: float = 0.0,
                  device_kind: str | None = None,
                  peak: float | None = None) -> dict:
    """Roofline annotation fields for a measured span duration."""
    c = StageCost(flops=float(flops), bytes=float(bytes))
    roof = c.roofline_gflops(device_kind=device_kind, peak=peak)
    return {
        "flops": c.flops,
        "bytes": c.bytes,
        "gflops": c.gflops(dur_s),
        "roofline_gflops": roof,
        "mfu": c.mfu(dur_s, device_kind=device_kind, peak=peak),
    }


# ---------------------------------------------------------------------------
# per-kernel FLOP formulas (exact closed forms — tests hand-count these)


def _nbox(box) -> int:
    return int(box[0]) * int(box[1]) * int(box[2])


def fft_flops(box, batch: int = 1) -> float:
    """One complex FFT on `box` costs 5 N log2 N real flops (the
    standard split-radix count the reference also reports)."""
    n = _nbox(box)
    return float(batch) * 5.0 * n * math.log2(max(n, 2))


def fft_bytes(box, batch: int = 1, itemsize: int = 16) -> float:
    """Minimal traffic of one complex FFT: read + write the box once."""
    return float(batch) * 2.0 * itemsize * _nbox(box)


def beta_gemm_flops(nb: int, nbeta: int, ngk: int) -> float:
    """One beta-projection GEMM <beta|psi>: [nb, ngk] x [ngk, nbeta]
    complex, 8 flops per complex MAC."""
    return 8.0 * nb * nbeta * ngk


def beta_gemm_bytes(nb: int, nbeta: int, ngk: int,
                    itemsize: int = 16) -> float:
    return float(itemsize) * (nb * ngk + nbeta * ngk + nb * nbeta)


def hpsi_flops(nb: int, ngk: int, nbeta: int, box) -> float:
    """Flops of ONE H*psi + S*psi application on [nb, ngk] (the counter
    the reference self-reports as GFLOPS): per band two complex FFTs on
    the coarse box, the pointwise V multiply, the kinetic diagonal, and
    the beta-projector einsums (project, D/Q apply, expand for both H
    and S; 8 flops/cmac)."""
    n = _nbox(box)
    fft = 2 * 5.0 * n * math.log2(max(n, 2))
    local = 7.0 * n + 8.0 * ngk
    nl = 8.0 * (3.0 * nbeta * ngk + 2.0 * nbeta * nbeta)
    return nb * (fft + local + nl)


def hpsi_bytes(nb: int, ngk: int, nbeta: int, box,
               itemsize: int = 16) -> float:
    """Minimal traffic of one H*psi + S*psi: per band two FFT round
    trips + veff read + psi read/write, plus one read of the projector
    table and the projection coefficients."""
    n = _nbox(box)
    per_band = 2 * 2.0 * itemsize * n + 8.0 * n + 2.0 * itemsize * ngk
    return nb * per_band + itemsize * (nbeta * ngk + 2.0 * nb * nbeta)


def davidson_applies(steps: int, nb: int, chunks: int | None = None) -> int:
    """H-applications in band rows of one davidson() call that ran `steps`
    steps in `chunks` chunks (delegates to solvers/davidson.num_applies so
    the counts can never drift). Without `chunks`: a solve that ran all of
    its `steps`, the bound a configuration's num_steps sets."""
    from sirius_tpu.solvers.davidson import max_chunks, num_applies

    return num_applies(
        steps, max_chunks(steps) if chunks is None else chunks, nb)


def davidson_cost(nb: int, ngk: int, nbeta: int, box,
                  num_steps: int, chunks: int | None = None) -> StageCost:
    """One davidson() solve of `num_steps` steps in `chunks` chunks (what a
    solve ran; without `chunks`, the bound): the H/S applications plus the
    per-step dense subspace algebra (3nb x 3nb Gram products, the
    Rayleigh-Ritz eigensolve, and the rotation GEMMs back to the band
    block)."""
    rows = davidson_applies(num_steps, nb, chunks)
    apply_f = hpsi_flops(1, ngk, nbeta, box) * rows
    apply_b = hpsi_bytes(1, ngk, nbeta, box) * rows
    m = 3 * nb  # [X, K R, P] subspace
    gram = 2.0 * 8.0 * m * m * ngk  # hsub + ssub
    eig = 30.0 * m ** 3  # eigh(3nb) + the basis transforms around it
    rot = 6.0 * 8.0 * nb * m * ngk  # xn/hxn/sxn + pn/hpn/spn
    sub_f = num_steps * (gram + eig + rot)
    sub_b = num_steps * 16.0 * (3.0 * m * ngk + 2.0 * m * m)
    return StageCost(flops=apply_f + sub_f, bytes=apply_b + sub_b)


def band_solve_cost(nb: int, ngk: int, nbeta: int, box, ran,
                    copies: int = 1) -> StageCost:
    """A set's band solve from what its loops ran: `ran` holds the fetched
    (steps, chunks) of every loop of the solve ([..., 2], as
    solvers/davidson.count_solve takes them), `copies` the (k, spin) lanes
    behind each."""
    import numpy as np

    flops = bytes_ = 0.0
    for steps, chunks in np.asarray(ran).reshape(-1, 2).tolist():
        c = davidson_cost(nb, ngk, nbeta, box, steps, chunks)
        flops += copies * c.flops
        bytes_ += copies * c.bytes
    return StageCost(flops=flops, bytes=bytes_)


def scf_stage_costs(nk: int, ns: int, nb: int, ngk: int, nbeta: int,
                    box, ng: int, num_steps: int,
                    box_fine=None, mix_history: int = 8,
                    aug: bool = True) -> dict[str, StageCost]:
    """Per-iteration StageCost keyed by the span names run_scf emits.

    Shapes come straight from the SimulationContext: `box` is the coarse
    FFT grid (wave functions), `box_fine` the fine grid (density and
    potential; defaults to the coarse box when not given), `ng` the fine
    G set, `ngk` the padded |G+k| sphere. `num_steps` is the band solve's
    bound (iterative_solver.num_steps): "scf.band_solve" here is the cost of
    a solve that takes every step, what the straggler model and the perf
    gate budget for. The span itself carries band_solve_cost of the steps
    that ran, or no cost where its time is not the solve's (dft/scf.py)."""
    bf = box_fine if box_fine is not None else box
    nf = _nbox(bf)
    c: dict[str, StageCost] = {}
    dav = davidson_cost(nb, ngk, nbeta, box, num_steps)
    c["scf.band_solve"] = StageCost(flops=nk * ns * dav.flops,
                                    bytes=nk * ns * dav.bytes)
    # screened D: augmentation Q * veff integrals on the fine G set
    dmat = (8.0 * ns * nbeta * nbeta * ng) if aug and nbeta else 2.0 * ng
    c["scf.d_matrix"] = StageCost(flops=dmat, bytes=16.0 * ns * ng)
    # fermi search: ~60 bisection sweeps over every band energy
    c["scf.occupations"] = StageCost(flops=60.0 * 4.0 * nk * ns * nb,
                                     bytes=8.0 * nk * ns * nb)
    # density: one inverse FFT + |psi|^2 accumulate per occupied band,
    # the coarse->fine map, plus the augmentation density matrix GEMM
    dens = nk * ns * nb * (fft_flops(box) + 2.0 * _nbox(box))
    dens_b = nk * ns * nb * fft_bytes(box)
    if aug and nbeta:
        dens += nk * ns * beta_gemm_flops(nb, nbeta, ngk) + \
            8.0 * ns * nbeta * nbeta * ng
        dens_b += 16.0 * (nbeta * ngk + ns * nbeta * nbeta)
    c["scf.density"] = StageCost(flops=dens, bytes=dens_b)
    # quasi-Newton mixing: history GEMMs over the packed vector
    nx = ng * (2 if ns == 2 else 1)
    c["scf.mixing"] = StageCost(flops=8.0 * nx * (2.0 * mix_history + 4.0),
                                bytes=16.0 * nx * (mix_history + 2.0))
    # potential: Hartree (pointwise on G), XC on the fine real grid
    # (~2 FFT round trips + the functional evaluation)
    potf = 10.0 * ng + 4.0 * fft_flops(bf) + 80.0 * ns * nf
    c["scf.potential"] = StageCost(flops=potf,
                                   bytes=4.0 * fft_bytes(bf) + 16.0 * ng)
    # fused device step = density assembly + mix + potential + D refresh
    c["scf.fused_step"] = StageCost(
        flops=c["scf.mixing"].flops + c["scf.potential"].flops
        + c["scf.d_matrix"].flops,
        bytes=c["scf.mixing"].bytes + c["scf.potential"].bytes
        + c["scf.d_matrix"].bytes,
    )
    # one [NUM_SCALARS] float64 vector per iteration (dft/fused.py; the
    # numerics-ledger invariants ride in the same record)
    c["scf.readback"] = StageCost(flops=0.0, bytes=8.0 * 20)
    c["scf.iteration"] = StageCost(
        flops=sum(v.flops for k, v in c.items()
                  if k not in ("scf.fused_step", "scf.readback")),
        bytes=sum(v.bytes for k, v in c.items()
                  if k not in ("scf.fused_step", "scf.readback")),
    )
    return c


# ---------------------------------------------------------------------------
# XLA cross-check


def xla_cost_analysis(jitted, *args, **kwargs) -> dict | None:
    """FLOP/byte counts from XLA's own cost model for a jitted callable
    at the given example arguments, or None when the backend provides
    nothing (older jax, some plugin backends) — callers must treat None
    as "skip the cross-check", never as a failure."""
    try:
        compiled = jitted.lower(*args, **kwargs).compile()
        ca = compiled.cost_analysis()
    except Exception:
        return None
    # historical jax versions returned [dict] per device program
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict) or not ca:
        return None
    return dict(ca)


def xla_flops(jitted, *args, **kwargs) -> float | None:
    """Just the flop count of the cross-check, or None when absent."""
    ca = xla_cost_analysis(jitted, *args, **kwargs)
    if ca is None:
        return None
    v = ca.get("flops")
    try:
        v = float(v)
    except (TypeError, ValueError):
        return None
    return v if v > 0 else None
