#!/usr/bin/env python3
"""The quickest proof that sirius_tpu still starts on the chip.

Drives the main path once through the entry points a user would call, at
the full published widths of the repo's flagship deck class (BASELINE
config 1 = the reference's verification/test08: Si-2 ultrasoft, gk_cutoff
6.0, pw_cutoff 20.0, 26 bands; species are the repo's synthetic ultrasoft
Si, made in memory — there are no species files off this machine), in the
32-bit types the chip runs:

  1. run_scf on the Gamma deck: the packed-real `gamma` solve and
     the fused device-resident tail it feeds (FusedScf), both on the chip;
  2. run_scf on the same cell with a (2,2,2) k-mesh, no symmetry: the
     batched k-set solve + the same fused step;
     (on a host with four chips, then once more on all four: the (k, b)
     mesh run_scf picks, and |E4 - E1|);
  3. three jobs of one shape bucket through sirius-serve's own main().

Every phase's total energy must agree to 1e-5 Ha with the f64 energy of the
same deck computed in this process on the CPU backend, and its `placement`
record must show the band solve and the fused step on the TPU in 32-bit
types. One JSON object per phase goes to stdout; the last line is the
verdict object. Any phase failure raises: there is no path to exit code 0
that skips a check.

`--chips 4` (run by hand, never by the driver) runs only what exists across
chips and what it is compared with: phase 2's deck on one chip and on the
four-chip (k, b) production mesh, then a 16-atom Gamma supercell on one chip
and G-sharded (slab FFT) on the four-chip "g" mesh.
`--rehearse` lifts the must-be-TPU check and shrinks the deck for a dry run
on the CPU backend; a rehearsal's last line always says "ok": false.

Convergence bars of the 32-bit runs: density_tol 1e-5, energy_tol 1e-5 —
measured on the CPU backend in f32, the density residual of this deck
floors near 1e-6 and the per-iteration energy noise near 1e-6 Ha, so
tighter bars are not reachable by a 32-bit iterate; the energy error is
second order in the density error and lands near 1e-6 Ha.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

E_TOL_HA = 1e-5
TOL_32 = {"density_tol": 1e-5, "energy_tol": 1e-5}
TOL_64 = {"density_tol": 1e-8, "energy_tol": 1e-9}
FULL = {"gk_cutoff": 6.0, "pw_cutoff": 20.0, "num_bands": 26}
# the 16-atom supercell of the G-sharded phase: 8 bands per 2-atom cell = 64
# (32 occupied), because a mesh program's subspace problem must stay within
# 3 * num_bands <= 256 on a TPU (runtime.TPU_MESH_EIGH_MAX)
FULL_G = {**FULL, "num_bands": 8}
TINY = {"gk_cutoff": 3.0, "pw_cutoff": 7.0, "num_bands": 8}
# rehearsal size of the G-sharded phase: its supercell's coarse box (32^3)
# divides by 4 along x and y, as the full-size one (60^3) does
TINY_G = {"gk_cutoff": 3.2, "pw_cutoff": 7.4, "num_bands": 8}


def deck(size, ngridk, precision, positions=None, supercell=1, control=None):
    """Species-file-free deck (serve/scheduler.build_job_context form)."""
    tol = TOL_32 if precision == "fp32" else TOL_64
    syn = {"ultrasoft": True}
    if positions is not None:
        syn["positions"] = positions
    if supercell > 1:
        syn["supercell"] = supercell
    return {
        "parameters": {
            "gk_cutoff": size["gk_cutoff"], "pw_cutoff": size["pw_cutoff"],
            "num_bands": size["num_bands"] * supercell**3,
            "ngridk": list(ngridk), "use_symmetry": False,
            "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"],
            "smearing_width": 0.025, "num_dft_iter": 60,
            "precision_wf": precision, **tol,
        },
        "control": {"ngk_pad_quantum": 16, "verbosity": 0, **(control or {})},
        "synthetic": syn,
    }


class Counters:
    """Backend compiles and persistent-cache traffic since the last take()."""

    def __init__(self):
        from jax import monitoring

        from sirius_tpu.obs import metrics

        self.n = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
                  "cache_misses": 0}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        self._compile_event = metrics.BACKEND_COMPILE_EVENT

    def _duration(self, event, dt, **kw):
        if event == self._compile_event:
            self.n["compiles"] += 1
            self.n["compile_s"] += float(dt)

    def _event(self, event, **kw):
        if event.endswith("/cache_hits"):
            self.n["cache_hits"] += 1
        elif event.endswith("/cache_misses"):
            self.n["cache_misses"] += 1

    def take(self):
        out = dict(self.n, compile_s=round(self.n["compile_s"], 3))
        for k in self.n:
            self.n[k] = 0
        return out


def peak_hbm(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)


def run(deck_dict, devices):
    """One run_scf through the library front door; wall time ends after
    the result is on the host (run_scf returns host values only)."""
    import jax

    from sirius_tpu.config.schema import load_config
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.serve.scheduler import build_job_context

    cfg = load_config(deck_dict)
    ctx = build_job_context(cfg, ".")
    t0 = time.time()
    r = run_scf(cfg, ctx=ctx, devices=devices)
    jax.block_until_ready(jax.live_arrays())
    r["wall_s"] = time.time() - t0
    if not r["converged"]:
        raise RuntimeError(
            f"SCF did not converge in {r['num_scf_iterations']} iterations "
            f"on {[str(d) for d in devices]}")
    return r


def check_placement(pl, platform, path):
    if pl["path"] != path:
        raise RuntimeError(f"path taken was {pl['path']!r}, expected {path!r}")
    stages = ["band_solve"] + (
        ["occupations", "fused_step", "density", "mixing", "potential"]
        if path in ("batched+fused", "gamma") else [])
    for s in stages:  # entries are [platform, dtype, device ids]
        if pl[s][0] != platform or pl[s][1] not in ("float32", "complex64"):
            raise RuntimeError(
                f"stage {s} ran at {pl[s]}, expected 32-bit on {platform}")


def report(phase, counters, **kw):
    print(json.dumps({"phase": phase, **kw, **counters.take()},
                     default=float), flush=True)


def energy_check(name, e, e_ref):
    de = abs(e - e_ref)
    if not de <= E_TOL_HA:
        raise RuntimeError(
            f"{name}: |E - E_ref| = {de:.3e} Ha exceeds {E_TOL_HA} Ha "
            f"(E = {e:.10f}, f64 CPU reference = {e_ref:.10f})")
    return de


def scf_phase(name, size, ngridk, path, chip, cpu, platform, counters, cache,
              **deck_kw):
    """Reference in f64 on the CPU backend, then the 32-bit run on the chip."""
    ref = run(deck(size, ngridk, "fp64", **deck_kw), cpu)
    counters.take()  # the reference's compiles are not the phase's
    r = run(deck(size, ngridk, "fp32", **deck_kw), chip)
    check_placement(r["placement"], platform, path)
    de = energy_check(name, r["energy"]["total"], ref["energy"]["total"])
    report(name, counters, energy_ha=r["energy"]["total"],
           energy_ref_f64_cpu_ha=ref["energy"]["total"], abs_de_ha=de,
           scf_iterations=r["num_scf_iterations"],
           ref_scf_iterations=ref["num_scf_iterations"],
           wall_s=round(r["wall_s"], 3), ref_wall_s=round(ref["wall_s"], 3),
           compile_cache_from_env=cache["from_env"],
           peak_hbm_bytes=peak_hbm(chip), placement=r["placement"])
    return r, ref


def serve_phase(size, chip, platform, rehearse, counters, cache, e_ref):
    """Three same-bucket jobs through sirius-serve's main(): the phase-2
    deck and two with perturbed positions."""
    from sirius_tpu.serve import engine

    shifts = [0.0, 0.004, -0.003]
    with tempfile.TemporaryDirectory() as tmp:
        paths = []
        for i, s in enumerate(shifts):
            pos = [[0.0, 0.0, 0.0], [0.25 + s, 0.25, 0.25 - s]]
            path = os.path.join(tmp, f"job{i}.json")
            with open(path, "w") as f:
                json.dump(deck(size, (2, 2, 2), "fp32", positions=pos), f)
            paths.append(path)
        stats_path = os.path.join(tmp, "stats.json")
        argv = [*paths, "--slices", "1", "--stats_out", stats_path]
        if not rehearse:
            argv += ["--platform", "tpu"]
        t0 = time.time()
        # main() prints its stats document; keep stdout to one object a line
        real_stdout, sys.stdout = sys.stdout, sys.stderr
        try:
            rc = engine.main(argv)
        finally:
            sys.stdout = real_stdout
        wall = time.time() - t0
        if rc != 0:
            raise RuntimeError(f"sirius-serve main() returned {rc}")
        with open(stats_path) as f:
            stats = json.load(f)
    jobs = stats["jobs"]
    if len(jobs) != 3 or any(j["status"] != "done" for j in jobs):
        raise RuntimeError(
            f"serve: jobs not all DONE: {[(j['id'], j['status']) for j in jobs]}")
    for j in jobs:
        check_placement(j["result"]["placement"], platform, "batched+fused")
    # job 0 is phase 2's deck: same f64 reference
    de = energy_check("serve job0", jobs[0]["result"]["energy_total"], e_ref)
    report("serve", counters, wall_s=round(wall, 3), abs_de_ha=de,
           energy_ref_f64_cpu_ha=e_ref,
           jobs=[{"id": j["id"], "status": j["status"],
                  "energy_ha": j["result"]["energy_total"],
                  "scf_iterations": j["result"]["num_scf_iterations"],
                  "compiled_executables": j["result"]["compiled_executables"]}
                 for j in jobs],
           compile_cache_from_env=cache["from_env"],
           peak_hbm_bytes=peak_hbm(chip),
           placement=jobs[0]["result"]["placement"])


def kmesh_on_all_chips(d, devices, e_one_chip, platform, counters, cache):
    """The k-mesh deck on the host's four chips: run_scf picks the (k, b)
    mesh; the energy against the same deck's on one chip."""
    r4 = run(d, devices)
    pl = r4["placement"]
    check_placement(pl, platform, "batched+fused")
    mesh = pl["mesh"] or {}
    if mesh.get("k", 0) * mesh.get("b", 0) != 4:
        raise RuntimeError(f"(k, b) mesh does not use 4 devices: {mesh}")
    if len(set(pl["psi_shard_devices"])) != 4 or len(pl["psi_shard_devices"]) != 4:
        raise RuntimeError(
            f"wave-function shards not on 4 distinct devices: "
            f"{pl['psi_shard_devices']}")
    de = energy_check("E4 vs E1", r4["energy"]["total"], e_one_chip)
    report("kmesh_4chip", counters, energy_ha=r4["energy"]["total"],
           abs_de_vs_1chip_ha=de, mesh=mesh,
           scf_iterations=r4["num_scf_iterations"],
           wall_s=round(r4["wall_s"], 3), peak_hbm_bytes=peak_hbm(devices),
           compile_cache_from_env=cache["from_env"], placement=pl)


def four_chip_phases(size, gsize, devices, platform, counters, cache):
    """Only what exists across chips, and what it is compared with."""
    if len(devices) != 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devices)}")
    d = deck(size, (2, 2, 2), "fp32")
    r1 = run(d, devices[:1])
    check_placement(r1["placement"], platform, "batched+fused")
    report("kmesh_1chip", counters, energy_ha=r1["energy"]["total"],
           scf_iterations=r1["num_scf_iterations"],
           wall_s=round(r1["wall_s"], 3), placement=r1["placement"])
    kmesh_on_all_chips(d, devices, r1["energy"]["total"], platform, counters,
                       cache)
    # Gamma supercell: G-sharded slab-FFT solve on the "g" mesh vs one chip
    g1 = run(deck(gsize, (1, 1, 1), "fp32", supercell=2), devices[:1])
    report("gshard_1chip", counters, energy_ha=g1["energy"]["total"],
           scf_iterations=g1["num_scf_iterations"],
           wall_s=round(g1["wall_s"], 3), placement=g1["placement"])
    g4 = run(deck(gsize, (1, 1, 1), "fp32", supercell=2,
                  control={"gshard": "force"}), devices)
    if g4["gshard_devices"] != 4 or g4["placement"]["path"] != "gshard":
        raise RuntimeError(
            f"G-sharded solve did not engage: gshard_devices="
            f"{g4['gshard_devices']}, path={g4['placement']['path']}")
    # the bar is per 2-atom cell: this cell holds eight of them
    de = energy_check("gshard E4 vs E1", g4["energy"]["total"] / 8,
                      g1["energy"]["total"] / 8) * 8
    report("gshard_4chip", counters, energy_ha=g4["energy"]["total"],
           abs_de_vs_1chip_ha=de, gshard_devices=g4["gshard_devices"],
           scf_iterations=g4["num_scf_iterations"],
           wall_s=round(g4["wall_s"], 3), peak_hbm_bytes=peak_hbm(devices),
           placement=g4["placement"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--rehearse", action="store_true",
                    help="dry run on whatever backend is up, tiny deck; "
                         "never prints the success line")
    args = ap.parse_args(argv)

    import jax

    from sirius_tpu import runtime

    if args.rehearse:
        # enough virtual CPU devices for the requested chip count
        jax.config.update("jax_num_cpu_devices", max(args.chips, 1))
    else:
        runtime.select_platform("tpu")  # raises where there is no chip
    cache = runtime.enable_compile_cache()
    devices = jax.devices()
    dev0 = devices[0]
    platform = dev0.platform
    if not args.rehearse and platform != "tpu":
        raise RuntimeError(f"no TPU: JAX reports platform {platform!r}")
    if len(devices) < args.chips:
        raise RuntimeError(
            f"--chips {args.chips} but JAX reports {len(devices)} device(s)")
    cpu = jax.devices("cpu")[:1]
    size = TINY if args.rehearse else FULL
    counters = Counters()
    print(json.dumps({"phase": "start", "device_kind": dev0.device_kind,
                      "devices": len(devices), "compile_cache": cache,
                      "deck": size, "tolerances_fp32": TOL_32}), flush=True)

    if args.chips == 4:
        four_chip_phases(size, TINY_G if args.rehearse else FULL_G,
                         devices[:4], platform, counters, cache)
    else:
        chip = devices[:1]
        scf_phase("gamma", size, (1, 1, 1), "gamma", chip, cpu, platform,
                  counters, cache)
        r1, ref = scf_phase("kmesh", size, (2, 2, 2), "batched+fused", chip,
                            cpu, platform, counters, cache)
        if len(devices) >= 4:  # a four-chip host: the same deck on all of it
            kmesh_on_all_chips(deck(size, (2, 2, 2), "fp32"), devices[:4],
                               r1["energy"]["total"], platform, counters,
                               cache)
        serve_phase(size, chip, platform, args.rehearse, counters, cache,
                    ref["energy"]["total"])

    print(json.dumps({
        "ok": not args.rehearse,
        "device": {"platform": platform, "kind": dev0.device_kind,
                   "count": len(devices)},
    }), flush=True)
    return 0 if not args.rehearse else 1


if __name__ == "__main__":
    raise SystemExit(main())
