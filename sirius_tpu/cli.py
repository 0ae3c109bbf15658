"""sirius-scf command-line mini-app (reference: apps/mini_app/sirius.scf.cpp).

Round-1 stub: argument surface is in place; SCF driving lands with the dft
layer. Exits with a clear message rather than ModuleNotFoundError.
"""

from __future__ import annotations

import argparse
import sys

from sirius_tpu.runtime import PLATFORMS, enable_compile_cache, select_platform


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="sirius-scf",
        description="TPU-native Kohn-Sham DFT SCF mini-app (sirius_tpu)",
    )
    p.add_argument("input", nargs="?", default="sirius.json", help="JSON input file")
    p.add_argument("--test_against", help="reference output JSON to compare against")
    p.add_argument(
        "--task",
        default="ground_state_new",
        choices=["ground_state_new", "ground_state_restart", "ground_state_relax", "ground_state_direct", "k_point_path", "eos", "molecular_dynamics"],
        help="calculation task (reference sirius.scf task semantics)",
    )
    p.add_argument("--volume_scale0", type=float, default=0.95,
                   help="eos task: first volume scale")
    p.add_argument("--volume_scale1", type=float, default=1.05,
                   help="eos task: last volume scale")
    p.add_argument("--num_steps", type=int, default=7,
                   help="eos task: number of volume points")
    p.add_argument(
        "--platform",
        default=None,
        choices=list(PLATFORMS),
        help="JAX platform; 'cpu' runs the f64 verification path, 'tpu' "
        "the chip (32-bit device programs, CPU backend kept beside it for "
        "host stages) and fails where there is none. Default: cpu when the "
        "deck requests processing_unit=cpu, else the jax default.",
    )
    p.add_argument("-v", "--verbose", action="count", default=0,
                   help="raise log level (-v info, -vv debug)")
    args = p.parse_args(argv)

    from sirius_tpu.obs.log import setup as _log_setup

    _log_setup(args.verbose)

    import json
    import os

    # fail fast on a bad input path, before any (slow) jax backend init
    if not os.path.isfile(args.input):
        print(f"sirius-scf: input file not found: {args.input}", file=sys.stderr)
        return 2

    platform = args.platform
    if platform is None:
        try:
            with open(args.input) as f:
                if json.load(f).get("control", {}).get("processing_unit") == "cpu":
                    platform = "cpu"
        except (OSError, json.JSONDecodeError):
            pass
    select_platform(platform)
    enable_compile_cache()
    try:
        from sirius_tpu.dft.scf import run_scf_from_file
    except ModuleNotFoundError as e:
        if e.name in ("sirius_tpu.dft.scf", "sirius_tpu.dft"):
            print("sirius-scf: SCF driver not built yet in this revision", file=sys.stderr)
            return 2
        raise
    if args.task == "molecular_dynamics":
        from sirius_tpu.md.driver import run_md_from_file

        if args.test_against:
            print(
                "sirius-scf: --test_against is not supported by the "
                "molecular_dynamics task", file=sys.stderr,
            )
            return 2
        return run_md_from_file(args.input)
    if args.task == "eos":
        from sirius_tpu.apps_util import run_eos

        if args.test_against:
            print(
                "sirius-scf: --test_against is not supported by the eos "
                "task (no reference eos artifacts in-tree)", file=sys.stderr,
            )
            return 2
        cfg_dict = json.load(open(args.input))
        out = run_eos(
            cfg_dict, os.path.dirname(os.path.abspath(args.input)) or ".",
            args.volume_scale0, args.volume_scale1, num_steps=args.num_steps,
        )
        for v, e in zip(out["volume"], out["energy"]):
            print(f"volume: {v}, energy: {e}")
        return 0
    return run_scf_from_file(args.input, test_against=args.test_against, task=args.task)


if __name__ == "__main__":
    raise SystemExit(main())
