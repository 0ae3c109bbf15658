#!/usr/bin/env python3
"""Write configs/<config>/refs.json: per geometry the f64 total energy of the
plain path (CPU backend, precision_wf fp64, device_scf off, tight
tolerances). Run once, in the sandbox; the benchmark's runs only read it.

  python benchmark/make_refs.py --config si2-k444-us --geometries 8
  python benchmark/make_refs.py --config si2-k444-us --only 3   # one geometry, merged in
"""

from __future__ import annotations

import argparse
import fcntl
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # the checkout: sirius_tpu, benchmark


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--geometries", type=int, default=None)
    ap.add_argument("--only", type=int, action="append", default=None)
    ap.add_argument("--block", default="deck", choices=["deck", "rehearse"])
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_enable_x64", True)
    from benchmark.harness import decks
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.serve.scheduler import build_job_context

    cdir = os.path.join(HERE, "configs", args.config)
    with open(os.path.join(cdir, "config.json")) as f:
        config = json.load(f)
    name = "refs.json" if args.block == "deck" else "refs_rehearse.json"
    path = os.path.join(cdir, name)
    geometry = dict(config["geometry"], **config[args.block].get("geometry", {}))
    total = args.geometries or int(geometry["geometries"])
    todo = args.only if args.only is not None else list(range(total))
    for g in todo:
        deck = decks.reference_deck(config, g, args.block)
        cfg = load_config(deck)
        ctx = build_job_context(cfg, ".")
        t0 = time.time()
        r = run_scf(cfg, ctx=ctx, devices=jax.devices("cpu")[:1])
        wall = time.time() - t0
        if not r["converged"]:
            raise RuntimeError(f"reference of geometry {g} did not converge")
        entry = {"energy_total_ha": float(r["energy"]["total"]),
                 "scf_iterations": int(r["num_scf_iterations"]),
                 "wall_s_cpu": round(wall, 1)}
        # merge under the file as it is now: several processes may each add
        # one, so the read-modify-write holds a lock on the directory
        lock = os.open(cdir, os.O_RDONLY)
        fcntl.flock(lock, fcntl.LOCK_EX)
        refs = {"config": args.config, "how": config["reference"]["how"],
                "geometries": {}}
        if os.path.exists(path):
            with open(path) as f:
                refs = json.load(f)
        refs["geometries"][str(g)] = entry
        refs["geometries"] = dict(sorted(refs["geometries"].items(),
                                         key=lambda kv: int(kv[0])))
        tmp = path + f".tmp{g}"
        with open(tmp, "w") as f:
            json.dump(refs, f, indent=1)
            f.write("\n")
        os.replace(tmp, path)
        os.close(lock)
        print(json.dumps({"config": args.config, "geometry": g, **entry}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
