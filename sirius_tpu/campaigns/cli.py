"""sirius-campaign: run a campaign DAG end-to-end on a local engine.

Examples::

    # Γ-point finite-displacement phonons of a deck (13 nodes for a
    # 2-atom cell: base + 12 displaced, all warm-started from base)
    sirius-campaign phonon si.json --displacement 0.01 --slices 4

    # Birch-Murnaghan EOS sweep, 7 volumes
    sirius-campaign eos si.json --scale0 0.94 --scale1 1.06 --points 7

    # relax then a final SCF at the relaxed geometry
    sirius-campaign chain si.json --force-tol 1e-4

    # an explicit spec (the JSON sirius-campaign writes next to its
    # journal), e.g. to resume after a crash: completed nodes are not
    # re-run, the rest replay from the journal with their edges intact
    sirius-campaign run --spec work/campaign.phonon.spec.json --resume

The campaign journal (``campaign.<id>.journal`` in the workdir by
default) makes the graph durable: re-running with ``--resume`` after a
SIGKILL picks up exactly the unfinished nodes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from sirius_tpu.runtime import PLATFORMS, enable_compile_cache, select_platform


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--campaign-id", default=None,
                   help="campaign id (default: the template name)")
    p.add_argument("--slices", type=int, default=1,
                   help="device slices / concurrent nodes")
    p.add_argument("--workdir", default=".",
                   help="artifacts + journal + results live here")
    p.add_argument("--journal", default=None,
                   help="journal path (default: "
                        "<workdir>/campaign.<id>.journal)")
    p.add_argument("--events", default=None,
                   help="append JSONL observability events to this file "
                        "(default: <workdir>/campaign.<id>.events.jsonl)")
    p.add_argument("--timeout", type=float, default=3600.0,
                   help="overall wait bound in seconds")
    p.add_argument("--out", default=None,
                   help="result JSON path (default: "
                        "<workdir>/campaign.<id>.result.json)")
    p.add_argument("--resume", action="store_true",
                   help="re-attach to an existing journal instead of "
                        "submitting fresh nodes")
    p.add_argument("--platform", default=None, choices=list(PLATFORMS))
    p.add_argument("-v", "--verbose", action="count", default=0)


def _load_deck(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="sirius-campaign",
        description="DAG job campaigns over the sirius_tpu serving engine",
    )
    sub = p.add_subparsers(dest="command", required=True)

    ph = sub.add_parser("phonon", help="finite-displacement Γ phonons")
    ph.add_argument("deck", help="base JSON deck (cli.py format)")
    ph.add_argument("--displacement", type=float, default=0.01,
                    help="Cartesian displacement in bohr")
    ph.add_argument("--atoms", default=None,
                    help="comma-separated atom indices to displace "
                         "(default: all)")
    _add_common(ph)

    eo = sub.add_parser("eos", help="Birch-Murnaghan EOS volume sweep")
    eo.add_argument("deck", help="base JSON deck (cli.py format)")
    eo.add_argument("--scale0", type=float, default=0.94)
    eo.add_argument("--scale1", type=float, default=1.06)
    eo.add_argument("--points", type=int, default=7)
    _add_common(eo)

    ch = sub.add_parser("chain", help="relax then SCF at the relaxed "
                                      "geometry")
    ch.add_argument("deck", help="base JSON deck (cli.py format)")
    ch.add_argument("--max-steps", type=int, default=10)
    ch.add_argument("--force-tol", type=float, default=1e-4)
    _add_common(ch)

    rn = sub.add_parser("run", help="run an explicit CampaignSpec JSON")
    rn.add_argument("--spec", required=True, help="CampaignSpec JSON file")
    _add_common(rn)
    return p


def _build_spec(args):
    from sirius_tpu.campaigns import chain, eos, phonon
    from sirius_tpu.campaigns.spec import CampaignSpec

    if args.command == "run":
        with open(args.spec) as f:
            return CampaignSpec.from_dict(json.load(f))
    deck = _load_deck(args.deck)
    cid = args.campaign_id or args.command
    if args.command == "phonon":
        atoms = ([int(t) for t in args.atoms.split(",")]
                 if args.atoms else None)
        return phonon.phonon_campaign(
            deck, displacement=args.displacement, atoms=atoms,
            campaign_id=cid)
    if args.command == "eos":
        return eos.eos_campaign(
            deck, scale0=args.scale0, scale1=args.scale1,
            num_points=args.points, campaign_id=cid)
    return chain.relax_scf_campaign(
        deck, max_steps=args.max_steps, force_tol=args.force_tol,
        campaign_id=cid)


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)

    from sirius_tpu import obs

    obs.setup_logging(args.verbose)

    if args.command != "run" and not os.path.isfile(args.deck):
        print(f"sirius-campaign: deck not found: {args.deck}",
              file=sys.stderr)
        return 2

    from sirius_tpu.campaigns.spec import CampaignSpecError

    try:
        spec = _build_spec(args)
    except (CampaignSpecError, ValueError, OSError, KeyError) as e:
        print(f"sirius-campaign: bad campaign spec: {e}", file=sys.stderr)
        return 2

    select_platform(args.platform)
    enable_compile_cache()

    from sirius_tpu.campaigns import runner
    from sirius_tpu.serve.engine import ServeEngine
    from sirius_tpu.serve.queue import JobStatus

    cid = spec.campaign_id
    workdir = args.workdir
    os.makedirs(workdir, exist_ok=True)
    spec_path = os.path.join(workdir, f"campaign.{cid}.spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec.to_dict(), f, indent=2)
    journal = args.journal or os.path.join(workdir, f"campaign.{cid}.journal")
    events = args.events or os.path.join(
        workdir, f"campaign.{cid}.events.jsonl")

    eng = ServeEngine(
        num_slices=args.slices, workdir=workdir, verbose=args.verbose > 0,
        journal_path=journal, events_path=events)
    eng.start()
    t0 = time.time()
    try:
        if args.resume:
            handle = runner.resume_campaign(eng, spec, workdir=workdir)
            print(f"sirius-campaign: resumed {cid}: "
                  f"{len(handle.jobs)} node(s) replayed, "
                  f"{len(handle.prior_status)} already settled",
                  file=sys.stderr)
        else:
            handle = runner.submit_campaign(eng, spec, workdir=workdir)
        ok = handle.wait(timeout=args.timeout)
        res = handle.result()
        res["wall_s"] = time.time() - t0
        res["engine"] = eng.stats()
    finally:
        eng.shutdown(wait=True, mode="drain")
    out_path = args.out or os.path.join(
        workdir, f"campaign.{cid}.result.json")
    with open(out_path, "w") as f:
        json.dump(res, f, indent=2, default=float)
    summary = res.get("summary") or {}
    if summary.get("kind") == "phonon":
        freqs = ", ".join(
            f"{x:.1f}" for x in summary["frequencies_cm1"])
        print(f"phonon frequencies (cm^-1): {freqs}")
    elif summary.get("kind") == "eos":
        print(f"EOS fit: V0={summary['v0_bohr3']:.3f} bohr^3  "
              f"B0={summary['b0_gpa']:.2f} GPa  "
              f"B0'={summary['b0_prime']:.3f}")
    elif summary.get("kind") == "chain":
        print(f"chain: E_final={summary['final_energy_ha']:.10f} Ha in "
              f"{summary['final_scf_iterations']} warm iterations")
    print(json.dumps({k: v for k, v in res.items()
                      if k in ("campaign_id", "kind", "num_done",
                               "num_nodes", "wall_s")}, indent=2))
    print(f"sirius-campaign: result written to {out_path}",
          file=sys.stderr)
    if not ok:
        print("sirius-campaign: timed out waiting for nodes",
              file=sys.stderr)
        return 3
    all_done = all(
        handle.node_status(n.node_id) == JobStatus.DONE
        for n in spec.nodes)
    if not all_done or res.get("finalize_error"):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
