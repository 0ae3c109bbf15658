#!/usr/bin/env python3
"""Serving load generator: replay a tier-1 deck mix through ServeEngine
and report throughput + latency + cache hit rate to SERVE_BENCH.json.

The mix is the tier-1 synthetic-silicon deck family (testing.py species,
no reference files needed): a base deck repeated with perturbed atomic
positions (same shape bucket — the geometry-screening serving case, fully
cache-shared) plus a second k-mesh variant (a second bucket). Padded
shapes + the executable cache mean only the first job of each bucket
compiles.

``--mix campaigns`` additionally runs a Γ-phonon campaign DAG
(sirius_tpu.campaigns) concurrently with the single-job traffic, and the
artifact reports submit-to-terminal latency per class (``single`` vs
``campaign_node`` — campaign nodes queue behind their dependency edges,
so their latency distribution is the interesting one).

``--mix screening`` (ISSUE 19) models the geometry-screening fleet case:
``--requests`` submissions drawn Zipf(``--zipf``)-skewed from a catalog
of ``--unique`` distinct decks, spread across ``--tenants`` tenants.
Three sub-runs feed one artifact:

1. *baseline*: a single engine, dedup off, FIFO — the cost of answering
   every request with a fresh SCF;
2. *fleet*: two federated engines sharing one FleetDir + result store,
   dedup on, fair-share on — duplicate requests attach to the in-flight
   donor or answer from the store, and the artifact reports per-tenant
   p50/p95 plus the dedup hit rate and the effective-jobs/min speedup
   over the baseline;
3. *fair-share A/B*: a whale tenant floods the queue before small
   tenants submit; per-tenant latency under FIFO-priority vs weighted
   deficit-round-robin, side by side.

Usage:
    python tools/loadgen.py [--jobs N] [--slices S] [--mix campaigns]
                            [--out SERVE_BENCH.json]
    python tools/loadgen.py --mix screening --tenants 3 --zipf 1.2 \
                            --requests 48 --unique 6

Exit status 0 = every job converged.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def make_deck(positions=None, ngridk=(1, 1, 1), device_scf="auto") -> dict:
    """A tier-1 synthetic-Si deck in cli.py JSON form."""
    deck = {
        "parameters": {
            "gk_cutoff": 3.0,
            "pw_cutoff": 7.0,
            "ngridk": list(ngridk),
            "num_bands": 8,
            "use_symmetry": False,
            "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"],
            "smearing_width": 0.025,
            "num_dft_iter": 40,
            "density_tol": 5e-9,
            "energy_tol": 1e-10,
        },
        "control": {
            "device_scf": device_scf,
            "ngk_pad_quantum": 16,
        },
        "synthetic": {"ultrasoft": True},
    }
    if positions is not None:
        deck["synthetic"]["positions"] = positions
    return deck


def deck_mix(num_jobs: int) -> list[dict]:
    """num_jobs decks: perturbed-position family + a 2x1x1-kmesh variant."""
    mix = []
    for i in range(num_jobs):
        if i % 4 == 3:
            mix.append(make_deck(ngridk=(2, 1, 1)))
        else:
            d = 0.002 * (i % 4)
            mix.append(make_deck(
                positions=[[0.0, 0.0, 0.0],
                           [0.25 + d, 0.25 - d, 0.25 + d]],
            ))
    return mix


# metric families worth keeping in the reviewable artifact; everything
# else (per-span histograms, device gauges, ...) needs --full-obs
OBS_WHITELIST = (
    "serve_job_run_seconds",
    "serve_job_retries_total",
    "serve_job_failures_total",
    "jax_backend_compiles_total",
    "scf_iterations_total",
    "scf_iteration_seconds",
)


def latency_summary(jobs) -> dict:
    """Submit-to-terminal latency stats for one job class."""
    lats = sorted(j.latency for j in jobs
                  if j.latency is not None and j.status == "done")

    def pct(p):
        if not lats:
            return None
        k = min(len(lats) - 1, max(0, int(round(p / 100 * (len(lats) - 1)))))
        return lats[k]

    return {
        "count": len(lats),
        "p50_s": pct(50),
        "p95_s": pct(95),
        "mean_s": (sum(lats) / len(lats)) if lats else None,
    }


def summarize_registry(registry: dict, whitelist=OBS_WHITELIST) -> dict:
    """Condense a metrics snapshot for the JSON artifact: whitelisted
    families only, histograms reduced to {labels, count, sum} (bucket
    vectors dropped). The full registry grew SERVE_BENCH.json to ~770
    lines; this keeps the artifact reviewable in a diff."""
    out = {}
    for fam, body in registry.items():
        if fam not in whitelist:
            continue
        samples = []
        for s in body.get("samples", []):
            if body.get("type") == "histogram":
                samples.append({"labels": s.get("labels", {}),
                                "count": s.get("count"),
                                "sum": s.get("sum")})
            else:
                samples.append({"labels": s.get("labels", {}),
                                "value": s.get("value")})
        out[fam] = {"type": body.get("type"), "samples": samples}
    return out


def _pct(lats, p):
    """Percentile of an already-sorted latency list (None when empty)."""
    if not lats:
        return None
    k = min(len(lats) - 1, max(0, int(round(p / 100 * (len(lats) - 1)))))
    return lats[k]


def _per_tenant_rows(samples) -> dict:
    """{tenant: {count,p50_s,p95_s}} from (tenant, latency_s) pairs."""
    by = {}
    for tenant, lat in samples:
        by.setdefault(tenant, []).append(lat)
    rows = {}
    for tenant in sorted(by):
        lats = sorted(by[tenant])
        rows[tenant] = {"count": len(lats),
                        "p50_s": _pct(lats, 50), "p95_s": _pct(lats, 95)}
    return rows


def screening_catalog(unique: int) -> list[dict]:
    """``unique`` distinct tier-1 decks, all in one shape bucket (the
    screening case: one structure, many candidate geometries)."""
    decks = []
    for k in range(unique):
        d = 0.0015 * (k + 1)
        decks.append(make_deck(
            positions=[[0.0, 0.0, 0.0], [0.25 + d, 0.25 - d, 0.25 + d]]))
    return decks


def screening_stream(requests: int, unique: int, tenants: int,
                     zipf_s: float, seed: int) -> list[tuple[str, int]]:
    """(tenant, deck_index) request stream: deck popularity follows
    Zipf(s) over the catalog rank (rank-1 dominates — the hot candidate
    everyone screens), tenants drawn uniformly."""
    rng = random.Random(seed)
    weights = [1.0 / (k + 1) ** zipf_s for k in range(unique)]
    return [(f"tenant{rng.randrange(tenants)}",
             rng.choices(range(unique), weights=weights)[0])
            for _ in range(requests)]


def run_screening(args, workdir: str) -> int:
    """The three screening sub-runs; writes the combined artifact."""
    from sirius_tpu.fleet import FleetDir
    from sirius_tpu.serve.engine import ServeEngine

    os.makedirs(workdir, exist_ok=True)
    catalog = screening_catalog(args.unique)
    stream = screening_stream(args.requests, args.unique, args.tenants,
                              args.zipf, args.seed)
    tenant_names = sorted({t for t, _ in stream})
    deck_desc = (f"synthetic-Si screening: {args.unique} geometries, "
                 f"Zipf(s={args.zipf}) popularity, {args.tenants} tenants")

    # -- sub-run 1: single engine, no dedup, FIFO (the baseline) ----------
    # A reduced request count: every request is a fresh SCF here, so the
    # full stream would just multiply wall time without changing the rate.
    base_n = min(args.baseline_requests, len(stream))
    print(f"[screening] baseline: 1 engine, dedup off, {base_n} requests")
    eng = ServeEngine(num_slices=args.slices,
                      workdir=os.path.join(workdir, "baseline"),
                      verbose=True,
                      events_path=os.path.join(workdir, "events.jsonl"))
    eng.start()
    t0 = time.monotonic()
    for i, (tenant, k) in enumerate(stream[:base_n]):
        eng.submit(catalog[k], job_id=f"base-{i}", tenant=tenant)
    base_ok = eng.wait_all(timeout=3600.0)
    base_wall = time.monotonic() - t0
    base_stats = eng.stats()
    base_lats = sorted(j.latency for j in eng._submitted
                       if j.latency is not None and j.status == "done")
    eng.shutdown(wait=True)
    baseline = {
        "engines": 1, "dedup": False, "fair_share": False,
        "requests": base_n, "num_done": base_stats["num_done"],
        "wall_s": base_wall,
        "jobs_per_min": base_stats["num_done"] / base_wall * 60.0,
        "p50_latency_s": _pct(base_lats, 50),
        "p95_latency_s": _pct(base_lats, 95),
    }

    # -- sub-run 2: two federated engines, dedup on, fair-share on --------
    print(f"[screening] fleet: 2 engines, dedup on, "
          f"{len(stream)} requests")
    fleet_root = os.path.join(workdir, "fleet")
    weights = {t: 1.0 for t in tenant_names}
    common = dict(num_slices=args.slices, fleet_dir=fleet_root,
                  fleet_poll=0.1, lease_ttl=6.0, fair_share=True,
                  tenants=weights, verbose=True,
                  events_path=os.path.join(workdir, "events.jsonl"))
    # disjoint device halves: two engines in ONE process each running
    # all-device collective programs from their own worker threads can
    # deadlock in the XLA CPU rendezvous (both wait for the shared
    # intra-op pool). Separate-process engines (chaos fleet_kill) don't
    # have this problem.
    import jax  # deferred: XLA_FLAGS is set in main() before first use
    devs = jax.devices()
    half = max(1, len(devs) // 2)
    e1 = ServeEngine(workdir=os.path.join(workdir, "e1"), engine_id="e1",
                     devices=devs[:half],
                     metrics_port=args.metrics_port, **common)
    e2 = ServeEngine(workdir=os.path.join(workdir, "e2"), engine_id="e2",
                     devices=devs[half:] or devs[:half], **common)
    e1.start()
    e2.start()
    client = FleetDir(fleet_root, owner="loadgen-client")
    t0 = time.monotonic()
    reqs = []  # one row per REQUEST (many requests -> one fleet job)
    for i, (tenant, k) in enumerate(stream):
        rec = client.submit(catalog[k], tenant=tenant,
                            trace_id=f"screen-{i}")
        reqs.append({"tenant": tenant, "deck": k,
                     "job_id": rec["job_id"],
                     "attached": bool(rec.get("attached")),
                     "submit_t": time.monotonic()})
    # poll for terminal records, stamping completion per fleet job
    pending = {r["job_id"] for r in reqs}
    done_t: dict[str, float] = {}
    deadline = time.monotonic() + 3600.0
    while pending and time.monotonic() < deadline:
        for jid in list(pending):
            if client.read_terminal(jid) is not None:
                done_t[jid] = time.monotonic()
                pending.discard(jid)
        if pending:
            time.sleep(0.1)
    fleet_wall = time.monotonic() - t0
    answered = [r for r in reqs if r["job_id"] in done_t]
    terminals = {jid: (client.read_terminal(jid) or {}) for jid in done_t}
    num_done_requests = sum(
        1 for r in answered
        if terminals.get(r["job_id"], {}).get("status") == "done")
    finished_by: dict = {}
    for rec in terminals.values():
        owner = rec.get("owner") or "?"
        finished_by[owner] = finished_by.get(owner, 0) + 1
    tenant_lats = [(r["tenant"], max(0.0, done_t[r["job_id"]]
                                     - r["submit_t"]))
                   for r in answered]
    d1, d2 = e1.stats()["dedup"], e2.stats()["dedup"]
    obs_snap = e1.metrics_snapshot()
    attach_count = sum(1 for r in reqs if r["attached"])
    lookups = d1["lookups"] + d2["lookups"]
    memo_hits = d1["memo_hits"] + d2["memo_hits"]
    watcher_attaches = d1["watcher_attaches"] + d2["watcher_attaches"]
    # dedup hit rate over the REQUEST stream: a request is a hit when it
    # never cost a fresh SCF — attached at the fleet dir, answered from
    # the store, or watcher-attached inside an engine
    hits = attach_count + memo_hits + watcher_attaches
    fleet = {
        "engines": 2, "dedup": True, "fair_share": True,
        "requests": len(stream), "unique_decks": args.unique,
        "num_answered": len(answered), "num_done": num_done_requests,
        "wall_s": fleet_wall,
        "effective_jobs_per_min": num_done_requests / fleet_wall * 60.0,
        "dedup_hit_rate": hits / max(1, len(stream)),
        "fleet_attach_count": attach_count,
        "engine_memo_hits": memo_hits,
        "engine_watcher_attaches": watcher_attaches,
        "engine_store_lookups": lookups,
        "jobs_finished_by_engine": finished_by,
        "per_tenant": _per_tenant_rows(tenant_lats),
        "store": e1.stats()["dedup"]["store"],
    }
    if args.linger > 0 and e1.metrics_url:
        print(f"[screening] lingering {args.linger}s at {e1.metrics_url}")
        time.sleep(args.linger)
    e1.shutdown(wait=True)
    e2.shutdown(wait=True)

    # -- sub-run 3: fair-share vs FIFO under a whale flood ----------------
    whale_jobs = max(4, args.requests // 8)
    small_each = 2

    def fairshare_run(fair_share: bool) -> dict:
        tag = "drr" if fair_share else "fifo"
        print(f"[screening] fair-share A/B: {tag}, whale={whale_jobs} "
              f"jobs, 2 small tenants x {small_each}")
        e = ServeEngine(num_slices=1,
                        workdir=os.path.join(workdir, f"ab_{tag}"),
                        verbose=True, fair_share=fair_share,
                        tenants={"whale": 1.0, "small0": 1.0,
                                 "small1": 1.0},
                        events_path=os.path.join(workdir, "events.jsonl"))
        # whale floods first, small tenants arrive behind the backlog;
        # submit before start so ordering is purely the queue's choice
        for i in range(whale_jobs):
            e.submit(catalog[0], job_id=f"{tag}-whale-{i}", tenant="whale")
        for t in ("small0", "small1"):
            for i in range(small_each):
                e.submit(catalog[1], job_id=f"{tag}-{t}-{i}", tenant=t)
        e.start()
        ok = e.wait_all(timeout=3600.0)
        rows = _per_tenant_rows(
            [(j.tenant, j.latency) for j in e._submitted
             if j.latency is not None and j.status == "done"])
        e.shutdown(wait=True)
        return {"ok": ok, "per_tenant": rows}

    ab_fifo = fairshare_run(False)
    ab_drr = fairshare_run(True)

    def small_p95(run):
        vals = [run["per_tenant"][t]["p95_s"]
                for t in ("small0", "small1")
                if run["per_tenant"].get(t, {}).get("p95_s") is not None]
        return max(vals) if vals else None

    bench = {
        "bench": "serve_loadgen",
        "mix": "screening",
        "deck": deck_desc,
        "tenants": args.tenants,
        "zipf_s": args.zipf,
        "requests": args.requests,
        "unique_decks": args.unique,
        "seed": args.seed,
        "num_slices": args.slices,
        "baseline_single_engine": baseline,
        "fleet": fleet,
        "speedup_effective_jobs_per_min": (
            fleet["effective_jobs_per_min"] / baseline["jobs_per_min"]
            if baseline["jobs_per_min"] else None),
        "fair_share_ab": {
            "scenario": (f"whale floods {whale_jobs} jobs before 2 small "
                         f"tenants submit {small_each} each; 1 slice, "
                         "equal weights"),
            "fifo": ab_fifo["per_tenant"],
            "fair_share": ab_drr["per_tenant"],
            "small_tenant_worst_p95_fifo_s": small_p95(ab_fifo),
            "small_tenant_worst_p95_fair_share_s": small_p95(ab_drr),
        },
        "obs": {
            "backend_compiles_total": obs_snap["backend_compiles_total"],
            "registry": summarize_registry(
                obs_snap["registry"],
                whitelist=OBS_WHITELIST + (
                    "fleet_lease_ops_total", "fleet_memo_total",
                    "fleet_watcher_attaches_total",
                    "serve_tenant_queue_depth")),
        },
        "events_log": os.path.join(workdir, "events.jsonl"),
    }
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=2, default=float)
    print(json.dumps(bench, indent=2, default=float))
    print(f"wrote {args.out}")
    ok = (base_ok and baseline["num_done"] == base_n
          and num_done_requests == len(stream)
          and ab_fifo["ok"] and ab_drr["ok"])
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--jobs", type=int, default=8)
    ap.add_argument("--slices", type=int, default=2)
    ap.add_argument("--mix", default="decks",
                    choices=["decks", "campaigns", "screening"],
                    help="decks: independent deck family only; campaigns: "
                         "the same family plus a concurrent Γ-phonon "
                         "campaign DAG, with per-class latency reported; "
                         "screening: Zipf-skewed multi-tenant fleet run "
                         "with dedup + fair-share (ISSUE 19)")
    ap.add_argument("--devices", type=int, default=4,
                    help="virtual CPU device count (0 = leave platform as-is);"
                         " >1 per slice keeps the fused/exec-cache path on")
    ap.add_argument("--out", default=os.path.join(REPO, "SERVE_BENCH.json"))
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--full-obs", action="store_true",
                    help="embed the FULL metrics registry in the artifact "
                         "instead of the whitelisted summary")
    sc = ap.add_argument_group("screening mix (ISSUE 19)")
    sc.add_argument("--tenants", type=int, default=3,
                    help="number of tenants in the request stream")
    sc.add_argument("--zipf", type=float, default=1.2,
                    help="Zipf skew s of deck popularity (larger = hotter "
                         "head, more dedup)")
    sc.add_argument("--requests", type=int, default=48,
                    help="total screening requests across all tenants")
    sc.add_argument("--unique", type=int, default=6,
                    help="distinct decks in the screening catalog")
    sc.add_argument("--baseline-requests", type=int, default=6,
                    help="requests for the no-dedup single-engine "
                         "baseline (each is a fresh SCF)")
    sc.add_argument("--seed", type=int, default=20260807,
                    help="stream-sampling seed")
    sc.add_argument("--metrics-port", type=int, default=None,
                    help="obs HTTP port on fleet engine e1 (screening)")
    sc.add_argument("--linger", type=float, default=0.0,
                    help="keep fleet engines (and /metrics) up this many "
                         "seconds after the run, for external scrapes")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # Must happen before jax initializes: a 1-device gamma-point run takes
    # the serial gamma path and never builds FusedScf, so the executable
    # cache would sit idle. Virtual devices give every slice a real mesh.
    flags = os.environ.get("XLA_FLAGS", "")
    if args.devices > 1 and "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import tempfile

    from sirius_tpu.runtime import enable_compile_cache
    from sirius_tpu.serve.engine import ServeEngine

    enable_compile_cache()
    workdir = args.workdir or tempfile.mkdtemp(prefix="sirius_loadgen_")
    if args.mix == "screening":
        return run_screening(args, workdir)
    eng = ServeEngine(num_slices=args.slices, workdir=workdir, verbose=True,
                      events_path=os.path.join(workdir, "events.jsonl"))
    eng.start()
    handle = None
    if args.mix == "campaigns":
        from sirius_tpu.campaigns import runner as campaign_runner
        from sirius_tpu.campaigns.phonon import phonon_campaign

        spec = phonon_campaign(
            make_deck(positions=[[0.0, 0.0, 0.0], [0.25, 0.25, 0.25]]),
            campaign_id="lg")
        handle = campaign_runner.submit_campaign(eng, spec, workdir=workdir)
    for i, deck in enumerate(deck_mix(args.jobs)):
        eng.submit(deck, job_id=f"lg-{i}")
    ok = eng.wait_all(timeout=3600.0)
    # snapshot BEFORE shutdown so queue/latency gauges reflect the run
    obs_snap = eng.metrics_snapshot()
    eng.shutdown(wait=True)

    stats = eng.stats()
    singles = [j for j in eng._submitted if j.campaign_id is None]
    nodes = [j for j in eng._submitted if j.campaign_id is not None]
    bench = {
        "bench": "serve_loadgen",
        "mix": args.mix,
        "deck": "synthetic-Si gk=3.0 pw=7.0 nb=8 (tier-1 mix)",
        "num_jobs": stats["num_jobs"],
        "num_done": stats["num_done"],
        "num_failed": stats["num_failed"],
        "num_slices": stats["num_slices"],
        "wall_s": stats["wall_s"],
        "jobs_per_min": stats["jobs_per_min"],
        "p50_latency_s": stats["p50_latency_s"],
        "p95_latency_s": stats["p95_latency_s"],
        "per_class_latency": {
            "single": latency_summary(singles),
            "campaign_node": latency_summary(nodes),
        },
        "cache_hit_rate": stats["cache"]["hit_rate"],
        "cache": stats["cache"],
        "retries_total": stats["retries_total"],
        # final observability snapshot: compile counts, queue high-water,
        # per-bucket latency histograms — whitelisted summary by default,
        # the full registry dump behind --full-obs
        "obs": {
            "backend_compiles_total": obs_snap["backend_compiles_total"],
            "queue_depth_high_water": obs_snap["queue_depth_high_water"],
            "cache_hit_rate": stats["cache"]["hit_rate"],
            "latency_by_bucket": obs_snap["registry"].get(
                "serve_job_run_seconds", {}).get("samples", []),
            "registry": (obs_snap["registry"] if args.full_obs
                         else summarize_registry(obs_snap["registry"])),
            "registry_full": bool(args.full_obs),
        },
        "events_log": os.path.join(workdir, "events.jsonl"),
        "per_job": [j.to_dict() for j in eng._submitted],
    }
    if handle is not None:
        camp = handle.result()
        bench["campaign"] = {k: camp.get(k) for k in (
            "campaign_id", "kind", "num_nodes", "num_done",
            "scf_iterations", "finalize_error")}
        bench["campaign"]["summary_kind"] = (
            (camp.get("summary") or {}).get("kind"))
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=2, default=float)
    print(json.dumps({k: v for k, v in bench.items() if k != "per_job"},
                     indent=2, default=float))
    print(f"wrote {args.out}")
    return 0 if (ok and stats["num_done"] == stats["num_jobs"]) else 1


if __name__ == "__main__":
    raise SystemExit(main())
