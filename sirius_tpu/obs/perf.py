"""Perf-gated bench time series (`sirius-bench` / tools/bench_regress.py).

Runs a pinned tier of synthetic decks under the span timeline
(obs/spans.py) with ``control.span_fence`` on, reduces every SCF stage to
a median + dispersion over repeats, and maintains a schema-versioned
``PERF_BASELINE.json`` *time series* — one entry per recorded run, newest
last. ``--compare`` re-measures and exits nonzero when any stage median
regresses beyond the tolerance recorded WITH the baseline (noise-aware:
each stage's tolerance is derived from its own observed dispersion, with
a generous floor so CPU jitter cannot page anyone).

Two comparison modes:

- absolute (default): stage medians in seconds — right when baseline and
  candidate run on the same machine class (the perf lab flow);
- ``--normalize``: stage *shares* of the iteration median — machine-
  independent, the mode the CI gate uses (a stage suddenly eating 2x its
  historical fraction of the iteration is a regression on any host).

Baseline schema::

    {"schema": 1,
     "series": [{"created": ..., "host": ..., "platform": ...,
                 "tiers": {"small": {"stages": {"scf.band_solve":
                     {"median_s": ..., "mad_s": ..., "p10_s": ..,
                      "p90_s": .., "n": .., "tol_ratio": ..,
                      "gflops": .., "roofline_gflops": ..,
                      "mfu": ..}, ...},
                     "iteration_median_s": .., "attributed_fraction": ..,
                     "repeats": .., "iterations": ..}}}]}
"""

from __future__ import annotations

import argparse
import json
import os
import platform as _platform
import statistics
import sys
import tempfile
import time

SCHEMA = 1

# stage tolerances never go below this ratio (CPU wall clocks are noisy;
# a 35% swing on a warm cache is routine)
MIN_TOL_RATIO = 1.5
# ignore regressions on stages faster than this (scheduler jitter floor)
ABS_FLOOR_S = 2e-3
# tolerance = max(MIN_TOL_RATIO, 1 + K * MAD/median): a stage that is
# noisy in the baseline gets proportionally more slack in the gate
TOL_MAD_K = 6.0

# pinned tiers: deck shape + iteration/repeat counts. The small tier is
# the CI deck (seconds on one CPU core); large is the perf-lab deck.
TIERS = {
    "small": {
        "gk_cutoff": 3.0, "pw_cutoff": 7.0, "num_bands": 8,
        "ngridk": [1, 1, 1], "num_dft_iter": 4, "repeats": 3,
    },
    "large": {
        "gk_cutoff": 4.0, "pw_cutoff": 9.0, "num_bands": 16,
        "ngridk": [1, 1, 1], "num_dft_iter": 3, "repeats": 2,
    },
}

# stages the gate watches (scf.setup, scf.finalize, the scf.run root and
# serve.* are not per-iteration, and scf.readback is pure sync noise
# without a device)
GATED_PREFIX = "scf."
UNGATED = {"scf.setup", "scf.readback", "scf.run", "scf.finalize"}


def tier_deck(spec: dict) -> dict:
    """Synthetic ultrasoft-Si deck for one tier (species-file free)."""
    return {
        "parameters": {
            "gk_cutoff": spec["gk_cutoff"],
            "pw_cutoff": spec["pw_cutoff"],
            "ngridk": list(spec["ngridk"]),
            "num_bands": spec["num_bands"],
            "use_symmetry": False,
            "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"],
            "smearing_width": 0.025,
            "num_dft_iter": spec["num_dft_iter"],
            # never converge early: every repeat must run the full pinned
            # iteration count or medians are not comparable
            "density_tol": 1e-14,
            "energy_tol": 1e-16,
        },
        "control": {
            "ngk_pad_quantum": 16,
            "telemetry": True,
            "span_fence": True,
            "verbosity": 0,
        },
        "synthetic": {"ultrasoft": True},
    }


def _median(xs):
    return statistics.median(xs)


def _mad(xs, med):
    return statistics.median([abs(x - med) for x in xs])


def _pct(xs, q):
    s = sorted(xs)
    i = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[i]


def run_tier(name: str, spec: dict, repeats: int | None = None,
             base_dir: str | None = None) -> dict:
    """Measure one tier: warmup run (compiles), then `repeats` measured
    runs under a span capture; reduce to per-stage statistics."""
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.obs import metrics as obs_metrics
    from sirius_tpu.obs import spans as obs_spans
    from sirius_tpu.obs.costs import peak_gflops
    from sirius_tpu.serve.scheduler import build_job_context

    nrep = int(repeats or spec["repeats"])
    own_tmp = base_dir is None
    tmp = tempfile.mkdtemp(prefix=f"sirius_bench_{name}_") if own_tmp \
        else base_dir
    cfg = load_config(tier_deck(spec))
    ctx = build_job_context(cfg, tmp)
    obs_metrics.set_enabled(True)
    # warmup: pays every XLA compile so the measured repeats see only
    # steady-state execution
    run_scf(cfg, base_dir=tmp, ctx=ctx)
    caps = []
    for _ in range(nrep):
        with obs_spans.capture() as cap:
            run_scf(cfg, base_dir=tmp, ctx=ctx)
        caps.append(cap)

    stages: dict[str, dict] = {}
    names = set()
    for cap in caps:
        names |= {n for n in cap.names() if n.startswith(GATED_PREFIX)}
    iter_durs = [d for cap in caps for d in cap.durations("scf.iteration")]
    iter_med = _median(iter_durs) if iter_durs else 0.0
    for sname in sorted(names):
        durs = [d for cap in caps for d in cap.durations(sname)]
        if not durs:
            continue
        med = _median(durs)
        mad = _mad(durs, med)
        ent = {
            "median_s": med,
            "mad_s": mad,
            "p10_s": _pct(durs, 0.10),
            "p90_s": _pct(durs, 0.90),
            "n": len(durs),
            "tol_ratio": max(MIN_TOL_RATIO,
                             1.0 + TOL_MAD_K * (mad / med if med > 0 else 0.0)),
        }
        if iter_med > 0 and sname != "scf.iteration":
            ent["share"] = med / iter_med
        # roofline annotations ride on the records (obs/costs.py)
        recs = [r for cap in caps for r in cap.by_name(sname)
                if "gflops" in r]
        if recs:
            ent["gflops"] = _median([r["gflops"] for r in recs])
            ent["roofline_gflops"] = recs[-1]["roofline_gflops"]
            ent["mfu"] = _median([r["mfu"] for r in recs])
        stages[sname] = ent

    # attribution check: per-iteration stage spans must explain the
    # iteration wall time (acceptance bar: >= 0.90 with fencing on)
    per_iter = [n for n in names
                if n not in UNGATED and n != "scf.iteration"]
    attributed = sum(stages[n]["median_s"] for n in per_iter
                     if n in stages)
    return {
        "deck": {k: spec[k] for k in
                 ("gk_cutoff", "pw_cutoff", "num_bands", "num_dft_iter")},
        "repeats": nrep,
        "iterations": len(iter_durs),
        "iteration_median_s": iter_med,
        "attributed_fraction": (attributed / iter_med) if iter_med else 0.0,
        "peak_gflops": peak_gflops(),
        "stages": stages,
    }


def measure(tiers: list[str], repeats: int | None = None) -> dict:
    entry = {
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": _platform.node(),
        "platform": None,
        "cpu_count": os.cpu_count(),
        "tiers": {},
    }
    from sirius_tpu.obs.costs import detect_platform

    entry["platform"] = detect_platform()
    for t in tiers:
        if t not in TIERS:
            raise SystemExit(f"unknown tier '{t}' (have {sorted(TIERS)})")
        entry["tiers"][t] = run_tier(t, TIERS[t], repeats=repeats)
    return entry


def load_baseline(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != SCHEMA:
        raise SystemExit(
            f"{path}: schema {doc.get('schema')!r} != supported {SCHEMA}")
    if not doc.get("series"):
        raise SystemExit(f"{path}: empty series")
    return doc


def compare(base_entry: dict, cur_entry: dict, normalize: bool = False,
            min_ratio: float | None = None) -> list[dict]:
    """Regressions of `cur_entry` vs `base_entry` (the newest series
    element). A stage present in the baseline but missing from the
    candidate is itself a regression — silently losing attribution is how
    perf gates rot."""
    regressions = []
    for tname, base_tier in base_entry["tiers"].items():
        cur_tier = cur_entry["tiers"].get(tname)
        if cur_tier is None:
            continue  # not re-measured this run (e.g. CI runs small only)
        base_iter = base_tier.get("iteration_median_s") or 0.0
        cur_iter = cur_tier.get("iteration_median_s") or 0.0
        for sname, b in base_tier["stages"].items():
            if sname in UNGATED:
                continue
            c = cur_tier["stages"].get(sname)
            if c is None:
                regressions.append({
                    "tier": tname, "stage": sname, "kind": "missing",
                    "detail": "stage present in baseline, absent now",
                })
                continue
            tol = float(b.get("tol_ratio", MIN_TOL_RATIO))
            if min_ratio is not None:
                tol = max(tol, float(min_ratio))
            if normalize and sname != "scf.iteration":
                if base_iter <= 0 or cur_iter <= 0:
                    continue
                bv = b["median_s"] / base_iter
                cv = c["median_s"] / cur_iter
                unit = "share"
            else:
                bv, cv = b["median_s"], c["median_s"]
                unit = "s"
            if bv <= 0:
                continue
            ratio = cv / bv
            if ratio > tol and (normalize
                                or (cv - bv) > ABS_FLOOR_S):
                regressions.append({
                    "tier": tname, "stage": sname, "kind": "slower",
                    "baseline": bv, "current": cv, "unit": unit,
                    "ratio": ratio, "tol_ratio": tol,
                })
    return regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="sirius-bench",
        description="span-attributed SCF bench + perf regression gate")
    ap.add_argument("--tiers", default="small",
                    help="comma list of tiers to run (small,large)")
    ap.add_argument("--repeats", type=int, default=None,
                    help="override the tier's pinned repeat count")
    ap.add_argument("--compare", metavar="BASELINE",
                    help="compare against the newest entry of this "
                    "PERF_BASELINE.json; exit 1 on regression")
    ap.add_argument("--update", metavar="BASELINE",
                    help="append this run to the baseline series "
                    "(creates the file if missing)")
    ap.add_argument("--normalize", action="store_true",
                    help="gate on stage shares of the iteration median "
                    "(machine-independent; the CI mode)")
    ap.add_argument("--min-ratio", type=float, default=None,
                    help="floor every stage tolerance at this ratio "
                    "(e.g. 2.0 for noisy CI hosts)")
    ap.add_argument("--out", metavar="PATH",
                    help="also write this run's entry as JSON")
    args = ap.parse_args(argv)

    tiers = [t.strip() for t in args.tiers.split(",") if t.strip()]
    entry = measure(tiers, repeats=args.repeats)

    for tname, tier in entry["tiers"].items():
        print(f"[{tname}] iteration median "
              f"{tier['iteration_median_s'] * 1e3:.2f} ms, "
              f"attributed {tier['attributed_fraction'] * 100:.1f}%")
        for sname, s in sorted(tier["stages"].items()):
            extra = ""
            if "gflops" in s:
                extra = (f"  {s['gflops']:.2f} GFLOP/s"
                         f" (roof {s['roofline_gflops']:.0f},"
                         f" mfu {s['mfu'] * 100:.2f}%)")
            print(f"  {sname:<18} {s['median_s'] * 1e3:9.3f} ms"
                  f" ±{s['mad_s'] * 1e3:.3f}{extra}")

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"schema": SCHEMA, "series": [entry]}, f, indent=1)
        print(f"wrote {args.out}")

    rc = 0
    if args.compare:
        doc = load_baseline(args.compare)
        regs = compare(doc["series"][-1], entry,
                       normalize=args.normalize, min_ratio=args.min_ratio)
        if regs:
            rc = 1
            print(f"PERF REGRESSION vs {args.compare} "
                  f"({doc['series'][-1]['created']}):", file=sys.stderr)
            for r in regs:
                if r["kind"] == "missing":
                    print(f"  {r['tier']}/{r['stage']}: {r['detail']}",
                          file=sys.stderr)
                else:
                    print(f"  {r['tier']}/{r['stage']}: "
                          f"{r['baseline']:.4g} -> {r['current']:.4g} "
                          f"{r['unit']} ({r['ratio']:.2f}x > "
                          f"{r['tol_ratio']:.2f}x allowed)",
                          file=sys.stderr)
        else:
            print(f"perf gate OK vs {args.compare}")

    if args.update:
        if os.path.exists(args.update):
            doc = load_baseline(args.update)
        else:
            doc = {"schema": SCHEMA, "series": []}
        doc["series"].append(entry)
        with open(args.update, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"appended to {args.update} "
              f"({len(doc['series'])} entries)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
