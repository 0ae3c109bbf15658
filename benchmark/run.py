#!/usr/bin/env python3
"""The benchmark's one command.

  python benchmark/run.py --workload W --seed N --seconds S --trace 0|1

Runs one cell of BENCHMARK.json on the machine it is started on: set-up
(imports, device check, compile cache, one untimed warm-up job), then a
window of S seconds of the cell's traffic, then the check of every counted
job against the stored f64 reference of its geometry. The last line of
standard output is the contract's JSON object; every line before it is one
JSON object naming platform, device kind and device count.

No TPU, or fewer chips than the cell asks for, is a non-zero exit and no
result line, except under --rehearse: the configuration's ``rehearse`` block
on the CPU backend (virtual devices for a four-chip cell), whose last line
always says "correct": false.

--control-precision P (default|high) runs the cell with run_scf's f32
matmuls at a lower precision than the configuration states (`highest`): the
control of the check, which has to come out "correct": false. It is forced
from here (runtime.scf_scope is replaced), with no knob in the program.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control-precision", default=None,
                    choices=["default", "high"])
    return ap.parse_args(argv)


def force_matmul_precision(precision: str) -> None:
    """The control: run_scf enters runtime.scf_scope(); give it one that
    computes f32 matmuls at ``precision`` instead of `highest`."""
    import jax

    from sirius_tpu import runtime

    @contextlib.contextmanager
    def lowered_scope():
        with runtime.host_scope(), jax.default_matmul_precision(precision):
            yield

    runtime.scf_scope = lowered_scope


def run_cell(cell, devices, platform: str, seed: int, seconds: float,
             trace: bool, block: str, say, workdir: str,
             warmup: bool = True) -> dict:
    """Everything after the look for a chip: warm-up, window, check, and (in
    a traced run) one traced job and the layer metrics. Returns the parts of
    the result line, ``correct`` as the check decided it."""
    import jax

    from benchmark.harness import check, counters, decks, jobs, sources
    from benchmark.harness import trace_reduce, window

    config, traffic = cell.config, cell.traffic
    refs = cell.refs if block == "deck" else cell.refs_rehearse
    geometry = dict(config["geometry"], **config[block].get("geometry", {}))
    num_geometries = int(geometry["geometries"])
    atoms = decks.atoms(config, block)
    g0 = seed % num_geometries
    control = {"span_fence": True} if trace else {}

    def deck_of(g, extra=None):
        deck = decks.job_deck(config, g, block)
        deck.setdefault("control", {}).update(control, **(extra or {}))
        return deck

    count = counters.Counters()
    runner = jobs.RUNNERS[traffic["runner"]](devices, traffic, workdir)
    try:
        if warmup:  # a control run has no timing to protect and skips it
            t0 = time.perf_counter()
            warm = runner.run(deck_of(g0))
            say(event="warmup", geometry=g0, seconds=time.perf_counter() - t0,
                iterations=warm["result"].get("num_scf_iterations"),
                **count.take())
        setup_s = time.time() - T_PROCESS

        def run_one(i):
            g = (g0 + i) % num_geometries
            rec = {"geometry": g}
            rec.update(runner.run(deck_of(g)))
            return rec

        records, _ = window.closed_loop(run_one, int(traffic["clients"]),
                                        seconds)
        in_window = count.take()
        memory_peak = counters.peak_hbm(devices)

        trace_job, traced, raw, steps = None, None, None, 0
        if trace:
            # one more job, fence off, with the program's own trace capture
            # armed for its first iterations (control.trace_capture)
            tdir = os.path.join(workdir, "trace")
            shutil.rmtree(tdir, ignore_errors=True)
            steps = int(config.get("trace_capture_steps", 5))
            control = {}
            g = (g0 + len(records)) % num_geometries
            trace_job = {"geometry": g}
            trace_job.update(runner.run(deck_of(g, {
                "trace_capture": tdir, "trace_capture_steps": steps})))
            path = trace_reduce.find_xplane(tdir)
            t_read = time.perf_counter()
            if path is not None:
                raw = trace_reduce.read_xplane(path)
                traced = trace_reduce.reduce(raw)
                say(event="trace", file_bytes=os.path.getsize(path),
                    lines=raw["lines"], num_events=traced["num_events"],
                    busy_by_device_s=traced["busy_by_device_s"],
                    window_s=traced["window_s"], modules=traced["modules"],
                    read_s=time.perf_counter() - t_read)
            shutil.rmtree(tdir, ignore_errors=True)
    finally:
        runner.close()

    tol = float(config["guarantee"]["energy_tol_ha_per_atom"])
    path = config["expected_path"]
    for rec in records:
        check.judge(rec, refs, atoms, tol, platform, path, cell.chips)
        res = rec.get("result") or {}
        say(event="job", index=rec["index"], geometry=rec["geometry"],
            t_start=rec["t_start"], seconds=rec["seconds"],
            iterations=res.get("num_scf_iterations"),
            converged=res.get("converged"),
            energy_ha=(res.get("energy") or {}).get("total"),
            abs_de_ha=rec["abs_de_ha"], de_limit_ha=rec["de_limit_ha"],
            path=(res.get("placement") or {}).get("path"), ok=rec["ok"],
            why=rec["why"])
    failed = sum(not r["ok"] for r in records)
    good = [r for r in records if r.get("result") is not None]

    values = {"setup_s": setup_s}
    if good:
        values["scf_s"] = window.scf_s(good)
        values["jobs_per_min"] = window.jobs_per_min(good)
    record = {
        "cell": cell.name, "chips": cell.chips, "jobs": records,
        "trace": traced, "trace_raw": raw, "trace_job": trace_job,
        "trace_steps": steps, "memory_peak_bytes": memory_peak,
        "window_compiles": in_window["compiles"], "config": config,
        "deck0": decks.job_deck(config, 0, block),
        "device_kind": devices[0].device_kind,
    }
    metrics = {}
    if trace:
        mdir = os.path.join(cell.bench_dir, "layer_metrics")
        for entry, spec in cell.layer_metrics:
            v = sources.read_metric(spec, mdir, entry["name"], record)
            if v is not None:
                metrics[entry["name"]] = {"value": float(v),
                                          "unit": entry["unit"]}
        if record.get("notes"):
            say(event="notes", **record["notes"])
    else:
        for entry in cell.end_to_end:
            if entry["name"] in values:
                metrics[entry["name"]] = {"value": float(values[entry["name"]]),
                                          "unit": entry["unit"]}
    say(event="window", jobs=len(records), failed=failed,
        window_compiles=in_window["compiles"], cache_hits=in_window["cache_hits"],
        cache_misses=in_window["cache_misses"], **{k: v for k, v in values.items()})
    return {"correct": bool(records) and failed == 0,
            "attempted": len(records), "failed": failed, "metrics": metrics,
            "memory_peak_bytes": memory_peak, "trace": traced}


def main(argv=None) -> int:
    args = parse(argv)
    try:
        import jax

        from benchmark.harness import loader
        from sirius_tpu import runtime
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    try:
        bench = loader.load_benchmark(ROOT)
        cell = loader.load_cell(ROOT, args.workload, bench)
    except loader.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", max(cell.chips, 1))
        cache = {"dir": None, "from_env": False}
    else:
        try:
            runtime.select_platform("tpu")
        except Exception as e:  # no chip: never a CPU fallback
            print(f"benchmark: no TPU: {e}", file=sys.stderr)
            return 3
        cache = runtime.enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        print(f"benchmark: no TPU: JAX reports {platform!r}", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"benchmark: cell {cell.name} needs {cell.chips} chip(s), JAX "
              f"reports {len(devices)}", file=sys.stderr)
        return 3
    devices = devices[:cell.chips]
    where = {"platform": platform, "device_kind": devices[0].device_kind,
             "count": len(devices)}

    def say(**kw):
        print(json.dumps({**where, **kw}, default=float), flush=True)

    if args.control_precision:
        force_matmul_precision(args.control_precision)
    say(event="start", workload=cell.name, seed=args.seed, seconds=seconds,
        trace=args.trace, rehearse=args.rehearse,
        control_precision=args.control_precision, compile_cache=cache,
        import_s=time.time() - T_PROCESS)
    workdir = os.path.join(ROOT, ".bench_work", cell.name)
    out = run_cell(cell, devices, platform, args.seed, seconds,
                   bool(args.trace), "rehearse" if args.rehearse else "deck",
                   say, workdir, warmup=not args.control_precision)
    shutil.rmtree(workdir, ignore_errors=True)
    with contextlib.suppress(OSError):  # leave no empty directory behind
        os.rmdir(os.path.dirname(workdir))

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"] and not args.rehearse,
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": out["metrics"], "device": device}
    if args.trace and out["trace"]:
        device["busy_s"] = out["trace"]["busy_s"]
        device["window_s"] = out["trace"]["window_s"]
        line["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                             "idle_gaps": []}
    print(json.dumps(line, default=float), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
