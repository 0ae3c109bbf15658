#!/usr/bin/env python3
"""Is a context of this tree, array by array, the context of another tree?

    python tools/context_digest.py --against <other tree> [--block deck|rehearse]
                                   [configuration ...]

builds, in one child process a tree (each imports its own ``sirius_tpu``
and ``benchmark``), the contexts of geometries 0, 1, 0, 2 of each benchmark
configuration through ``serve/scheduler.build_job_context`` (the second 0 is
a build that finds the process's tables, where the tree keeps any) and takes
a sha1 of every array and number under the context, by path. Exit code 0
where every path both trees' contexts have has one digest in both, in every
build, 1 with the paths that differ otherwise; a path only one tree's
context carries (a table a later PR added) is listed. The other tree is a checkout of any commit
that has ``benchmark/configs`` (``git archive <commit> | tar -x -C <dir>``):
PR 47 used it to hold the memoised build to its parent's, cold and hit.

    python tools/context_digest.py --digest <out.json> ...   (the child)

CPU only; the 54-atom configuration takes ~15 s a build and ~1 GB.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

GEOMETRIES = (0, 1, 0, 2)


def digests(obj, path="ctx", out=None) -> dict:
    """sha1 (12 hex digits) of every array under ``obj`` by path, and the
    repr of every number. The deck and the species' own arrays are inputs,
    not built, and a counter of reuse differs by design."""
    import numpy as np

    out = {} if out is None else out
    if isinstance(obj, np.ndarray):
        h = hashlib.sha1(f"{obj.dtype.str}{obj.shape}".encode())
        h.update(np.ascontiguousarray(obj).tobytes())
        out[path] = h.hexdigest()[:12]
    elif dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        if type(obj).__name__ in ("Config", "AtomType"):
            return out
        for f in dataclasses.fields(obj):
            if f.name != "tables_reused":
                digests(getattr(obj, f.name), f"{path}.{f.name}", out)
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            digests(v, f"{path}[{i}]", out)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[path] = repr(obj)
    return out


def _digest_tree(out_file: str, block: str, names: list) -> None:
    """The child: run from the root of the tree it measures."""
    sys.path.insert(0, os.getcwd())
    from benchmark.harness import decks
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.serve.scheduler import build_job_context

    res = {}
    for name in names:
        with open(f"benchmark/configs/{name}/config.json") as f:
            config = json.load(f)
        for n, g in enumerate(GEOMETRIES):
            cfg = load_config(decks.job_deck(config, g, block))
            t0 = time.perf_counter()
            ctx = build_job_context(cfg, ".")
            dt = time.perf_counter() - t0
            res[f"{name}:build{n}:geometry{g}"] = digests(ctx)
            print(f"{os.getcwd()} {name} geometry {g}: {dt:.3f} s, "
                  f"reused {getattr(ctx, 'tables_reused', None)}",
                  file=sys.stderr, flush=True)
    with open(out_file, "w") as f:
        json.dump(res, f)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", help="root of the other tree")
    ap.add_argument("--digest", help=argparse.SUPPRESS)
    ap.add_argument("--block", default="deck", choices=("deck", "rehearse"))
    ap.add_argument("configs", nargs="*")
    args = ap.parse_args()
    if args.digest:
        _digest_tree(args.digest, args.block, args.configs)
        return 0
    if not args.against:
        ap.error("--against <other tree> is required")
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = args.configs or sorted(os.listdir(
        os.path.join(here, "benchmark", "configs")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    found = []
    with tempfile.TemporaryDirectory() as tmp:
        for i, tree in enumerate((here, os.path.abspath(args.against))):
            out = os.path.join(tmp, f"{i}.json")
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--digest", out,
                 "--block", args.block, *names], cwd=tree, env=env, check=True)
            with open(out) as f:
                found.append(json.load(f))
    mine, other = found
    differ, lone, paths = [], set(), 0
    for build in sorted(set(mine) | set(other)):
        a, b = mine.get(build, {}), other.get(build, {})
        paths += len(a)
        differ += [f"{build} {p}" for p in sorted(set(a) & set(b))
                   if a[p] != b[p]]
        # what only one tree's context carries is named, not held against it
        lone |= set(a) ^ set(b)
    print(json.dumps({"configs": names, "block": args.block,
                      "builds": len(mine), "paths": paths,
                      "differ": len(differ), "in_one_tree_only": sorted(lone)}))
    for line in differ[:40]:
        print("DIFFERS", line)
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
