"""Benchmark entry point.

    python -m benchmarks.run [--full] [--only name,...]      # figure lanes
    python -m benchmarks.run --list                          # what exists
    python -m benchmarks.run --exp smoke --override steps=30 # any spec
    python -m benchmarks.run --exp smoke \
        --runners stepwise,fused,netsim,protocol
    python -m benchmarks.run --exp smoke --store             # sweep cache

Figure lanes run one experiment per paper figure/claim (reduced sizes by
default; --full runs paper-scale step counts). ``--exp`` runs a ``repro.exp`` preset
(with ``--override key=val`` field overrides) through one or more runners and
writes each RunResult verbatim. Every result JSON carries a ``provenance``
block (spec hash, git sha, jax version, device). ``--store`` additionally
appends the results to the spec-hash-keyed store (``benchmarks/store.py``):
identical (spec_hash, runner, git_sha) entries dedupe, metric drift vs the
stored run is diffed and printed.
"""
from __future__ import annotations

import argparse
import json
import os
import time

EXPERIMENTS = [
    ("convergence", "exp_convergence"),
    ("byz_workers", "exp_byz_workers"),
    ("byz_servers", "exp_byz_servers"),
    ("variance_bound", "exp_variance_bound"),
    ("contraction", "exp_contraction"),
    ("t_sensitivity", "exp_t_sensitivity"),
    ("filters", "exp_filters"),
    ("messages", "exp_messages"),
    ("netsim", "exp_netsim"),
    ("agg", "exp_agg_backends"),
    ("throughput", "exp_throughput"),
    ("serve", "exp_serve"),
    ("elastic", "exp_elastic"),
    ("analyze", "exp_analyze"),
]


def _lane_provenance(name: str, full: bool) -> dict:
    """Provenance for a figure lane: the 'spec' is the lane's (name, scale)
    pair — hashed the same way Experiment hashes its dict."""
    import hashlib

    import repro.exp as exp
    blob = json.dumps({"lane": name, "full": full}, sort_keys=True)
    return exp.provenance(hashlib.sha256(blob.encode()).hexdigest()[:16])


def list_everything() -> str:
    import repro.exp as exp
    lines = ["figure lanes (--only name,...):"]
    for name, mod in EXPERIMENTS:
        lines.append(f"  {name:15s} -> benchmarks/{mod}.py")
    lines.append("\nexperiment presets (--exp NAME, override with "
                 "--override key=val):\n")
    lines.append(exp.markdown_table())
    return "\n".join(lines)


def run_preset(args) -> None:
    import repro.exp as exp
    from benchmarks.common import parse_overrides
    overrides = parse_overrides(args.override)
    runners = (args.runners.split(",") if args.runners
               else [exp.get(args.exp, **overrides).runner])
    for runner in runners:
        res = exp.run(args.exp, **{**overrides, "runner": runner})
        print(res.summary())
        path = exp.write_result(res, out_dir=args.out)
        print(f"  -> {path}")
        if args.store:
            from benchmarks import store
            status, drift = store.store(res.to_dict())
            print(f"  store[{res.experiment.spec_hash}/{runner}]: {status}")
            for line in drift:
                print(f"    drift vs stored: {line}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale step counts (slow)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="results/benchmarks")
    ap.add_argument("--list", action="store_true",
                    help="print figure lanes + registered experiment presets")
    ap.add_argument("--exp", default=None, metavar="PRESET",
                    help="run one repro.exp preset instead of figure lanes")
    ap.add_argument("--override", action="append", default=[],
                    metavar="KEY=VAL",
                    help="Experiment field override (repeatable)")
    ap.add_argument("--runners", default=None,
                    help="comma list for --exp (e.g. stepwise,fused,netsim,"
                    "protocol); default: the preset's declared runner")
    ap.add_argument("--store", action="store_true",
                    help="with --exp: append each RunResult to "
                    "results/store.jsonl keyed on provenance.spec_hash, "
                    "deduping identical (spec_hash, runner, git_sha) entries "
                    "and printing a diff when metrics drift")
    ap.add_argument("--compare", default=None, metavar="BASELINE_JSON",
                    help="after the throughput experiment, fail (exit 1) on "
                    "a fused steps/sec regression beyond --compare-tol vs "
                    "this baseline file")
    ap.add_argument("--compare-tol", type=float, default=0.25,
                    help="relative regression tolerance for --compare "
                    "(default 0.25)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.list:
        print(list_everything())
        return
    if args.exp:
        os.makedirs(args.out, exist_ok=True)
        run_preset(args)
        return

    only = set(args.only.split(",")) if args.only else None
    os.makedirs(args.out, exist_ok=True)

    baseline = None
    if args.compare:
        # load before running: the run overwrites results/benchmarks/*.json
        with open(args.compare) as f:
            baseline = json.load(f)

    import importlib
    t00 = time.time()
    results = {}
    for name, mod_name in EXPERIMENTS:
        if only and name not in only:
            continue
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        t0 = time.time()
        res = mod.run(quick=not args.full)
        results[name] = res
        print(mod.summarize(res))
        print(f"  ({time.time()-t0:.1f}s)\n")
        res["provenance"] = _lane_provenance(name, args.full)
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump(res, f, indent=1, default=float)

    print(f"\ntotal {time.time()-t00:.1f}s")

    if baseline is not None:
        if "throughput" not in results:
            print("[compare] --compare given but the throughput experiment "
                  "did not run (add --only throughput or drop --only)")
            raise SystemExit(2)
        from benchmarks.exp_throughput import compare
        problems = compare(results["throughput"], baseline,
                           tol=args.compare_tol)
        if problems:
            print("[compare] throughput REGRESSION vs "
                  f"{args.compare}:")
            for p in problems:
                print(f"  {p}")
            raise SystemExit(1)
        print(f"[compare] fused throughput within {100*args.compare_tol:.0f}%"
              f" of {args.compare} — OK")


if __name__ == "__main__":
    main()
