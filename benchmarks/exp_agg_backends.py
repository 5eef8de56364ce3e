"""Aggregator backend timing: jnp reference vs Pallas kernels, per rule.

Times every registered rule on each backend it declares and writes
``results/benchmarks/agg_backends.json``. Off-TPU the Pallas kernels run in
interpret mode — correct but slow, so those timings measure the *fallback*,
not the kernel (flagged ``interpret: true`` in the output). Run via
``python -m benchmarks.run --only agg`` or ``make agg-bench``. A backend
that fails is recorded in the JSON and then fails the run.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

import repro.agg as agg

OUT_PATH = os.path.join("results", "benchmarks", "agg_backends.json")


def _pick_f(name: str, n: int) -> int:
    """Largest declared f the rule's breakdown admits at this n (>= 1)."""
    k, c = agg.get(name).requires
    f = (n - c) // k if k else n - 1
    return max(min(f, n - 1, 2), 1)


def _time_call(fn, x, iters: int) -> float:
    fn(x).block_until_ready()          # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e3


def run(quick: bool = True):
    n, d = (13, 1024) if quick else (15, 16384)
    iters = 3 if quick else 10
    interpreted = jax.default_backend() != "tpu"
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)
    results = {"n": n, "d": d, "platform": jax.default_backend(), "rules": {}}
    for name in agg.names():
        spec = agg.get(name)
        f = _pick_f(name, n)
        entry = {"f": f, "breakdown": spec.breakdown, "backends": {}}
        for backend in spec.backends:
            def call(x, _b=backend):
                return spec(x, f, backend=_b)
            try:
                ms = _time_call(jax.jit(call), x, iters)
            except Exception as e:  # noqa: BLE001 - recorded, raised below
                entry["backends"][backend] = {"error": str(e)[:200]}
                continue
            entry["backends"][backend] = {
                "ms": ms, "interpret": backend == "pallas" and interpreted}
        results["rules"][name] = entry
    from repro.exp import provenance
    results["provenance"] = provenance()
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as fh:
        json.dump(results, fh, indent=1, default=float)
    results["out"] = OUT_PATH
    errors = [f"{name}/{backend}: {r['error']}"
              for name, entry in results["rules"].items()
              for backend, r in entry["backends"].items() if "error" in r]
    if errors:
        raise RuntimeError(f"{len(errors)} backend(s) failed (all recorded "
                           f"in {OUT_PATH}):\n  " + "\n  ".join(errors))
    return results


def summarize(res: dict) -> str:
    lines = [f"[agg backends] per-rule timings, [n={res['n']}, d={res['d']}] "
             f"on {res['platform']} -> {res.get('out', OUT_PATH)}:"]
    for name, entry in res["rules"].items():
        cells = []
        for backend, r in entry["backends"].items():
            if "error" in r:
                cells.append(f"{backend}: ERR")
            else:
                tag = " (interpret)" if r.get("interpret") else ""
                cells.append(f"{backend}: {r['ms']:8.2f} ms{tag}")
        lines.append(f"  {name:14s} f={entry['f']}  " + "  ".join(cells))
    if res["platform"] != "tpu":
        lines.append("  note: off-TPU the pallas column is interpret-mode "
                     "(fallback correctness path, not kernel speed)")
    return "\n".join(lines)


if __name__ == "__main__":
    from .common import claim_main
    claim_main(run, summarize, description=__doc__)
