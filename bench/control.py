"""Readings that set the limits of a cell's check; the benchmark's own runs
never run this.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --what sound
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --what control
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --what faults

- ``sound``: the cell's own runs (set-up, a short window of ``--seconds``,
  the check), one per seed, all in this process with one program. Their
  largest readings are the lower ends of the limits.
- ``control``: the same, with the program's own lower-precision path
  switched on: replicas stored in bfloat16 where the configuration states
  float32 (``param_dtype``). It has to read above the limits.
- ``faults``: the reference with a fault planted, put in the program's
  place and compared with the sound reference: the exchange left out
  (``no_exchange``) and half of each row left out (``half_batch``). A state
  left unchanged reads 1 by construction and needs no run.

Prints one JSON line per reading. Fails without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

FAULTS = ("no_exchange", "half_batch")


def fault_readings(cell, seed: int, device) -> dict:
    """Numbers of each planted fault against the sound reference."""
    import jax
    import numpy as np

    from benchlib import check
    hosts = {}
    for fault in FAULTS:                 # one outcome on the device at a time
        _, bad = check.reference(cell, seed, device, faults=(fault,))
        hosts[fault] = [[np.asarray(l) for l in jax.tree.leaves(r)]
                        for r in bad.replicas]
        del bad
    p0, sound = check.reference(cell, seed, device)
    out = {}
    for fault, host in hosts.items():
        nums = check.compare(host, sound, p0)
        out[fault] = {k: nums[k] for k in check.NUMBERS}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--what", choices=("sound", "control", "faults"),
                    required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    import jax

    import run as bench_run
    from benchlib import program, spec
    from repro.launch.compile_cache import enable_compile_cache
    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.what == "faults":
        for seed in seeds:
            print(json.dumps({"seed": seed, **fault_readings(
                cell, seed, devices[0])}), flush=True)
        return 0
    overrides = ({"param_dtype": "bfloat16"} if args.what == "control"
                 else None)
    prog = program.build(cell, devices, overrides)
    for seed in seeds:
        t0 = time.time()
        res = bench_run.run_cell(cell, seed, args.seconds, False, devices,
                                 t0, prog=prog)
        print(json.dumps({"seed": seed, "what": args.what,
                          "correct": res["correct"],
                          "metrics": res["metrics"],
                          "check": res["check"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
