"""Run a tiny cell over four virtual CPU devices (one ByzSGD group per
device, as the four-chip cell lays them out) and print its result line.
Started by test_bench_mesh.py with the device count forced before JAX is
imported."""
import json
import sys
import time

import _bench_tiny
import jax

import run as bench_run

if __name__ == "__main__":
    cell = _bench_tiny.cell(sys.argv[1], chips=4)
    res = bench_run.run_cell(cell, 2 ** 35 + 1, 0.3, False,
                             jax.devices()[:4], time.time())
    print(json.dumps(res))
