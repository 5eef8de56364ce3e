"""Record the small profiler trace that the reducer's tests read.

    python bench/record_trace_fixture.py --out tests/bench/data

Runs, on one TPU chip, a few dispatches of a tiny jitted step that holds the
flash-attention kernels (forward and backward) and a matmul, under the
harness's own spans (``bench/dispatch_epoch``, ``bench/wait``), with a host
pause between dispatches so that the trace has an idle gap. Writes the
``.xplane.pb`` as ``<out>/fixture.xplane.pb`` and prints the trace's planes,
lines and most frequent event names, so that a reader can see how the device
and host timelines are laid out. Fails without a TPU.
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_trace_fixture: no TPU", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData

    from repro.kernels.flash_attention.ops import flash_attention

    def step(q, k, v, w):
        def loss(q, k, v):
            o = flash_attention(q, k, v, q_block=128, kv_block=128)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return g, (w @ w).sum()

    step = jax.jit(step)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 256, 4, 128), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, 256, 2, 128), jnp.bfloat16)
            for kk in ks[1:3])
    w = jax.random.normal(ks[3], (1024, 1024), jnp.bfloat16)
    jax.block_until_ready(step(q, k, v, w))

    tmp = tempfile.mkdtemp(prefix="fixture-", dir=os.environ.get("TMPDIR"))
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/dispatch_epoch"):
            out = step(q, k, v, w)
        with jax.profiler.TraceAnnotation("bench/wait"):
            jax.block_until_ready(out)
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    os.makedirs(args.out, exist_ok=True)
    dest = os.path.join(args.out, "fixture.xplane.pb")
    shutil.copyfile(path, dest)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {dest} ({os.path.getsize(dest)} bytes)")

    for plane in ProfileData.from_file(dest).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for name, n in names.most_common(12):
                e = next(x for x in evs if x.name == name)
                stats = {k: (v if len(str(v)) < 60 else str(v)[:60])
                         for k, v in e.stats}
                print(f"    {n:4d} x {name!r} start {e.start_ns:.0f} "
                      f"dur {e.duration_ns:.0f} stats {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
