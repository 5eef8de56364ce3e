"""Record the small profiler trace that the stage readers' tests read.

    python bench/record_stage_fixture.py --out tests/bench/data

Runs, on one TPU chip, two epochs of a tiny ``ProtocolEngine``
(``tiny_engine``: two stacked dense layers, G=4 groups, T=2 steps, the
Median pull, MDA, the DMC gather at each epoch's end) through the harness's
own loop (``bench/run.py`` ``drive``: the spans ``bench/dispatch_epoch`` and
``bench/wait`` around the program's ``repro/run_epoch``), compiled before
the trace starts. Writes
``<out>/stages.xplane.pb`` and ``<out>/stages.by_name.json``, the stage that
the compiled epoch's text gives each instruction the trace ran (the route
the harness's readers take), and prints each stage's device time per step
by that route and by the trace's own ``tf_op``. Fails without a TPU, or
when the trace holds more than 300 KB.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), HERE]

LIMIT_BYTES = 300_000


def tiny_engine():
    """A ``ProtocolEngine`` over a stack of two dense layers, one leaf,
    G=4, T=2, the Median pull, MDA, SGD. The leaf ``[G, 2, D, D]`` is over
    ``chunk_bytes``, so the pull, the aggregation and the gather stream it a
    layer at a time, as the benchmark's model does, and the update runs
    apart from the aggregation. Returns the engine and its batch stream."""
    import jax
    import jax.numpy as jnp

    from repro.core import protocol
    from repro.data.pipeline import DeviceBatchStream, MixtureSpec
    from repro.optim.schedules import inverse_linear

    mix = MixtureSpec(n_classes=8, dim=128, sep=2.5)

    def init(key):
        return {"w": jax.random.normal(key, (2, mix.dim, mix.dim)) / 12.0}

    def loss(params, batch):
        x, y = batch
        x = jnp.tanh(x @ params["w"][0]) @ params["w"][1]
        logp = jax.nn.log_softmax(x[:, :mix.n_classes])
        return -jnp.mean(logp[jnp.arange(y.shape[0]), y])

    pcfg = dataclasses.replace(
        protocol.ProtocolConfig.derive(4, T=2, f_workers=1, f_servers=0,
                                       q_workers=3, q_servers=4),
        chunk_bytes=2 ** 16)
    eng = protocol.ProtocolEngine(protocol.ProblemBundle(init=init, loss=loss),
                                  pcfg, inverse_linear(0.05, 0.01))
    return eng, DeviceBatchStream(0, mix, 4, 32)


def without_plane(space: bytes, name: str) -> bytes:
    """An XSpace's bytes less its plane ``name`` (the HLO protos here, which
    no reader reads). Every XSpace field is length-delimited; planes are
    field 1, and an XPlane's name is its field 2."""
    from benchlib.stages import _fields, _varint
    out, i = [], 0
    while i < len(space):
        start = i
        key, i = _varint(space, i)
        size, i = _varint(space, i)
        body, i = space[i:i + size], i + size
        if not (key >> 3 == 1 and dict(_fields(body)).get(2) == name.encode()):
            out.append(space[start:i])
    return b"".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_stage_fixture: no TPU", file=sys.stderr)
        return 1
    # shorter source paths in the ops' metadata, so a smaller file
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      r".*/(src|bench)/")
    from benchlib import stages
    from run import drive

    T = 2
    eng, stream = tiny_engine()
    pool = [stream.next(T) for _ in range(4)]
    state = eng.init_state(jax.random.PRNGKey(0))
    marker = jax.jit(lambda t: t + 1)
    for batches in pool[:2]:          # compile before the trace
        state, _ = eng.run_epoch(state, batches)
    jax.block_until_ready((state, marker(state.t)))

    tmp = tempfile.mkdtemp(prefix="stages-", dir=os.environ.get("TMPDIR"))
    options = jax.profiler.ProfileOptions()    # no Python function events
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    state, n, _ = drive(jax, eng, marker, state, pool, lambda n, s: n >= 2)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    os.makedirs(args.out, exist_ok=True)
    dest = os.path.join(args.out, "stages.xplane.pb")
    with open(path, "rb") as src, open(dest, "wb") as out:
        out.write(without_plane(src.read(), "/host:metadata"))
    shutil.rmtree(tmp, ignore_errors=True)
    size = os.path.getsize(dest)
    print(f"wrote {dest} ({size} bytes)")

    tr, by_text = stages.load(dest)
    ran = {op.name for ops in tr.ops.values() for op in ops}
    by_name = {k: v for k, v in stages.dispatched_stages().items()
               if k in ran}
    with open(os.path.join(args.out, "stages.by_name.json"), "w") as fh:
        json.dump(by_name, fh, indent=0, sort_keys=True)
    steps = n * T
    for route, stage_by in (("trace tf_op", by_text),
                            ("compiled text", by_name)):
        st = stages.stage_times(tr, [0], steps, stage_by)
        total = sum(st.stage_s.values()) + st.unattributed_s
        print(f"{route}: " + ", ".join(
            f"{k} {1e3 * v:.4f} ms" for k, v in st.stage_s.items())
              + f"; unattributed {1e3 * st.unattributed_s:.4f} ms; stages"
              f" + unattributed {1e3 * total:.4f} ms against busy "
              f"{1e3 * st.busy_s:.4f} ms a step")
    gaps = {}
    for name, a, b in tr.idle_gaps(0):
        gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9
    print(f"idle gaps by span: {gaps}")
    if size > LIMIT_BYTES:
        print(f"the trace holds {size} bytes, over {LIMIT_BYTES}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
