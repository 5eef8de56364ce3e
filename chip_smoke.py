"""Smoke run of ByzSGD training on TPU chips, through ``repro.exp.run``.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: phase (b) across them,
                                      # compared with the same run on one

Phase (a) runs the ``quickstart`` preset: the fused engine with ALIE
attacking 2 of 9 workers, MDA over gradients, the Median pull and T=10.
Phase (b) trains phi4-mini-3.8b at its published widths (the
``phi4_mini_cut`` model: depth and vocabulary cut, see ``repro.exp.spec``)
through ``runner="protocol"`` with G=4 worker+server groups, T=5, for 10
steps on 2048-token rows. Weights and data are random, made from the seed.

Each phase prints its losses (or accuracies) at the first and last step, wall
and compile seconds, the mesh and the backend each aggregation primitive
resolved to. It fails (non-zero exit, no result line) if any check fails:
non-finite or non-improving metrics, no flash-attention kernel in the
protocol epoch the run dispatched, flash forward or gradients off the float32
reference attention by rel-L2 2e-2, or (``--chips 4``) a 4-chip run that
differs from the one-chip run: final parameters beyond rel-L2 1e-4 or
rel-max 1e-2 on any leaf, or the update from the initial parameters beyond
rel-L2 2e-2. Without a TPU it exits non-zero before running anything. The last
line of a passing run is one JSON object naming the device.

Everything runs in this one process: a chip belongs to one process at a
time. Compiled programs go to JAX's persistent cache
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache`` here).
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self.EVENTS:
            self.total += secs


def _phase_spec(exp):
    """Phase (b): G=4 co-located groups on the dense architecture at its
    published widths, f_w=1 as in the lm presets. At d_model 3072 plain SGD
    oscillates from lr 2e-3 up (the lm presets' 2e-2 diverges), so the
    rate is 5e-4."""
    return exp.Experiment(
        name="chip/phi4_mini_cut", runner="protocol", n_workers=4,
        f_workers=1, n_servers=4, f_servers=0, T=5, steps=10, batch=1,
        model="phi4_mini_cut", data="tokens_v16k_s2k", schedule="constant",
        lr0=5e-4, metrics_every=1, eval_n=4)


def _report(tag, res, compile_s, metric, dispatch):
    first, last = res.logs[0], res.final
    sign = -1.0 if metric == "loss" else 1.0
    vals = [sign * m["acc"] for m in res.logs] + [sign * last["acc"]]
    print(f"[{tag}] {res.experiment.name}: runner={res.experiment.runner} "
          f"mesh={res.provenance.get('mesh', 'single device, no mesh')} "
          f"{metric} {vals[0]:.4f} (step {first['step']}) -> {vals[-1]:.4f} "
          f"(final, after step {res.experiment.steps}) "
          f"wall {res.wall_s:.2f}s compile {compile_s:.2f}s")
    print(f"[{tag}] {metric} per logged step: "
          + " ".join(f"{v:.4f}" for v in vals[:-1]))
    print(f"[{tag}] agg primitive backends: "
          f"{dispatch.resolved_backends(reset=True) or 'none dispatched'}")
    if not all(math.isfinite(v) for v in vals):
        _fail(f"[{tag}] non-finite {metric}: {vals}")
    better = vals[-1] < vals[0] if metric == "loss" else vals[-1] > vals[0]
    if not better:
        _fail(f"[{tag}] no progress: {metric} {vals[0]} -> {vals[-1]}")


def _memory(jax, tag):
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[{tag}] device 0 peak memory "
              f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
              f"{stats.get('bytes_limit', float('nan')) / 2**30:.2f} GiB")


def _flash_vs_reference(jax):
    """The flash kernels, forward and backward, against the float32
    reference attention at phase (b)'s head and block widths."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    B, S, H, kvH, hd = 1, 2048, 24, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (B, S, kvH, hd), jnp.bfloat16)
            for kk in ks[1:3])
    w = jax.random.normal(ks[3], (B, S, H, hd), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, q_block=512, kv_block=1024)

    def ref(q, k, v):
        f32 = jnp.float32
        with jax.default_matmul_precision("highest"):
            return attention_ref(q.astype(f32), k.astype(f32), v.astype(f32))

    def outputs(fn):
        grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                         argnums=(0, 1, 2))(q, k, v)
        return [fn(q, k, v), *grads]

    rel = [float(np.linalg.norm(np.float32(a) - np.float32(b))
                 / np.linalg.norm(np.float32(b)))
           for a, b in zip(outputs(flash), outputs(ref))]
    print("[b] flash vs f32 reference, rel-L2: out {:.2e} dq {:.2e} "
          "dk {:.2e} dv {:.2e} (limit 2e-2)".format(*rel))
    if max(rel) >= 2e-2:
        _fail(f"flash attention disagrees with the reference: {rel}")


def _flash_check(res):
    """The protocol epoch the run dispatched must hold the flash kernels (a
    Mosaic ``tpu_custom_call``), not the blocked or naive jnp attention.
    The engine lowers that program again from the argument types it ran
    with, so its compile is served by the persistent cache."""
    t0 = time.time()
    compiled = res.engine.lower().compile()
    n = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    gib = {k: getattr(mem, f"{k}_size_in_bytes") / 2**30
           for k in ("argument", "output", "alias", "temp")}
    print(f"[b] the run's protocol epoch (compiled again in "
          f"{time.time() - t0:.2f}s): {n} tpu_custom_call; its memory per "
          f"device: peak {mem.peak_memory_in_bytes / 2**30:.2f} GiB, "
          + ", ".join(f"{k} {v:.2f} GiB" for k, v in gib.items()))
    if n == 0:
        _fail("no tpu_custom_call in the compiled protocol step: attention "
              "fell back to the jnp path")


def one_chip(jax, exp, dispatch, clock):
    c0 = clock.total
    res = exp.run("quickstart", steps=50, metrics_every=10)
    _report("a", res, clock.total - c0, "acc", dispatch)
    del res

    c0 = clock.total
    res = exp.run(_phase_spec(exp))
    _report("b", res, clock.total - c0, "loss", dispatch)
    _memory(jax, "b")
    _flash_check(res)
    del res
    _flash_vs_reference(jax)


def _host_params(jax, state):
    import numpy as np
    return [np.asarray(l, np.float32)
            for l in jax.tree.leaves(jax.device_get(state.params))]


def four_chips(jax, exp, dispatch, clock):
    import numpy as np
    from repro.exp import runners
    from repro.launch.mesh import make_protocol_mesh, use_mesh

    spec = _phase_spec(exp)
    c0 = clock.total
    res = exp.run(spec)
    _report("b4", res, clock.total - c0, "loss", dispatch)
    if res.provenance["mesh"] != {"rep": 4, "fsdp": 1, "model": 1}:
        _fail(f"expected a (rep=4, fsdp=1, model=1) mesh, got "
              f"{res.provenance['mesh']}")
    p4 = _host_params(jax, res.state)
    del res                      # chip 0 needs its whole memory for G=4
    gc.collect()

    # the same spec pinned to one chip: all four groups on device 0
    runners._protocol_mesh = lambda G: make_protocol_mesh(
        G, devices=jax.devices()[:1])
    c0 = clock.total
    res = exp.run(spec)
    _report("b1", res, clock.total - c0, "loss", dispatch)
    _memory(jax, "b1")
    p1 = _host_params(jax, res.state)
    eng = res.engine
    del res
    gc.collect()
    with use_mesh(eng.mesh):     # the initial state both runs started from
        p0 = _host_params(jax, eng.init_state(
            jax.random.PRNGKey(spec.seed)))

    # bf16 activations: reduction order differs across layouts. Gate the
    # parameters per leaf (rel-L2, rel-max), and the 10 steps' update
    # p - p0 over the whole model, so that a layout which dropped or
    # changed part of the update (a pull, a gather) cannot hide behind
    # an update that is small next to the parameters.
    def rel(d, ref):
        return float(np.linalg.norm(d)) / (float(np.linalg.norm(ref)) + 1e-12)

    par_l2 = max(rel(a - b, b) for a, b in zip(p4, p1))
    par_max = max(float(np.max(np.abs(a - b)))
                  / (float(np.max(np.abs(b))) + 1e-12)
                  for a, b in zip(p4, p1))
    u4 = np.concatenate([(a - c).ravel() for a, c in zip(p4, p0)])
    u1 = np.concatenate([(b - c).ravel() for b, c in zip(p1, p0)])
    upd = rel(u4 - u1, u1)
    upd_leaf = max(rel((a - c) - (b - c), b - c)
                   for a, b, c in zip(p4, p1, p0))
    size = rel(u1, np.concatenate([c.ravel() for c in p0]))
    print(f"[b4 vs b1] update p - p0 is {size:.3e} of the parameters "
          f"(rel-L2); final params: worst leaf rel-L2 {par_l2:.3e}, "
          f"rel-max {par_max:.3e} (limits 1e-4, 1e-2); update: rel-L2 "
          f"{upd:.3e} (limit 2e-2), worst leaf {upd_leaf:.3e}")
    if not (par_l2 < 1e-4 and par_max < 1e-2 and upd < 2e-2):
        _fail("4-chip and 1-chip runs disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a) and (b); 4: phase (b) across four "
                         "chips vs pinned to one")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        _fail(f"no src/repro next to {__file__}: run from a checkout")
    sys.path.insert(0, os.path.join(HERE, "src"))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPU chips, "
              f"JAX found {len(devices)}")

    from repro import exp
    from repro.agg import dispatch
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(f"devices: {len(devices)} x {devices[0].device_kind}")
    clock = _CompileClock(jax)
    t0 = time.time()
    (four_chips if args.chips == 4 else one_chip)(jax, exp, dispatch, clock)
    print(f"total {time.time() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
