"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``, from process start): import, compile cache,
the program built as ``repro.exp`` builds a protocol run, weights and a pool
of token rows made on the device from the seed, and one T-step epoch through
``ProtocolEngine.run_epoch``, which compiles (or loads) the cell's only
program and is the epoch the check follows. Its G replicas are copied to
the host for the check; that copy is not set-up and is left out of
``setup_s``.

``--trace 0``: the window dispatches whole epochs, cycling through the pool,
until ``--seconds`` have passed, and ends when the device is done:
``tokens_per_s`` is G x rows x tokens per row x steps over the window's
seconds. ``--trace 1``: a run of its own that profiles a few epochs and
reduces the trace to the cell's per-layer metrics and a breakdown.

Then the program's state is freed and the plain reference follows the
checked epoch (``benchlib.check``); the numbers compared are printed beside
their limits as the last lines on standard error and under ``check`` in the
result. The last line of standard output is the result, one JSON object.

Fails, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, when anything compiles inside the window, and outside a
checkout of the repository.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Seconds and counts of JAX's trace, lower and compile events, from its
    own monitoring (the listener stays for the process's life)."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = dict.fromkeys(self.EVENTS, 0.0)
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self.seconds:
            self.seconds[event] += secs
            self.count += 1
            self.last = event

    def summary(self) -> str:
        return ", ".join(f"{k.rsplit('/', 1)[1]} {v:.2f}s"
                         for k, v in self.seconds.items())


class TracedRun:
    """What a per-layer metric reader is given."""

    def __init__(self, trace, chips, config, peaks, seq, tokens, steps):
        self.trace, self.chips, self.config = trace, chips, config
        self.peaks, self.seq, self.tokens, self.steps = (peaks, seq, tokens,
                                                         steps)


def drive(jax, engine, marker, state, pool, stop):
    """Dispatch epochs from ``pool[2]`` on (set-up ran 0 and 1), cycling,
    with at most two in flight (``marker`` of the step counter tells when
    one is done), until ``stop(epochs, seconds)``; end on the device
    finishing."""
    from jax.profiler import TraceAnnotation
    prev, n = None, 0
    t0 = time.perf_counter()
    while True:
        with TraceAnnotation("bench/dispatch_epoch"):
            state, _ = engine.run_epoch(state, pool[(2 + n) % len(pool)])
            mark = marker(state.t)
        n += 1
        with TraceAnnotation("bench/wait"):
            if prev is not None:
                prev.block_until_ready()
        prev = mark
        if stop(n, time.perf_counter() - t0):
            break
    with TraceAnnotation("bench/wait"):
        jax.block_until_ready(state)
    return state, n, time.perf_counter() - t0


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, prog=None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object.
    ``prog`` is the program to drive (``benchlib.program.build``), built
    here when not given."""
    import jax
    import numpy as np

    from benchlib import check, program, spec, traffic
    from benchlib import trace as trace_mod
    from benchlib.peaks import peaks
    from repro.launch.mesh import use_mesh

    clock = CompileClock(jax)
    settings, conf, tr = cell.settings, cell.config, cell.traffic
    prog = prog or program.build(cell, devices)
    T, G = prog.pcfg.T, prog.pcfg.n_groups
    k_model, k_run, k_rows = traffic.run_keys(seed)
    state = prog.make_state(k_model, k_run)
    pool = traffic.make_pool(k_rows, tr, vocab=conf["vocab_size"], T=T,
                             groups=G, sharding=prog.replicated)
    with use_mesh(prog.mesh):
        t0 = time.time()
        state, _ = prog.engine.run_epoch(state, pool[0])
        jax.block_until_ready(state)
        log(f"checked epoch (compile or cache load, then run) "
            f"{time.time() - t0:.2f}s")
        t0 = time.time()
        replicas = program.host_replicas(state.params)
        copy_s = time.time() - t0
        # a second epoch, from the state an epoch returns: its layout can
        # differ from the initial state's, and the window's calls take it
        t0 = time.time()
        marker = jax.jit(lambda t: t + 1)
        state, _ = prog.engine.run_epoch(state, pool[1])
        jax.block_until_ready((state, marker(state.t)))
        log(f"second epoch {time.time() - t0:.2f}s")
        setup_s = time.time() - t_start - copy_s
        log(f"set-up {setup_s:.2f}s ({clock.summary()}); copy of the checked "
            f"epoch's replicas {copy_s:.2f}s, not counted")
        before = clock.count
        tmp = None
        if trace:
            tmp = tempfile.mkdtemp(prefix="bench-trace-",
                                   dir=os.environ.get("TMPDIR"))
            jax.profiler.start_trace(tmp)
            epochs = int(settings["trace_epochs"])
            state, n, window_s = drive(jax, prog.engine, marker, state, pool,
                                       lambda n, s: n >= epochs)
            jax.profiler.stop_trace()
        else:
            state, n, window_s = drive(jax, prog.engine, marker, state, pool,
                                       lambda n, s: s >= seconds)
    if clock.count != before:
        raise RuntimeError(f"{clock.count - before} compile events inside "
                           f"the window, the last {clock.last}")
    steps = n * T
    tokens = steps * G * int(tr["rows_per_group"]) * int(tr["seq"])
    log(f"window: {n} epochs, {steps} steps, {tokens} tokens in "
        f"{window_s:.3f}s")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    result = {}
    if trace:
        path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp)
                    for f in fs if f.endswith(".xplane.pb"))
        tr_ = trace_mod.load(path)
        shutil.rmtree(tmp, ignore_errors=True)
        chips = [d.id for d in devices]
        run = TracedRun(tr_, chips, conf, peaks(dev.device_kind),
                        int(tr["seq"]), tokens, steps)
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = float(np.mean([tr_.busy_s(c) for c in chips]))
        device["window_s"] = tr_.window_s
        result["breakdown"] = {"device_ops": tr_.top_ops(10),
                               "idle_gaps": tr_.top_gaps(chips[0], 10)}
    else:
        e2e = {"tokens_per_s": (tokens / window_s, "tokens/s"),
               "setup_s": (setup_s, "s")}
        metrics = {m["name"]: {"value": e2e[m["name"]][0],
                               "unit": e2e[m["name"]][1]}
                   for m in cell.end_to_end}

    # the reference: after the window, with the program's state freed
    del state, pool, prog
    gc.collect()
    t0 = time.time()
    limits = settings["limits"]
    p0, outcome = check.reference(cell, seed, dev)
    numbers = check.compare(replicas, outcome, p0)
    correct = check.passes(numbers, limits)
    log(f"reference {time.time() - t0:.2f}s; MDA least margin "
        f"{outcome.least_margin:.3e}; worst leaves {numbers['worst']}; "
        f"left out (unmoved in the reference): {numbers['excluded']}")
    result = {"correct": bool(correct), "attempted": steps,
              "failed": 0 if correct else T, "metrics": metrics,
              "device": device, **result,
              "check": {k: {"value": numbers[k], "limit": limits[k]}
                        for k in check.NUMBERS}}
    for name, gap, diff in numbers["leaves"]:
        log(f"leaf {name}: norm gap {gap:.3e}, diff {diff:.3e}")
    for k in check.NUMBERS:
        log(f"check {k} {numbers[k]:.6e} limit {limits[k]:.6e}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (os.path.isdir(os.path.join(ROOT, "src", "repro"))
            and os.path.isfile(os.path.join(ROOT, "BENCHMARK.json"))):
        log(f"no src/repro or BENCHMARK.json under {ROOT}: run from a "
            f"checkout of the repository")
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    from benchlib import spec
    cell = spec.load_cell(args.workload)

    # the TPU runtime's logs go under TMPDIR, not to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"JAX found no TPU (platform {devices[0].platform!r})")
        return 1
    if len(devices) < cell.chips:
        log(f"{args.workload} needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    # every program in the cache, so that a warm set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
