"""``repro.exp.run`` — one entry point, every runner.

Dispatches an :class:`~repro.exp.spec.Experiment` to

  * ``stepwise`` — the per-step ``ByzSGDSimulator.run`` reference loop (the
    debugging/correctness oracle; host batch iterator, host metrics),
  * ``fused``    — the compiled :class:`repro.core.engine.EpochEngine` hot
    path (device batch stream, donated ``lax.scan`` epochs, one host
    transfer),
  * ``netsim``   — a trace-driven run: the named netsim scenario is simulated
    first, the realized quorums/staleness replay through ``TraceDelivery``,
    and the cluster's accounting rides along in the result,
  * ``protocol`` — the genuinely-distributed path: the same spec lowered to
    ``ProtocolConfig`` (G = n_workers = n_servers co-located groups) and run
    through :class:`repro.core.protocol.ProtocolEngine` fused epochs over a
    ('rep', 'fsdp', 'model') mesh built from the available devices (down to
    one device, where the fused runner is its oracle); the mesh shape and
    collective engine land in the result's provenance,

and returns a uniform :class:`RunResult`: strided metric ``logs``, ``final``
metrics, wall seconds, and a ``provenance`` block (spec hash + git sha +
jax/device info) that ``benchmarks/run.py`` writes verbatim into
``results/benchmarks/*.json``. The runners train the *same* experiment:
stepwise and fused are equivalence-tested (params allclose) in
``tests/test_exp.py``, and protocol against both in
``tests/test_protocol_engine.py``.
"""
from __future__ import annotations

import json
import os
import subprocess
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..agg import default_backend
from ..agg.dispatch import backend_override
from ..agg.rules import use_sort_network
from ..core.engine import EpochEngine
from ..core.simulator import coordinatewise_diameter_sum, l2_diameter
from ..data.pipeline import (DeviceBatchStream, DeviceTokenStream,
                             classification_stream)
from . import presets
from .spec import Experiment


def git_sha() -> str | None:
    """Current repo revision, or None outside a git checkout."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def provenance(spec_hash: str | None = None) -> dict[str, Any]:
    """The provenance block every result JSON carries."""
    dev = jax.devices()[0]
    return {"spec_hash": spec_hash, "git_sha": git_sha(),
            "jax_version": jax.__version__, "device": dev.platform,
            "device_kind": getattr(dev, "device_kind", None),
            "agg_backend": default_backend()}


@dataclass
class RunResult:
    """Uniform result of :func:`run` across the three runners.

    ``logs``/``final``/``wall_s``/``provenance``/``netsim`` serialize via
    :meth:`to_dict`; ``state`` (the final ``SimState``), ``buffers`` (the
    dense per-step device metric buffers, host numpy) and ``engine`` (the
    protocol runner's epoch engine, for lowering its program) are runtime
    attachments for tests and notebook analysis, never written to JSON.
    """
    experiment: Experiment
    logs: list[dict]
    final: dict
    wall_s: float
    provenance: dict
    netsim: dict | None = None
    state: Any = field(default=None, repr=False, compare=False)
    buffers: dict | None = field(default=None, repr=False, compare=False)
    engine: Any = field(default=None, repr=False, compare=False)

    def to_dict(self) -> dict[str, Any]:
        out = {"experiment": self.experiment.to_dict(),
               "logs": self.logs, "final": self.final, "wall_s": self.wall_s,
               "provenance": self.provenance}
        if self.netsim is not None:
            out["netsim"] = self.netsim
        return out

    def summary(self) -> str:
        e = self.experiment
        bits = [f"[{e.name}] runner={e.runner}", f"steps={e.steps}",
                f"final acc {self.final.get('acc', float('nan')):.3f}",
                f"wall {self.wall_s:.1f}s", f"spec {e.spec_hash}"]
        if self.netsim is not None:
            bits.append(f"virtual {self.netsim['virtual_ms']:.0f}ms "
                        f"(shortfalls {self.netsim['shortfalls']})")
        return "  ".join(bits)


def run(experiment: Experiment | str, **overrides) -> RunResult:
    """Run an experiment (or a preset name) through its declared runner."""
    if isinstance(experiment, str):
        e = presets.get(experiment, **overrides)
    else:
        e = experiment.replace(**overrides) if overrides else experiment
    with backend_override(e.agg_backend), use_sort_network(e.sort_network):
        # delivery is orthogonal to the runner: a "trace" experiment can
        # train stepwise or fused; runner="netsim" is fused + trace with
        # the cluster accounting attached (delivery normalized at
        # construction).
        delivery, info = (_trace_delivery(e) if e.delivery == "trace"
                          else (None, None))
        if e.runner == "stepwise":
            return _run_stepwise(e, delivery, info)
        if e.runner == "protocol":
            return _run_protocol(e, delivery, info)
        if e.runner == "elastic":
            return _run_elastic(e)
        return _run_fused(e, delivery, info)


# ---------------------------------------------------------------------------
# runner implementations
# ---------------------------------------------------------------------------


def _trace_delivery(e: Experiment):
    """Simulate the named scenario; return (TraceDelivery, netsim dict)."""
    from ..netsim import ClusterSim
    sc = e.to_scenario()
    trace = ClusterSim(sc).run()
    step_ms = np.diff(np.maximum.accumulate(trace.step_done_ms), prepend=0.0)
    info = {
        "scenario": sc.name, "steps": int(sc.steps),
        "virtual_ms": float(trace.step_done_ms[-1]),
        "mean_step_ms": float(step_ms.mean()),
        "p95_step_ms": float(np.percentile(step_ms, 95)),
        "mean_pull_staleness_ms": float(trace.pull_stale.mean()),
        "events": int(trace.events), "shortfalls": int(trace.shortfalls),
        "totals": trace.ledger.totals(),
        "summary": trace.ledger.summary(sc),
    }
    return trace.to_delivery(), info


def _final_metrics(e: Experiment, state, acc, eval_set, mbuf=None) -> dict:
    p0 = jax.tree.map(lambda l: l[0], state.params)
    cfg = e.to_config()
    final = {"acc": float(acc(p0, *eval_set))}
    if e.track_delta:
        final["delta"] = float(coordinatewise_diameter_sum(state.params,
                                                           cfg.h_servers))
        final["l2_diam"] = float(l2_diameter(state.params, cfg.h_servers))
    if mbuf is not None and "rejects" in mbuf:
        final["rejects"] = int(np.asarray(mbuf["rejects"][-1]).sum())
    return final


def _run_stepwise(e: Experiment, delivery=None, netsim=None) -> RunResult:
    sim = e.build_sim(delivery)
    cfg = sim.cfg
    _, _, acc = e.build_problem()
    state = sim.init_state(jax.random.PRNGKey(e.seed))
    stream, eval_fn = classification_stream(e.seed, e.mixture, cfg.n_workers,
                                            e.batch, e.steps)
    ex, ey = eval_fn(e.eval_n)

    def metrics(s):
        m = {"acc": float(acc(jax.tree.map(lambda l: l[0], s.params), ex, ey))}
        if e.track_delta:
            m["delta"] = float(coordinatewise_diameter_sum(s.params,
                                                           cfg.h_servers))
            m["l2_diam"] = float(l2_diameter(s.params, cfg.h_servers))
        return m

    t0 = time.time()
    state, logs = sim.run(state, stream, metrics_fn=metrics,
                          metrics_every=e.metrics_every)
    wall = time.time() - t0
    final = _final_metrics(e, state, acc, (ex, ey))
    return RunResult(e, logs, final, wall, provenance(e.spec_hash),
                     netsim=netsim, state=state)


def _run_fused(e: Experiment, delivery=None, netsim=None) -> RunResult:
    sim = e.build_sim(delivery)
    cfg = sim.cfg
    _, _, acc = e.build_problem()
    state = sim.init_state(jax.random.PRNGKey(e.seed))
    stream = DeviceBatchStream(e.seed, e.mixture, cfg.n_workers, e.batch)
    ex, ey = stream.eval_set(e.eval_n)
    eng = EpochEngine(sim, acc_fn=acc, eval_set=(ex, ey),
                      track_delta=e.track_delta,
                      metrics_every=e.metrics_every)
    t0 = time.time()
    state, mbuf = eng.run(state, stream=stream, steps=e.steps,
                          epoch_steps=e.epoch_steps)
    wall = time.time() - t0

    logs = []
    for i in range(0, e.steps, e.metrics_every):
        m = {"step": i, "acc": float(mbuf["acc"][i])}
        if e.track_delta:
            m["delta"] = float(mbuf["delta"][i])
            m["l2_diam"] = float(mbuf["l2_diam"][i])
        if "rejects" in mbuf:
            m["rejects"] = int(np.asarray(mbuf["rejects"][i]).sum())
        stal = sim.delivery.staleness(i)
        if stal:
            m.update(stal)
        logs.append(m)
    final = _final_metrics(e, state, acc, (ex, ey), mbuf)
    return RunResult(e, logs, final, wall, provenance(e.spec_hash),
                     netsim=netsim, state=state, buffers=mbuf)


# (G, device_count) -> protocol mesh. Reusing the SAME Mesh object across
# runs (and across the elastic runner's membership epochs with equal G) keeps
# the engines' semantic compile cache hot: the epoch cache keys the mesh by
# identity, so a fresh Mesh per run would force a re-trace every time.
_MESH_CACHE: dict[tuple, Any] = {}


def _protocol_mesh(G: int):
    key = (G, jax.device_count())
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        from ..launch.mesh import make_protocol_mesh
        mesh = _MESH_CACHE[key] = make_protocol_mesh(G)
    return mesh


def _lm_acc(bundle):
    """LM metric under the runners' uniform ``acc`` key: NEGATIVE eval loss
    (higher is better, like accuracy; documented in README §Models)."""

    def acc(params, tokens, labels):
        return -bundle.loss(params, {"tokens": tokens, "labels": labels})

    return acc


def _run_protocol(e: Experiment, delivery=None, netsim=None) -> RunResult:
    from ..core import protocol as _protocol
    from ..launch.mesh import use_mesh
    from .spec import DATA, is_arch_model
    pcfg = e.to_protocol_config()
    G = pcfg.n_groups
    bundle = e.build_bundle()
    mesh = _protocol_mesh(G)
    if is_arch_model(e.model):
        # zoo arch through the protocol: token stream, activation sharding
        # rules from the launch layer, negative-eval-loss metric
        from ..launch.steps import train_rules
        stream = DeviceTokenStream(e.seed, DATA[e.data], G, e.batch)
        ex, ey = stream.eval_set(e.eval_n)
        acc = _lm_acc(bundle)
        rules = train_rules(mesh, bundle.cfg)
    else:
        _, _, acc = e.build_problem()
        stream = DeviceBatchStream(e.seed, e.mixture, G, e.batch)
        ex, ey = stream.eval_set(e.eval_n)
        rules = None
    with_attack = bool(e.byz.worker_attack or e.byz.server_attack)
    with use_mesh(mesh):
        eng = _protocol.ProtocolEngine(
            bundle, pcfg, e.build_schedule(), mesh=mesh, delivery=delivery,
            with_attack=with_attack, acc_fn=acc, eval_set=(ex, ey),
            track_delta=e.track_delta, metrics_every=e.metrics_every,
            rules=rules)
        state = eng.init_state(jax.random.PRNGKey(e.seed))
        t0 = time.time()
        if e.ckpt_every:
            # chunk the fused run at checkpoint boundaries: the engine's
            # gather cadence rides on the step counter carried in the state,
            # so chunking is training-equivalent to one eng.run call
            if not e.ckpt_dir:
                raise ValueError(
                    f"experiment {e.name!r} sets ckpt_every={e.ckpt_every} "
                    "but no ckpt_dir; pass one at run time, e.g. "
                    'exp.run(name, ckpt_dir="...")')
            from ..checkpoint import checkpointer as ck
            bufs, done = [], 0
            while done < e.steps:
                n = min(e.ckpt_every, e.steps - done)
                state, b = eng.run(state, stream=stream, steps=n,
                                   epoch_steps=e.epoch_steps)
                bufs.append(b)
                done += n
                ck.save(e.ckpt_dir, done, state)
            mbuf = {k: np.concatenate([b[k] for b in bufs])
                    for k in bufs[0]}
        else:
            state, mbuf = eng.run(state, stream=stream, steps=e.steps,
                                  epoch_steps=e.epoch_steps)
        wall = time.time() - t0
        # under the run's mesh: the state is committed to its devices, and
        # a kernel on them runs per shard only where the mesh is ambient
        final = _final_metrics(e, state, acc, (ex, ey), mbuf)

    logs = []
    for i in range(0, e.steps, e.metrics_every):
        m = {"step": i, "acc": float(mbuf["acc"][i])}
        if e.track_delta:
            m["delta"] = float(mbuf["delta"][i])
            m["l2_diam"] = float(mbuf["l2_diam"][i])
        stal = eng.delivery.staleness(i)
        if stal:
            m.update(stal)
        logs.append(m)
    prov = provenance(e.spec_hash)
    prov["mesh"] = dict(zip(mesh.axis_names,
                            (int(n) for n in mesh.devices.shape)))
    prov["protocol_engine"] = pcfg.engine
    return RunResult(e, logs, final, wall, prov, netsim=netsim, state=state,
                     buffers=mbuf, engine=eng)


class _GroupView:
    """Width-adapted view of a :class:`DeviceBatchStream`: draws batches for
    the epoch's active-group count while advancing the base stream's key chain
    one split per step — exactly as the full-width stream would — so the data
    sequence stays aligned with the global step counter across membership
    changes."""

    def __init__(self, base: DeviceBatchStream, n_groups: int):
        self.base = base
        self.n_groups = n_groups

    def next(self, length: int):
        return self.base.next(length, n_workers=self.n_groups)


def _run_elastic(e: Experiment) -> RunResult:
    """Join/leave-tolerant protocol training (``runner="elastic"``).

    The run is chunked at every membership boundary of the plan (authored in
    the spec, or lowered from the named netsim scenario's realized crash
    windows). At each boundary the replica-stacked ``ByzState`` is
    checkpointed (when a ckpt_dir is given), the mesh and resilience
    parameters are re-formed for the new fleet
    (:func:`repro.core.membership.epoch_config` — Table-1 re-validated, hard
    :class:`~repro.core.membership.MembershipFloorError` below the floor),
    and re-admitted groups are seeded from the DMC median of the survivors.
    With an empty plan the run is bit-identical to ``runner="protocol"``."""
    import dataclasses as _dc

    from ..checkpoint import checkpointer as ck
    from ..core import membership as _membership
    from ..core import protocol as _protocol
    from ..launch.mesh import use_mesh

    pcfg0 = e.to_protocol_config()
    G0 = pcfg0.n_groups
    sync = e.variant == "sync"

    plan, plan_source, netsim = e.membership_plan, "spec", None
    if plan is None and e.scenario is not None:
        from ..netsim import ClusterSim
        sc = e.to_scenario()
        trace = ClusterSim(sc).run()
        plan = _membership.plan_from_trace(sc, trace)
        plan_source = f"scenario:{e.scenario}"
        netsim = {"scenario": sc.name, "steps": int(sc.steps),
                  "virtual_ms": float(trace.step_done_ms[-1]),
                  "events": int(trace.events),
                  "shortfalls": int(trace.shortfalls)}
    if plan is None:
        plan = _membership.MembershipPlan()
    if not plan.events:
        plan_source = "static" if plan_source == "spec" else plan_source
    segs = plan.epochs(G0, e.steps)

    init_fn, loss_fn, acc = e.build_problem()
    bundle = _protocol.ProblemBundle(init=init_fn, loss=loss_fn)
    stream = DeviceBatchStream(e.seed, e.mixture, G0, e.batch)
    ex, ey = stream.eval_set(e.eval_n)
    with_attack = bool(e.byz.worker_attack or e.byz.server_attack)

    if e.ckpt_every and not e.ckpt_dir:
        raise ValueError(
            f"experiment {e.name!r} sets ckpt_every={e.ckpt_every} "
            "but no ckpt_dir; pass one at run time, e.g. "
            'exp.run(name, ckpt_dir="...")')

    # resume: the latest checkpoint's manifest meta names the active set it
    # was saved under (absent for runner="protocol" checkpoints -> launch G)
    start, resume_active = 0, None
    if e.ckpt_dir:
        latest = ck.latest_step(e.ckpt_dir)
        if latest is not None:
            start = int(latest)
            if start > e.steps:
                raise ValueError(
                    f"checkpoint at step {start} under {e.ckpt_dir!r} is "
                    f"beyond this run (steps={e.steps}); wrong ckpt_dir?")
            meta = ck.read_manifest(e.ckpt_dir, start).get("meta") or {}
            resume_active = tuple(int(g) for g in
                                  meta.get("active", range(G0)))

    def _save(step: int, state, active) -> None:
        ck.save(e.ckpt_dir, step, state,
                meta={"elastic": True, "active": [int(g) for g in active],
                      "n_groups_launch": G0, "spec_hash": e.spec_hash})

    def _shardings(pcfg, mesh):
        return _protocol.state_shardings(
            jax.eval_shape(_protocol.make_init_fn(bundle, pcfg),
                           jax.random.PRNGKey(0)),
            mesh, overrides=_protocol.attn_overrides(bundle.cfg, mesh))

    state, prev_active, bufs = None, None, []
    pcfg = pcfg0
    mesh = _protocol_mesh(G0)
    t0 = time.time()
    for seg in segs:
        if seg.stop <= start and seg.stop < e.steps:
            continue  # fully replayed by the checkpoint (keep the last seg)
        pcfg = _membership.epoch_config(pcfg0, seg.active, synchronous=sync)
        mesh = _protocol_mesh(pcfg.n_groups)
        if state is not None and prev_active != seg.active:
            # re-form on the old mesh's layout, before the new mesh becomes
            # ambient (a jitted op on arrays committed to the old devices
            # is refused under another mesh), then place onto the new one
            params = _membership.reform_params(state.params, prev_active,
                                               seg.active)
            # re-stack per-replica optimizer moments alongside the params
            # (scalars — adamw's step count — ride through untouched)
            opt = jax.tree.map(
                lambda l: _membership.reform_params(l, prev_active,
                                                    seg.active)
                if getattr(l, "ndim", 0) >= 1
                and l.shape[0] == len(prev_active) else l,
                state.opt)
            state = _protocol.ByzState(params=params, t=state.t,
                                       key=state.key, opt=opt)
            state = jax.tree.map(jax.device_put, state,
                                 _shardings(pcfg, mesh))
            if e.ckpt_dir:
                # the boundary save overwrites the chunk save at the same
                # step: the post-re-form state (new G) is what a resume
                # of THIS epoch must restore
                _save(seg.start, state, seg.active)
        with use_mesh(mesh):
            eng = _protocol.ProtocolEngine(
                bundle, pcfg, e.build_schedule(), mesh=mesh,
                with_attack=with_attack, acc_fn=acc, eval_set=(ex, ey),
                track_delta=e.track_delta, metrics_every=e.metrics_every)
            if state is None:
                if start > 0:
                    if resume_active != seg.active:
                        raise ValueError(
                            f"checkpoint at step {start} was saved with "
                            f"active groups {resume_active}, but this plan's "
                            f"epoch there has {seg.active} — the checkpoint "
                            "does not belong to this membership plan")
                    like = jax.eval_shape(
                        _protocol.make_init_fn(bundle, pcfg),
                        jax.random.PRNGKey(0))
                    state, _ = ck.restore(e.ckpt_dir, start, like,
                                          _shardings(pcfg, mesh))
                    stream.skip(start)
                else:
                    state = eng.init_state(jax.random.PRNGKey(e.seed))
            prev_active = seg.active

            stream.place(NamedSharding(mesh, PartitionSpec()))
            seg_stream = _GroupView(stream, pcfg.n_groups)
            done = max(seg.start, start)
            while done < seg.stop:
                n = seg.stop - done
                if e.ckpt_every:
                    n = min(n, e.ckpt_every - done % e.ckpt_every)
                state, b = eng.run(state, stream=seg_stream, steps=n,
                                   epoch_steps=e.epoch_steps)
                bufs.append(b)
                done += n
                if e.ckpt_every:
                    _save(done, state, seg.active)
    if e.ckpt_dir and not e.ckpt_every and start < e.steps:
        _save(e.steps, state, prev_active)
    wall = time.time() - t0

    mbuf = ({k: np.concatenate([b[k] for b in bufs]) for k in bufs[0]}
            if bufs else {})
    logs = []
    if "acc" in mbuf:
        # buffer index j is global step start + j; acc lands where the global
        # step hits the metrics_every stride
        for j in range((-start) % e.metrics_every, len(mbuf["acc"]),
                       e.metrics_every):
            m = {"step": start + j, "acc": float(mbuf["acc"][j])}
            if e.track_delta:
                m["delta"] = float(mbuf["delta"][j])
                m["l2_diam"] = float(mbuf["l2_diam"][j])
            logs.append(m)

    p0 = jax.tree.map(lambda l: l[0], state.params)
    final = {"acc": float(acc(p0, ex, ey))}
    if e.track_delta:
        from ..core.simulator import coordinatewise_diameter_sum, l2_diameter
        h = pcfg.n_groups - e.byz.n_byz_servers
        final["delta"] = float(coordinatewise_diameter_sum(state.params, h))
        final["l2_diam"] = float(l2_diameter(state.params, h))

    prov = provenance(e.spec_hash)
    prov["mesh"] = dict(zip(mesh.axis_names,
                            (int(n) for n in mesh.devices.shape)))
    prov["protocol_engine"] = pcfg0.engine
    prov["membership"] = {
        "plan_source": plan_source,
        "events": [_dc.asdict(ev) for ev in plan.events],
        "epochs": [{"start": s.start, "stop": s.stop,
                    "active": list(s.active)} for s in segs],
        "resumed_at": start or None,
    }
    return RunResult(e, logs, final, wall, prov, netsim=netsim, state=state,
                     buffers=mbuf)


def write_result(res: RunResult, out_dir: str = "results/benchmarks",
                 name: str | None = None) -> str:
    """Write a RunResult verbatim as JSON; returns the path."""
    os.makedirs(out_dir, exist_ok=True)
    base = name or f"exp_{res.experiment.name.replace('/', '_')}" \
                   f"_{res.experiment.runner}"
    path = os.path.join(out_dir, base + ".json")
    with open(path, "w") as fh:
        json.dump(res.to_dict(), fh, indent=1, default=float)
    return path
