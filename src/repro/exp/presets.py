"""Named experiment presets — the registry :func:`repro.exp.run` resolves.

A preset is a frozen :class:`~repro.exp.spec.Experiment`; :func:`get` applies
field overrides with ``dataclasses.replace`` (re-validating), so every CLI
(``benchmarks/run.py --exp NAME --override key=val``) and test shrinks or
scales presets without bespoke wiring.

The ``netsim/*`` presets mirror — and subsume — the ``repro.netsim.scenarios``
factories: each names its scenario and the matching threat model, with
``runner="netsim"`` so :func:`repro.exp.run` simulates the cluster and trains
over the realized trace. ``python -m repro.exp`` prints the table below for
the README.
"""
from __future__ import annotations

from ..core.attacks import ByzantineSpec
from ..core.membership import MembershipEvent, MembershipPlan
from .spec import Experiment

_PRESETS: dict[str, Experiment] = {}


def register(exp: Experiment, *, replace: bool = False) -> Experiment:
    """Register a preset under ``exp.name`` (third parties included)."""
    if exp.name in _PRESETS and not replace:
        raise ValueError(f"experiment preset {exp.name!r} already registered")
    _PRESETS[exp.name] = exp
    return exp


def get(name: str, **overrides) -> Experiment:
    """Preset by name, with field overrides applied (and re-validated)."""
    try:
        base = _PRESETS[name]
    except KeyError:
        raise KeyError(f"unknown experiment preset {name!r}; "
                       f"have {sorted(_PRESETS)}") from None
    return base.replace(**overrides) if overrides else base


def names() -> tuple[str, ...]:
    return tuple(sorted(_PRESETS))


def specs() -> tuple[Experiment, ...]:
    return tuple(_PRESETS[n] for n in names())


# ---------------------------------------------------------------------------
# built-in presets
# ---------------------------------------------------------------------------

# the CI/`make exp` smoke spec: small enough to run through every runner in
# seconds, shaped to exercise a gather boundary and a tail (steps % T != 0).
# n_workers == n_servers so the same spec also sweeps onto the distributed
# protocol runner (G = 5 co-located groups) unchanged.
register(Experiment(
    name="smoke", n_workers=5, f_workers=1, n_servers=5, f_servers=1, T=5,
    steps=12, batch=8, model="mlp_h32", data="mixture5_small",
    scenario="baseline_uniform", metrics_every=5, eval_n=256))

# clean baselines (Fig. 3): async and sync ByzSGD without adversaries
register(Experiment(name="clean_async", variant="async", steps=120))
register(Experiment(name="clean_sync", variant="sync", n_workers=5,
                    f_workers=1, steps=120))

# the quickstart: 2/9 workers mounting ALIE, converges anyway (§6 headline)
register(Experiment(
    name="quickstart", data="mixture10_easy",
    byz=ByzantineSpec(worker_attack="alie", n_byz_workers=2,
                      equivocate=True)))

# Fig. 6 operating point: max declared f_w, all of it actually Byzantine
register(Experiment(
    name="alie_workers", n_workers=13, f_workers=4, steps=120,
    byz=ByzantineSpec(worker_attack="alie", n_byz_workers=4,
                      equivocate=True)))

# Fig. 5 operating points: one Byzantine server
register(Experiment(
    name="lie_server", steps=120, track_delta=True,
    byz=ByzantineSpec(server_attack="lie", n_byz_servers=1,
                      equivocate=True)))
register(Experiment(
    name="reversed_server", steps=120, track_delta=True,
    byz=ByzantineSpec(server_attack="reversed", n_byz_servers=1,
                      equivocate=True)))

# sync filter variant under a Byzantine server (Fig. 10 operating point)
register(Experiment(
    name="sync_filters", variant="sync", n_workers=5, f_workers=1, T=20,
    steps=100, batch=100, lip_horizon=32, l2=3e-2, decay=0.001,
    byz=ByzantineSpec(server_attack="reversed", n_byz_servers=1,
                      equivocate=True)))

# netsim presets: one per scenario factory, trained over the realized trace
_NETSIM_COMMON = dict(
    runner="netsim", T=5, steps=30, batch=16, model="mlp_h32",
    data="mixture5_small", metrics_every=10, eval_n=512)
for _scen in ("baseline_uniform", "heavy_tail_stragglers", "partitioned_dmc",
              "crash_storm", "membership_churn"):
    register(Experiment(name=f"netsim/{_scen}", scenario=_scen,
                        **_NETSIM_COMMON))
# the compound adversary: netsim makes the Byzantine workers slow, the
# simulator's injection makes them malicious (mirrors the factory's defaults)
register(Experiment(
    name="netsim/byzantine_plus_slow", scenario="byzantine_plus_slow",
    byz=ByzantineSpec(worker_attack="alie", n_byz_workers=2, equivocate=True),
    **_NETSIM_COMMON))

# serve presets: protocol-runner training that emits replica-stacked
# checkpoints for repro.serve (ckpt_dir comes from the caller at run time:
# exp.run("serve/ckpt_smoke", ckpt_dir=...)). G=5 satisfies Table 1's
# n_ps >= 3f+2 for training; serving reads tolerate f=1 of any 2f+1 subset.
_SERVE_COMMON = dict(
    runner="protocol", n_workers=5, f_workers=1, n_servers=5, f_servers=1,
    T=5, steps=10, batch=8, model="mlp_h32", data="mixture5_small",
    metrics_every=5, eval_n=256, ckpt_every=5)
register(Experiment(name="serve/ckpt_smoke", **_SERVE_COMMON))
# same training run with a lie-attacking server: the checkpoint carries the
# corrupted replica, which quorum reads (or a consolidated restore) outvote
register(Experiment(
    name="serve/ckpt_lie_server",
    byz=ByzantineSpec(server_attack="lie", n_byz_servers=1, equivocate=True),
    **_SERVE_COMMON))


# elastic presets: join/leave-tolerant protocol training (core/membership).
# G=5 launches at the declared Table-1 point (f_w=f_ps=1); while a group is
# down (G'=4) the churn-driven resilience caps f_ps' at 0, so these presets
# stay honest (no Byzantine servers) — a Byz-server spec with a shrink event
# is rejected at construction (MembershipFloorError).
_ELASTIC_COMMON = dict(
    runner="elastic", n_workers=5, f_workers=1, n_servers=5, f_servers=1,
    T=5, steps=24, batch=8, model="mlp_h32", data="mixture5_small",
    metrics_every=4, eval_n=256)
# static fleet: bit-identical to runner="protocol" on the same spec (the
# elastic equivalence gate, tests/test_membership.py)
register(Experiment(name="elastic/static", **_ELASTIC_COMMON))
# authored plan: group 4 leaves at step 8 (G 5->4) and rejoins at step 16,
# re-seeded from the DMC median of the survivors
register(Experiment(
    name="elastic/planned_churn",
    membership_plan=MembershipPlan(events=(
        MembershipEvent(step=8, kind="leave", group=4),
        MembershipEvent(step=16, kind="join", group=4))),
    **_ELASTIC_COMMON))
# scenario-driven plan: the membership_churn crash windows realize through
# the netsim engine and lower to leave/join events (plan_from_trace)
register(Experiment(name="elastic/netsim_churn", scenario="membership_churn",
                    **_ELASTIC_COMMON))


# lm presets: zoo architectures through the distributed protocol — one per
# trainable model family (dense transformer / MoE / RWKV6 SSM), reduced
# configs on the Zipf token task. G=4 co-located groups satisfy Table 1
# (n_w >= 3·1+1 = 4 workers, n_ps >= 3·0+2 = 2 servers) AND split 2D on an
# 8-device fleet: make_protocol_mesh lights up (rep=4, fsdp=2, model=1), so
# these presets are the repo's paper-scale 2D-sharding acceptance path. The
# "acc" metric is the NEGATIVE eval loss (higher is better; README §Models).
_LM_COMMON = dict(
    runner="protocol", n_workers=4, f_workers=1, n_servers=4, f_servers=0,
    T=5, steps=12, batch=4, data="tokens_tiny", schedule="constant",
    lr0=0.02, metrics_every=4, eval_n=64)
register(Experiment(name="lm/tfm_tiny", model="tfm_tiny", **_LM_COMMON))
register(Experiment(name="lm/moe_tiny", model="moe_tiny", **_LM_COMMON))
register(Experiment(name="lm/rwkv_tiny", model="rwkv_tiny", **_LM_COMMON))


# ---------------------------------------------------------------------------
# registry-derived documentation (README preset table)
# ---------------------------------------------------------------------------


def runners_table() -> str:
    """README "Runners" table (``python -m repro.exp`` regenerates it).

    One row per ``Experiment.runner`` value; the collective-volume column
    models the per-step cross-'rep' exchange of the protocol's two collective
    engines (P = model parameters; see
    ``repro.core.protocol.collective_volume_bytes``)."""
    rows = [
        ("stepwise", "per-step jitted oracle loop (`ByzSGDSimulator.run`)",
         "uniform or trace", "one host, replica-stacked `[n_ps, ...]`", "—"),
        ("fused", "donated `lax.scan` epochs (`EpochEngine`)",
         "uniform or trace", "one host, replica-stacked `[n_ps, ...]`", "—"),
        ("netsim", "fused epochs over the realized netsim trace "
         "(+ cluster accounting in the result)", "trace",
         "one host, replica-stacked `[n_ps, ...]`", "—"),
        ("protocol", "donated `lax.scan` epochs (`ProtocolEngine`)",
         "uniform or trace",
         "`[G, ...]` sharded over the ('rep','fsdp','model') mesh",
         "2(G−1)·P/K either engine, K = fsdp axis size (HLO-audited; the "
         "engines differ in temp memory, not ring traffic)"),
        ("elastic", "protocol epochs chunked at membership boundaries "
         "(`core/membership.py`): mesh/quorums re-formed per epoch, "
         "checkpointed resume, DMC-seeded re-admission", "uniform",
         "`[G', ...]` re-stacked per membership epoch", "as protocol, "
         "per-epoch G′"),
    ]
    out = ["| runner | loop | delivery | state layout | "
           "per-step collective volume |",
           "|---|---|---|---|---|"]
    for name, loop, deliv, layout, vol in rows:
        out.append(f"| `{name}` | {loop} | {deliv} | {layout} | {vol} |")
    return "\n".join(out)


def models_table() -> str:
    """README "Models" table (``python -m repro.exp`` regenerates it).

    One row per ``repro.exp.spec.MODELS`` registry entry. Zoo archs lower
    through ``models.registry.get_bundle`` and train only on the protocol
    runner (they need the mesh + activation-sharding rules); their "acc"
    metric is the NEGATIVE eval loss, so higher is better everywhere."""
    from ..models.registry import get_bundle
    from .spec import MODELS, is_arch_model
    out = ["| model | definition | family | runners | `acc` metric |",
           "|---|---|---|---|---|"]
    for name in sorted(MODELS):
        m = MODELS[name]
        if is_arch_model(name):
            cfg = get_bundle(m["arch"],
                             reduced=m.get("reduced", False)).cfg
            defn = f"zoo `{m['arch']}`"
            cuts = ", ".join(f"{k} {v}" for k, v in m.items()
                             if k not in ("arch", "reduced"))
            if m.get("reduced"):
                defn += " (reduced)"
            elif cuts:
                defn += f" (published widths; cut: {cuts})"
            fam, runners = cfg.family, "`protocol`"
            metric = "negative eval loss (higher is better)"
        else:
            defn = f"MLP (hidden {m['hidden']}, depth {m['depth']})"
            fam, runners = "mlp", "all"
            metric = "eval accuracy"
        out.append(f"| `{name}` | {defn} | {fam} | {runners} | {metric} |")
    return "\n".join(out)


def markdown_table() -> str:
    """README preset table (``python -m repro.exp`` regenerates it)."""
    head = ("| preset | runner | variant | cluster (n_w/f_w, n_ps/f_ps, T) | "
            "gar | attack | steps |")
    out = [head, "|---|---|---|---|---|---|---|"]
    for e in specs():
        atk = "—"
        if e.byz.worker_attack:
            atk = f"{e.byz.worker_attack} ×{e.byz.n_byz_workers} (workers)"
        elif e.byz.server_attack:
            atk = f"{e.byz.server_attack} ×{e.byz.n_byz_servers} (servers)"
        out.append(
            f"| `{e.name}` | {e.runner} | {e.variant} | "
            f"{e.n_workers}/{e.f_workers}, {e.n_servers}/{e.f_servers}, "
            f"T={e.T} | `{e.gar}` | {atk} | {e.steps} |")
    return "\n".join(out)
