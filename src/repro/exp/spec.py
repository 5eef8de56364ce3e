"""The :class:`Experiment` spec — one frozen, fully-serializable object that
*names* a ByzSGD experiment.

Every comparative claim in the paper (async vs sync §5, GAR vs GAR under
attack §6, uniform vs adversarial delivery) is a pair of experiments that
differ in one field. Before this module each benchmark hand-wired a
``ByzSGDConfig``, a data stream, a model factory, a schedule and one of three
run paths; an ``Experiment`` carries all of it declaratively:

  * cluster shape + message schedule (``n_workers`` … ``T``, ``variant``),
  * threat model (a :class:`repro.core.attacks.ByzantineSpec`),
  * delivery model (``"uniform"`` = Assumption 7, ``"trace"`` = a realized
    ``repro.netsim`` schedule from the named ``scenario``),
  * per-role GARs (``gar``/``pull_gar``/``gather_gar``/``worker_gar`` — the
    comm-optimized schedules of arXiv:1911.07537 are just field choices),
  * model / data / schedule referenced **by registry name** (``MODELS`` /
    ``DATA`` / ``SCHEDULES`` below), never by closure,
  * the runner (``stepwise`` oracle loop, ``fused`` epoch engine, or
    ``netsim`` trace-driven) and backend knobs.

Specs are plain values: ``to_dict``/``from_dict`` round-trip exactly
(including through JSON), ``spec_hash`` is stable under dict key order, and
invalid combinations fail at construction, not at run time. ``Experiment``
*lowers* to the internal carriers — :meth:`to_config` (``ByzSGDConfig``) and
:meth:`to_scenario` (netsim ``Scenario``) — and the lowering cross-validates
that the round trip preserved every shared field.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from dataclasses import dataclass, field
from typing import Any, Callable

from ..configs.paper_models import make_mlp_problem
from ..core.attacks import GRADIENT_ATTACKS, MODEL_ATTACKS, ByzantineSpec
from ..core.membership import MembershipPlan, epoch_config
from ..core.simulator import ByzSGDConfig
from ..data.pipeline import MixtureSpec, TokenSpec
from ..optim import schedules as _schedules

# ---------------------------------------------------------------------------
# named resources: models / data / lr schedules
# ---------------------------------------------------------------------------

#: model registry. Two kinds of entry:
#:
#: * ``{"hidden", "depth"}`` — MLP width (depth-2 MLPs mirror the paper's
#:   CPU-scale testbed models; see configs/paper_models.py), trainable by
#:   every runner;
#: * ``{"arch", "reduced", ...overrides}`` — a ``models/`` zoo architecture,
#:   lowered via :func:`repro.models.registry.get_bundle` (extra keys are
#:   ``ArchConfig.reduced`` overrides). Arch entries train through the
#:   distributed protocol only (``runner="protocol"``): their sharded-pytree
#:   states, activation sharding rules and token batches are protocol-engine
#:   capabilities the single-host simulator does not carry.
MODELS: dict[str, dict[str, Any]] = {
    "mlp_h32": {"hidden": 32, "depth": 2},
    "mlp_h64": {"hidden": 64, "depth": 2},
    "mlp_h128": {"hidden": 128, "depth": 2},
    "mlp_h256": {"hidden": 256, "depth": 2},
    "mlp_h1024": {"hidden": 1024, "depth": 2},
    # reduced zoo archs — one per trainable model family (dense transformer
    # with the flash-attention hot path, MoE, RWKV6 SSM)
    "tfm_tiny": {"arch": "phi4-mini-3.8b", "reduced": True},
    "moe_tiny": {"arch": "qwen3-moe-235b-a22b", "reduced": True},
    "rwkv_tiny": {"arch": "rwkv6-3b", "reduced": True},
    # phi4-mini-3.8b at its published widths (d_model 3072, 24/8 heads of
    # 128, d_ff 8192, attention blocks q 512 / kv 1024), sized so that G=4
    # f32 replicas train on one 16 GB chip. The override keys are the cuts:
    # n_layers 32 -> 2 and a vocabulary slice 200064 -> 16384 (≈0.25B
    # params, ≈1 GB f32 per replica). chip_smoke.py drives it.
    "phi4_mini_cut": {"arch": "phi4-mini-3.8b", "reduced": False,
                      "n_layers": 2, "vocab": 16384},
}


def is_arch_model(name: str) -> bool:
    """True iff the MODELS entry lowers through the models/ zoo registry."""
    return "arch" in MODELS[name]

#: data registry: name -> synthetic task. MixtureSpec entries feed the MLP
#: models (see data/pipeline.py for why MNIST/CIFAR are substituted);
#: TokenSpec entries feed the arch-registry LM models (Zipf-distributed
#: next-token batches).
DATA: dict[str, MixtureSpec | TokenSpec] = {
    # the benchmark default (harder task: close centres, high noise)
    "mixture10": MixtureSpec(n_classes=10, dim=32, sep=1.0, noise=1.2),
    # the quickstart/example task (well-separated, converges in ~100 steps)
    "mixture10_easy": MixtureSpec(n_classes=10, dim=32),
    # tiny task for smoke presets and netsim walkthroughs
    "mixture5_small": MixtureSpec(n_classes=5, dim=16, sep=2.5),
    # LM token task matching the reduced zoo vocab (ArchConfig.reduced)
    "tokens_tiny": TokenSpec(vocab=512, seq=64),
    # LM token task for phi4_mini_cut: its vocabulary slice, 2048-token rows
    "tokens_v16k_s2k": TokenSpec(vocab=16384, seq=2048),
}

#: lr-schedule registry: name -> factory(lr0, decay) (paper condition B.1)
SCHEDULES: dict[str, Callable] = {
    "inverse_linear": lambda lr0, decay: _schedules.inverse_linear(lr0, decay),
    "inverse_sqrt": lambda lr0, decay: _schedules.inverse_sqrt(lr0),
    "constant": lambda lr0, decay: _schedules.constant(lr0),
}

#: schedules whose factory actually consumes ``decay`` — setting decay on any
#: other schedule is rejected at construction (it would change spec_hash and
#: provenance without changing the run)
SCHEDULES_WITH_DECAY = frozenset({"inverse_linear"})

RUNNERS = ("stepwise", "fused", "netsim", "protocol", "elastic")
DELIVERIES = ("uniform", "trace")
PROTOCOL_ENGINES = ("naive", "sharded")


@dataclass(frozen=True)
class Experiment:
    """One serializable experiment spec; see the module docstring."""
    name: str = "experiment"
    # -- cluster shape + message schedule (paper Table 1 preconditions)
    n_workers: int = 9
    f_workers: int = 2
    n_servers: int = 5
    f_servers: int = 1
    q_workers: int | None = None
    q_servers: int | None = None
    T: int = 10
    variant: str = "async"            # "async" | "sync"
    # -- per-role GARs (any repro.agg registry name with pytree support)
    gar: str = "mda"
    pull_gar: str = "median"
    gather_gar: str = "median"
    worker_gar: str = "meamed"
    # -- threat model
    byz: ByzantineSpec = field(default_factory=ByzantineSpec)
    # -- delivery model
    delivery: str = "uniform"         # "uniform" | "trace"
    scenario: str | None = None       # netsim scenario name (delivery="trace")
    model_d: int | None = None        # netsim payload size override (scalars)
    # -- model / data / optimizer by registry name
    model: str = "mlp_h64"
    data: str = "mixture10"
    schedule: str = "inverse_linear"
    optimizer: str = "sgd"            # repro.optim registry ref; non-sgd is a
                                      # protocol/elastic-runner capability
    lr0: float = 0.05
    decay: float = 0.005
    l2: float = 1e-4
    # -- run shape
    runner: str = "fused"     # "stepwise" | "fused" | "netsim" | "protocol"
    steps: int = 150
    batch: int = 25
    seed: int = 0
    metrics_every: int = 10
    eval_n: int = 2048
    track_delta: bool = False
    # -- protocol + backend knobs
    lip_horizon: int = 128
    mda_exact_limit: int = 200_000
    agg_backend: str | None = None    # None = process default (env/auto)
    sort_network: bool = True
    epoch_steps: int | None = None    # fused scan chunk (None = T)
    protocol_engine: str = "sharded"  # runner="protocol" collective engine
    # -- checkpointing (runner="protocol"): emit the replica-stacked ByzState
    # every ckpt_every steps into ckpt_dir (repro.checkpoint format; serve
    # restores it via repro.serve.ReplicaPool.from_checkpoint). Presets may
    # set ckpt_every with ckpt_dir=None — callers pass ckpt_dir at run time.
    ckpt_every: int | None = None
    ckpt_dir: str | None = None
    # -- elastic membership (runner="elastic"): a declarative join/leave
    # schedule in virtual steps (core/membership.py). None with
    # runner="elastic" means: lower the plan from the named netsim scenario's
    # realized crash windows (scenario set), or run statically (no scenario —
    # bit-identical to runner="protocol").
    membership_plan: MembershipPlan | None = None

    # -- construction-time validation -------------------------------------
    def __post_init__(self):
        if not isinstance(self.byz, ByzantineSpec):
            raise TypeError("byz must be a ByzantineSpec "
                            f"(got {type(self.byz).__name__})")
        # normalize attack_kwargs to a tuple-of-pairs so equality and hashing
        # are representation-independent (JSON round-trips lists)
        kw = tuple((str(k), v) for k, v in self.byz.attack_kwargs)
        if kw != self.byz.attack_kwargs:
            object.__setattr__(self, "byz",
                               dataclasses.replace(self.byz, attack_kwargs=kw))
        if self.runner not in RUNNERS:
            raise ValueError(f"unknown runner {self.runner!r}; "
                             f"choose from {RUNNERS}")
        if self.delivery not in DELIVERIES:
            raise ValueError(f"unknown delivery {self.delivery!r}; "
                             f"choose from {DELIVERIES}")
        if self.runner == "netsim" and self.delivery != "trace":
            object.__setattr__(self, "delivery", "trace")
        if self.membership_plan is not None:
            mp = self.membership_plan
            if isinstance(mp, dict):
                mp = MembershipPlan.from_dict(mp)
                object.__setattr__(self, "membership_plan", mp)
            if not isinstance(mp, MembershipPlan):
                raise TypeError("membership_plan must be a MembershipPlan "
                                f"(got {type(mp).__name__})")
            if self.runner != "elastic":
                raise ValueError(
                    'membership_plan is a runner="elastic" knob (only the '
                    "elastic runner re-forms the mesh at membership "
                    f"boundaries); got runner={self.runner!r}")
        if self.runner == "elastic" and self.delivery == "trace":
            raise ValueError(
                'runner="elastic" needs delivery="uniform": trace delivery '
                "tables are staged at the launch fleet width and cannot "
                "follow a membership change (a scenario still drives the "
                'elastic run — its realized crash windows become the '
                "membership plan)")
        if self.delivery == "trace" and self.scenario is None:
            raise ValueError('delivery="trace" needs a netsim scenario '
                             "name (Experiment.scenario)")
        if self.scenario is not None:
            from ..netsim import scenarios as _scen
            if self.scenario not in _scen.SCENARIOS:
                raise ValueError(f"unknown netsim scenario {self.scenario!r}; "
                                 f"have {sorted(_scen.SCENARIOS)}")
        for reg, key in ((MODELS, "model"), (DATA, "data"),
                         (SCHEDULES, "schedule")):
            val = getattr(self, key)
            if val not in reg:
                raise ValueError(f"unknown {key} {val!r}; "
                                 f"registered: {sorted(reg)}")
        if is_arch_model(self.model):
            if self.runner != "protocol":
                raise ValueError(
                    f"model {self.model!r} is an arch-registry model and "
                    'trains through runner="protocol" only (sharded states, '
                    "activation sharding rules and token batches are "
                    f"protocol-engine capabilities); got {self.runner!r}")
            if not isinstance(DATA[self.data], TokenSpec):
                raise ValueError(
                    f"arch model {self.model!r} needs token data (a TokenSpec "
                    f"DATA entry); {self.data!r} is "
                    f"{type(DATA[self.data]).__name__}")
            vocab = self.build_bundle().cfg.vocab
            if DATA[self.data].vocab != vocab:
                raise ValueError(
                    f"data {self.data!r} has vocab {DATA[self.data].vocab} "
                    f"but model {self.model!r} has vocab {vocab}")
        elif isinstance(DATA[self.data], TokenSpec):
            raise ValueError(
                f"MLP model {self.model!r} needs mixture data (a MixtureSpec "
                f"DATA entry); {self.data!r} is a TokenSpec")
        from .. import optim as _optim
        if self.optimizer not in _optim.OPTIMIZERS:
            raise ValueError(f"unknown optimizer {self.optimizer!r}; "
                             f"registered: {sorted(_optim.OPTIMIZERS)}")
        if self.optimizer != "sgd" and self.runner not in ("protocol",
                                                           "elastic"):
            raise ValueError(
                f"optimizer={self.optimizer!r} needs the protocol/elastic "
                "runner (the single-host simulator implements the paper's "
                f"Eq. 2 SGD only); got runner={self.runner!r}")
        default_decay = type(self).__dataclass_fields__["decay"].default
        if self.schedule not in SCHEDULES_WITH_DECAY \
                and self.decay != default_decay:
            raise ValueError(
                f"schedule {self.schedule!r} ignores decay — setting "
                f"decay={self.decay} would change the spec_hash without "
                f"changing the run (leave it at the default {default_decay})")
        wa, sa = self.byz.worker_attack, self.byz.server_attack
        if wa is not None and wa not in GRADIENT_ATTACKS:
            raise ValueError(f"unknown worker_attack {wa!r}; "
                             f"have {sorted(GRADIENT_ATTACKS)}")
        if sa is not None and sa not in MODEL_ATTACKS:
            raise ValueError(f"unknown server_attack {sa!r}; "
                             f"have {sorted(MODEL_ATTACKS)}")
        for key, lo in (("steps", 1), ("batch", 1), ("metrics_every", 1),
                        ("eval_n", 1), ("T", 1)):
            if getattr(self, key) < lo:
                raise ValueError(f"{key} must be >= {lo}, "
                                 f"got {getattr(self, key)}")
        if self.agg_backend not in (None, "auto", "jnp", "pallas"):
            raise ValueError(f"unknown agg_backend {self.agg_backend!r}")
        if self.ckpt_every is not None:
            if self.runner not in ("protocol", "elastic"):
                raise ValueError(
                    'ckpt_every is a runner="protocol"/"elastic" knob (those '
                    "engines own the replica-stacked ByzState that "
                    f"checkpoints save); got runner={self.runner!r}")
            if self.ckpt_every < 1:
                raise ValueError(f"ckpt_every must be >= 1, "
                                 f"got {self.ckpt_every}")
        elif self.ckpt_dir is not None and self.runner != "elastic":
            # the elastic runner reads ckpt_dir without ckpt_every: it resumes
            # from the latest checkpoint and still saves at every membership
            # boundary (+ the final step) even without a periodic cadence
            raise ValueError("ckpt_dir without ckpt_every does nothing; "
                             "set ckpt_every to emit checkpoints")
        if self.protocol_engine not in PROTOCOL_ENGINES:
            raise ValueError(f"unknown protocol_engine "
                             f"{self.protocol_engine!r}; "
                             f"choose from {PROTOCOL_ENGINES}")
        # the cluster-shape / GAR / threat-model preconditions: lowering to
        # ByzSGDConfig runs the paper's Table-1 validation + registry checks
        self.to_config()
        if self.runner in ("protocol", "elastic"):
            # the distributed path maps G co-located worker+server groups
            # onto 'rep' failure domains: shape + rule capabilities validated
            # by lowering to ProtocolConfig at construction, not at run time
            pcfg = self.to_protocol_config()
            if self.runner == "elastic" and self.membership_plan is not None:
                # every membership epoch must satisfy Table 1 for its shrunk/
                # regrown fleet — a below-floor plan fails HERE, not mid-run
                for seg in self.membership_plan.epochs(self.n_workers,
                                                       self.steps):
                    epoch_config(pcfg, seg.active,
                                 synchronous=(self.variant == "sync"))

    # -- serialization -----------------------------------------------------
    def to_dict(self) -> dict[str, Any]:
        """Nested plain-value dict (JSON-compatible; tuples become lists on a
        JSON round trip, which :meth:`from_dict` normalizes back)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "Experiment":
        d = dict(d)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown Experiment fields: {sorted(unknown)}")
        byz = d.get("byz")
        if isinstance(byz, dict):
            byz = dict(byz)
            byz["attack_kwargs"] = tuple(
                (str(k), v) for k, v in byz.get("attack_kwargs", ()))
            d["byz"] = ByzantineSpec(**byz)
        mp = d.get("membership_plan")
        if isinstance(mp, dict):
            d["membership_plan"] = MembershipPlan.from_dict(mp)
        return cls(**d)

    @property
    def spec_hash(self) -> str:
        """Stable content hash: canonical JSON (sorted keys) of
        :meth:`to_dict`, independent of field/dict ordering."""
        blob = json.dumps(self.to_dict(), sort_keys=True, default=list)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def replace(self, **kw) -> "Experiment":
        return dataclasses.replace(self, **kw)

    # -- lowering to the internal carriers ---------------------------------
    def to_config(self) -> ByzSGDConfig:
        """Lower to the simulator's ``ByzSGDConfig`` and cross-validate that
        the lowering round-trips (every shared field preserved)."""
        cfg = ByzSGDConfig(
            n_workers=self.n_workers, f_workers=self.f_workers,
            n_servers=self.n_servers, f_servers=self.f_servers,
            q_workers=self.q_workers, q_servers=self.q_servers, T=self.T,
            gar=self.gar, pull_gar=self.pull_gar,
            gather_gar=self.gather_gar, worker_gar=self.worker_gar,
            variant=self.variant, mda_exact_limit=self.mda_exact_limit,
            lip_horizon=self.lip_horizon, byz=self.byz)
        for key in ("n_workers", "f_workers", "n_servers", "f_servers", "T",
                    "gar", "pull_gar", "gather_gar", "worker_gar", "variant",
                    "byz"):
            if getattr(cfg, key) != getattr(self, key):
                raise ValueError(f"lowering to ByzSGDConfig changed {key}: "
                                 f"{getattr(self, key)!r} -> "
                                 f"{getattr(cfg, key)!r}")
        for key in ("q_workers", "q_servers"):
            mine = getattr(self, key)
            if mine is not None and getattr(cfg, key) != mine:
                raise ValueError(f"lowering to ByzSGDConfig changed {key}")
        return cfg

    def to_protocol_config(self):
        """Lower to the distributed :class:`~repro.core.protocol.ProtocolConfig`
        (``runner="protocol"``), cross-validated like :meth:`to_config`.

        The protocol's failure domains are G co-located worker+server groups,
        so the spec must declare ``n_workers == n_servers`` (= G); quorum
        defaults come from the ``ByzSGDConfig`` lowering so the 1-device
        protocol draws the same quorums as the single-host oracle. The
        ``variant`` maps onto the protocol's pull schedule: async → masked
        ``pull_gar`` over the delivered quorum (oracle-matched against the
        fused runner on a 1-device mesh), sync → the protocol's own §5
        round-robin pull + distance filter — a collective formulation that is
        a *documented deviation* from the single-host sync filter variant
        (different filters, no per-worker model state), so sync protocol runs
        are not equivalence-gated against the fused runner."""
        from ..core.protocol import ProtocolConfig
        if self.n_workers != self.n_servers:
            raise ValueError(
                f'runner="protocol" maps co-located worker+server groups '
                f"onto 'rep' failure domains and needs "
                f"n_workers == n_servers (= G); got "
                f"{self.n_workers} != {self.n_servers}")
        cfg = self.to_config()
        pcfg = ProtocolConfig.derive(
            self.n_workers, T=self.T, engine=self.protocol_engine,
            pull=("roundrobin" if self.variant == "sync" else "median"),
            f_workers=self.f_workers, f_servers=self.f_servers,
            q_workers=cfg.q_workers, q_servers=cfg.q_servers,
            gar=self.gar, pull_gar=self.pull_gar,
            gather_gar=self.gather_gar, optimizer=self.optimizer,
            mda_exact_limit=self.mda_exact_limit, byz=self.byz)
        for key, mine in (("n_groups", self.n_workers),
                          ("f_workers", self.f_workers),
                          ("f_servers", self.f_servers),
                          ("q_workers", cfg.q_workers),
                          ("q_servers", cfg.q_servers), ("T", self.T),
                          ("gar", self.gar), ("pull_gar", self.pull_gar),
                          ("gather_gar", self.gather_gar),
                          ("optimizer", self.optimizer),
                          ("byz", self.byz)):
            if getattr(pcfg, key) != mine:
                raise ValueError(f"lowering to ProtocolConfig changed {key}: "
                                 f"{mine!r} -> {getattr(pcfg, key)!r}")
        return pcfg

    def to_scenario(self, **overrides):
        """Lower to the netsim ``Scenario`` (via its factory registry),
        cross-validated: shape, schedule, GAR and threat-model fields must
        survive the factory unchanged. ``overrides`` are forwarded to the
        factory (e.g. ``model_d=…`` for payload sizing)."""
        from ..netsim import scenarios as _scen
        if self.scenario is None:
            raise ValueError(f"experiment {self.name!r} names no netsim "
                             "scenario")
        kw = dict(n_workers=self.n_workers, f_workers=self.f_workers,
                  n_servers=self.n_servers, f_servers=self.f_servers,
                  q_workers=self.q_workers, q_servers=self.q_servers,
                  T=self.T, steps=self.steps, seed=self.seed, gar=self.gar,
                  variant=self.variant,
                  worker_attack=self.byz.worker_attack,
                  server_attack=self.byz.server_attack,
                  n_byz_workers=self.byz.n_byz_workers,
                  n_byz_servers=self.byz.n_byz_servers)
        if self.model_d is not None:
            kw["model_d"] = self.model_d
        kw.update(overrides)
        sc = _scen.build(self.scenario, **kw)
        for key in ("n_workers", "f_workers", "n_servers", "f_servers", "T",
                    "gar", "variant", "worker_attack", "server_attack",
                    "n_byz_workers", "n_byz_servers"):
            if getattr(sc, key) != kw[key]:
                raise ValueError(f"lowering to Scenario changed {key}: "
                                 f"{kw[key]!r} -> {getattr(sc, key)!r}")
        return sc

    # -- resource construction ---------------------------------------------
    @property
    def mixture(self) -> MixtureSpec:
        return DATA[self.data]

    def build_problem(self):
        """(init_fn, loss_fn, accuracy_fn) for the named model on the named
        data spec (MLP models; arch models lower via :meth:`build_bundle`)."""
        if is_arch_model(self.model):
            raise ValueError(
                f"model {self.model!r} is an arch-registry model; it lowers "
                "through build_bundle() (a ModelBundle), not the MLP "
                "(init, loss, acc) problem triple")
        mix = self.mixture
        m = MODELS[self.model]
        return make_mlp_problem(dim=mix.dim, hidden=m["hidden"],
                                n_classes=mix.n_classes, depth=m["depth"],
                                l2=self.l2)

    def build_bundle(self):
        """The protocol-ready bundle for the named model: the zoo
        :class:`~repro.models.registry.ModelBundle` for arch entries
        (registry overrides applied on the reduced config), or the MLP
        problem wrapped in a
        :class:`~repro.core.protocol.ProblemBundle`."""
        m = MODELS[self.model]
        if "arch" in m:
            from ..models.registry import get_bundle
            kw = {k: v for k, v in m.items() if k not in ("arch", "reduced")}
            return get_bundle(m["arch"], reduced=m.get("reduced", False),
                              **kw)
        from ..core.protocol import ProblemBundle
        init, loss, _ = self.build_problem()
        return ProblemBundle(init=init, loss=loss)

    def build_schedule(self):
        return SCHEDULES[self.schedule](self.lr0, self.decay)

    def build_sim(self, delivery=None):
        """A ready :class:`~repro.core.simulator.ByzSGDSimulator` (delivery
        defaults to ``UniformDelivery``; pass a ``TraceDelivery`` for
        trace-driven runs)."""
        from ..core.simulator import ByzSGDSimulator
        init, loss, _ = self.build_problem()
        return ByzSGDSimulator(self.to_config(), init, loss,
                               self.build_schedule(), delivery=delivery)
