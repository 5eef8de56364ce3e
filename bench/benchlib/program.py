"""The system under test, built as ``repro.exp`` builds a protocol run.

``Experiment`` -> ``to_protocol_config()`` -> ``make_protocol_mesh(G)`` over
the cell's chips -> ``launch.steps.train_rules`` -> ``ProtocolEngine``, the
entry the window drives (``run_epoch``: one donated ``lax.scan`` of T
steps). No eval runs inside the scan. The configuration file's ``program``
entry is registered into ``repro.exp.spec.MODELS`` under the configuration's
name, and the traffic's vocabulary and row length into ``DATA``.

The initial state is the benchmark's, not the program's: the reference's
``init_params`` makes the weights from the seed, in one jitted call that
places them as the program lays out its state (G replicas, per-leaf
shardings). The program and the reference thus start from the same weights,
and the reference takes nothing that the program made.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec


@dataclass
class Program:
    engine: Any
    mesh: Any
    pcfg: Any
    make_state: Any          # jitted: weights key, run key -> ByzState
    replicated: Any          # sharding for the traffic pool


def experiment(cell, model_overrides: dict | None = None):
    """The cell as a ``repro.exp.Experiment`` (registering its model and
    data names), and the configuration's model name used."""
    from repro.data.pipeline import TokenSpec
    from repro.exp import spec as S
    conf, traffic = cell.config, cell.traffic
    model = dict(conf["program"], **(model_overrides or {}))
    name = cell.config_name + "".join(
        f"+{k}={v}" for k, v in sorted((model_overrides or {}).items()))
    if S.MODELS.get(name, model) != model:
        raise ValueError(f"MODELS[{name!r}] is {S.MODELS[name]}, but "
                         f"the configuration file says {model}")
    S.MODELS[name] = model
    data = f"bench:{conf['vocab_size']}x{traffic['seq']}"
    S.DATA[data] = TokenSpec(vocab=conf["vocab_size"], seq=traffic["seq"],
                             zipf=traffic["zipf"])
    return S.Experiment(
        name=f"bench/{cell.name}", runner="protocol", model=name, data=data,
        batch=int(traffic["rows_per_group"]), steps=cell.settings[
            "experiment"]["T"], **cell.settings["experiment"])


def build(cell, devices, model_overrides: dict | None = None) -> Program:
    from repro import optim
    from repro.core import protocol
    from repro.launch.mesh import make_protocol_mesh, use_mesh
    from repro.launch.steps import train_rules

    e = experiment(cell, model_overrides)
    pcfg = e.to_protocol_config()
    bundle = e.build_bundle()
    mesh = make_protocol_mesh(pcfg.n_groups, devices=devices)
    with use_mesh(mesh):
        eng = protocol.ProtocolEngine(
            bundle, pcfg, e.build_schedule(), mesh=mesh,
            rules=train_rules(mesh, bundle.cfg))
    init = protocol.make_init_fn(bundle, pcfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    shardings = protocol.state_shardings(
        shapes, mesh, overrides=protocol.attn_overrides(bundle.cfg, mesh))
    ref, conf = cell.reference, cell.config
    pdt = jnp.dtype(bundle.cfg.param_dtype)
    opt = optim.get(pcfg.optimizer)
    G = pcfg.n_groups

    def make_state(k_model, k_run):
        p0 = ref.init_params(k_model, conf)
        params = jax.tree.map(
            lambda l: jnp.broadcast_to(l.astype(pdt), (G,) + l.shape), p0)
        return protocol.ByzState(params=params, t=jnp.zeros((), jnp.int32),
                                 key=k_run, opt=opt.init(params))

    got = jax.eval_shape(make_state, jax.random.PRNGKey(0),
                         jax.random.PRNGKey(1))
    if jax.tree.structure(got) != jax.tree.structure(shapes) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(got), jax.tree.leaves(shapes))):
        raise ValueError("the reference's parameter tree does not match the "
                         "program's: the configuration file and the program "
                         "disagree on a size")
    return Program(engine=eng, mesh=mesh, pcfg=pcfg,
                   make_state=jax.jit(make_state, out_shardings=shardings),
                   replicated=NamedSharding(mesh, PartitionSpec()))


def host_replicas(params) -> list[list[np.ndarray]]:
    """The program's G replicas on the host, as float32 leaf lists, read
    shard by shard (no program runs for the copy)."""
    leaves = jax.tree.leaves(params)
    G = leaves[0].shape[0]
    full = []
    for leaf in leaves:
        host = np.empty(leaf.shape, np.float32)
        for shard in leaf.addressable_shards:
            host[shard.index] = np.asarray(shard.data, np.float32)
        full.append(host)
    return [[h[g] for h in full] for g in range(G)]
