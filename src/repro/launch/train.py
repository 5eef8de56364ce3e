"""End-to-end ByzSGD training driver (single-host; mesh = available devices).

Features exercised: the distributed protocol (pjit over the ('rep','fsdp',
'model') mesh), deterministic sharded data, checkpoint/restart (crash-safe,
elastic), Byzantine attack injection, DMC cadence, metrics logging.

Examples:
  # 8 fake devices, reduced arch, clean run
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --arch phi4-mini-3.8b --reduced \
      --steps 100 --groups 4 --mesh 4x2
  # with Byzantine workers
  ... --worker-attack alie --n-byz 1
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint import checkpointer as ck
from ..core import protocol
from ..core.attacks import ByzantineSpec
from ..data.pipeline import token_stream
from ..models import sharding as shrules
from ..models.registry import get_bundle
from ..optim.schedules import inverse_linear
from .mesh import make_mesh, make_byz_mesh, use_mesh
from .steps import train_rules


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--groups", type=int, default=None)
    ap.add_argument("--mesh", default=None, help="e.g. 4x2 (data x model)")
    ap.add_argument("--batch-per-group", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--T", type=int, default=10)
    ap.add_argument("--engine", default="sharded")
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--worker-attack", default=None)
    ap.add_argument("--server-attack", default=None)
    ap.add_argument("--n-byz", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    n_dev = jax.device_count()
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
    else:
        m = 1
        d = n_dev
    base = make_mesh((d, m), ("data", "model"))
    G = args.groups or d
    bmesh = make_byz_mesh(base, G)

    bundle = get_bundle(args.arch, reduced=args.reduced)
    byz = ByzantineSpec(worker_attack=args.worker_attack,
                        server_attack=args.server_attack,
                        n_byz_workers=args.n_byz if args.worker_attack else 0,
                        n_byz_servers=args.n_byz if args.server_attack else 0)
    pcfg = protocol.ProtocolConfig.derive(d * 1, d // G if G else 1,
                                          T=args.T, engine=args.engine,
                                          byz=byz)
    # derive() computes G from R//divisor; force exact:
    pcfg = protocol.ProtocolConfig(
        n_groups=G, f_workers=max((G - 1) // 3, 0),
        f_servers=max((G - 2) // 3, 0), q_workers=G - max((G - 1) // 3, 0),
        q_servers=max(G - max((G - 2) // 3, 0),
                      min(2 * max((G - 2) // 3, 0) + 2, G)),
        T=args.T, engine=args.engine, byz=byz)

    init = protocol.make_init_fn(bundle, pcfg)
    step = protocol.make_train_step(
        bundle, pcfg, inverse_linear(args.lr, 0.005),
        with_attack=bool(args.worker_attack or args.server_attack),
        mesh=bmesh)
    rules = train_rules(bmesh, bundle.cfg)

    with use_mesh(bmesh):
        shardings = protocol.state_shardings(
            jax.eval_shape(init, jax.random.PRNGKey(0)), bmesh,
            overrides=protocol.attn_overrides(bundle.cfg, bmesh))
        state = jax.jit(init)(jax.random.PRNGKey(0))
        state = jax.tree.map(jax.device_put, state, shardings)

        start = 0
        if args.ckpt_dir:
            latest = ck.latest_step(args.ckpt_dir)
            if latest is not None:
                state, start = ck.restore(args.ckpt_dir, latest, state,
                                          shardings=shardings)
                print(f"[train] restored checkpoint at step {start} "
                      f"(elastic re-shard onto {n_dev} devices)")

        def wrapped(state, batch):
            with shrules.sharding_rules(rules):
                return step(state, batch)

        jstep = jax.jit(wrapped, donate_argnums=0)
        stream = token_stream(0, bundle.cfg.vocab, G, args.batch_per_group,
                              args.seq, args.steps)
        bshard = NamedSharding(bmesh, P("rep"))
        t0 = time.time()
        for i, batch in enumerate(stream):
            if i < start:
                continue
            batch = jax.tree.map(lambda l: jax.device_put(l, bshard), batch)
            state = jstep(state, batch)
            if i % args.log_every == 0:
                p0 = jax.tree.map(lambda l: l[0], state.params)
                with shrules.sharding_rules(rules):
                    loss = float(bundle.loss(
                        p0, jax.tree.map(lambda x: x[0], batch)))
                print(f"[train] step {i:5d} loss {loss:8.4f} "
                      f"({time.time()-t0:.1f}s)")
            if args.ckpt_dir and i > 0 and i % args.ckpt_every == 0:
                ck.save(args.ckpt_dir, i, state)
                print(f"[train] checkpoint @ {i}")
        if args.ckpt_dir:
            ck.save(args.ckpt_dir, args.steps, state)
        p0 = protocol.consolidate(state.params, pcfg)
        n = sum(l.size for l in jax.tree.leaves(p0))
        print(f"[train] done: {args.steps} steps, {n/1e6:.1f}M params, "
              f"{time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
