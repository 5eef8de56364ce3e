"""Batched serving driver: prefill + decode over a ByzSGD-trained model.

Three model sources, by flag:

  * default — fresh init, vanilla DP x TP single-model serving;
  * ``--ckpt-dir`` — restore a replica-stacked ByzSGD checkpoint and
    median-consolidate it to one model (a Byzantine-suspect replica is
    outvoted at load time — checkpoint/checkpointer.py semantics);
  * ``--ckpt-dir --quorum`` — keep ALL restored replicas live and serve
    through :class:`repro.serve.QuorumService`: every token is a quorum
    read, so up to f Byzantine replicas cannot corrupt a continuation.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --arch phi4-mini-3.8b --reduced \
      --batch 4 --prefill 64 --decode 32 --mesh 4x2
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..models import sharding as shrules
from ..models.registry import get_bundle
from .mesh import make_mesh, make_serve_mesh, use_mesh
from .steps import serve_rules


def _serve_quorum(args, bundle, pool, rules):
    """--quorum path: all restored replicas live, every token a quorum read."""
    from ..serve import QuorumService
    B, S = args.batch, args.prefill
    svc = QuorumService(pool, bundle, n_slots=B,
                        max_len=S + args.decode + 1, rules=rules)
    pf = bundle.make_batch("prefill", B, S, jax.random.PRNGKey(1))
    prompts = [row.tolist() for row in jax.device_get(pf["tokens"])]
    t0 = time.time()
    outs = svc.generate(prompts, max_new=args.decode)
    wall = time.time() - t0
    rep = svc.report()
    print(f"[serve] quorum ({rep['rule']}): {rep['committed_tokens']} tokens "
          f"across {rep['n_replicas']} replicas (f={rep['f']}, "
          f"{rep['n_active']} active) in {wall:.2f}s "
          f"({rep['tok_s']:.1f} tok/s) | disagreement "
          f"{rep['disagreement_rate']:.4f} | ejections {rep['ejections']} | "
          f"retries {rep['retries']}")
    print(f"[serve] sample continuation ids: {outs[0][:10]}")
    return rep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prefill", type=int, default=64)
    ap.add_argument("--decode", type=int, default=32)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore + median-consolidate a ByzSGD checkpoint")
    ap.add_argument("--quorum", action="store_true",
                    help="with --ckpt-dir: serve every restored replica "
                         "behind quorum reads instead of consolidating")
    args = ap.parse_args(argv)

    n_dev = jax.device_count()
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
    else:
        d, m = n_dev, 1
    base = make_mesh((d, m), ("data", "model"))
    smesh = make_serve_mesh(base)

    bundle = get_bundle(args.arch, reduced=args.reduced)
    rules = serve_rules(smesh, bundle.cfg)

    with use_mesh(smesh):
        pool = None
        if args.ckpt_dir:
            from ..serve import ReplicaPool, checkpoint_groups
            step, R = checkpoint_groups(args.ckpt_dir)
            f = (R - 1) // 3   # the protocol's server tolerance for R groups
            pool = ReplicaPool.from_checkpoint(args.ckpt_dir, bundle.init,
                                               step=step, f=f)
            print(f"[serve] restored step {step}: {R} replicas (f={f}) "
                  f"from {args.ckpt_dir}")
            if args.quorum:
                return _serve_quorum(args, bundle, pool, rules)
            params = pool.consolidated()
            print("[serve] median-consolidated to one serving model")
        else:
            params = bundle.init(jax.random.PRNGKey(0))
        params = jax.tree.map(lambda l: l.astype(jnp.bfloat16)
                              if l.dtype == jnp.float32 else l, params)

        B, S = args.batch, args.prefill
        max_len = S + args.decode + 1
        caches = bundle.init_caches(B, max_len=max_len, n_chunks=max(m, 1))
        pf = bundle.make_batch("prefill", B, S, jax.random.PRNGKey(1))

        def prefill_fn(p, b, c):
            with shrules.sharding_rules(rules):
                return bundle.prefill(p, b, c)

        def decode_fn(p, c, b):
            with shrules.sharding_rules(rules):
                return bundle.decode(p, c, b)

        jprefill = jax.jit(prefill_fn)
        jdecode = jax.jit(decode_fn, donate_argnums=1)

        t0 = time.time()
        logits, caches = jprefill(params, pf, caches)
        logits.block_until_ready()
        t_pf = time.time() - t0
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out_tokens = [tok]
        t0 = time.time()
        for i in range(args.decode):
            batch = {"token": tok}
            if bundle.cfg.family == "vlm":
                batch = {"embeds": pf["embeds"][:, -1:],
                         "positions": pf["positions"][:, :, -1:] + i + 1}
            logits, caches = jdecode(params, caches, batch)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            out_tokens.append(tok)
        jax.block_until_ready(tok)
        t_dec = time.time() - t0
        total = B * args.decode
        print(f"[serve] {args.arch}: prefill {B}x{S} in {t_pf:.2f}s | "
              f"decode {args.decode} steps x batch {B} = {total} tokens in "
              f"{t_dec:.2f}s ({total / max(t_dec, 1e-9):.1f} tok/s on "
              f"{n_dev} host devices)")
        sample = jnp.concatenate(out_tokens, axis=1)[0, :10]
        print(f"[serve] sample continuation ids: {sample.tolist()}")


if __name__ == "__main__":
    main()
