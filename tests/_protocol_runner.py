"""Subprocess body for distributed-protocol tests (needs 8 forced devices,
which must be set before jax initialises — hence not in-process)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import protocol  # noqa: E402
from repro.core.attacks import ByzantineSpec  # noqa: E402
from repro.launch.mesh import (make_mesh, make_byz_mesh,  # noqa: E402
                               use_mesh)
from repro.models.registry import get_bundle  # noqa: E402
from repro.optim.schedules import inverse_linear  # noqa: E402


def main():
    mesh = make_mesh((4, 2), ("data", "model"))
    bmesh = make_byz_mesh(mesh, n_groups=4)
    bundle = get_bundle("phi4-mini-3.8b", reduced=True)

    for engine in ("naive", "sharded"):
        pcfg = protocol.ProtocolConfig.derive(4, T=3, engine=engine)
        init = protocol.make_init_fn(bundle, pcfg)
        step = protocol.make_train_step(bundle, pcfg,
                                        inverse_linear(0.05, 0.01), mesh=bmesh)
        with use_mesh(bmesh):
            state = jax.jit(init)(jax.random.PRNGKey(0))
            shardings = protocol.state_shardings(
                jax.eval_shape(init, jax.random.PRNGKey(0)), bmesh,
                overrides=protocol.attn_overrides(bundle.cfg, bmesh))
            state = jax.tree.map(jax.device_put, state, shardings)
            G, B, S = 4, 2, 16
            batch = bundle.make_batch("train", G * B, S, jax.random.PRNGKey(1))
            batch = jax.tree.map(
                lambda l: jax.device_put(
                    l.reshape((G, B) + l.shape[1:]),
                    NamedSharding(bmesh, P("rep"))), batch)
            jstep = jax.jit(step, donate_argnums=0)
            losses = []
            for _ in range(7):
                p0 = jax.tree.map(lambda l: l[0], state.params)
                losses.append(float(bundle.loss(
                    p0, jax.tree.map(lambda x: x[0], batch))))
                state = jstep(state, batch)
            assert losses[-1] < losses[0] - 0.2, (engine, losses)
            assert all(bool(jnp.all(jnp.isfinite(l)))
                       for l in jax.tree.leaves(state.params)), engine
            # consolidate for serving: median over replicas
            served = protocol.consolidate(state.params, pcfg)
            assert jax.tree.leaves(served)[0].shape == \
                jax.tree.leaves(state.params)[0].shape[1:]
            print(f"{engine}: loss {losses[0]:.3f} -> {losses[-1]:.3f} OK")

    # Byzantine run: reversed gradients from 1 group, with attack injection
    pcfg = protocol.ProtocolConfig.derive(
        4, T=3, byz=ByzantineSpec(worker_attack="reversed", n_byz_workers=1))
    init = protocol.make_init_fn(bundle, pcfg)
    step = protocol.make_train_step(bundle, pcfg, inverse_linear(0.05, 0.01),
                                    with_attack=True, mesh=bmesh)
    with use_mesh(bmesh):
        state = jax.jit(init)(jax.random.PRNGKey(0))
        G, B, S = 4, 2, 16
        batch = bundle.make_batch("train", G * B, S, jax.random.PRNGKey(1))
        batch = jax.tree.map(
            lambda l: l.reshape((G, B) + l.shape[1:]), batch)
        jstep = jax.jit(step, donate_argnums=0)
        l0 = float(bundle.loss(jax.tree.map(lambda l: l[0], state.params),
                               jax.tree.map(lambda x: x[0], batch)))
        for _ in range(7):
            state = jstep(state, batch)
        l1 = float(bundle.loss(jax.tree.map(lambda l: l[0], state.params),
                               jax.tree.map(lambda x: x[0], batch)))
        assert l1 < l0 - 0.2, ("byzantine", l0, l1)
        print(f"byzantine(MDA): loss {l0:.3f} -> {l1:.3f} OK")
    print("PROTOCOL_TESTS_PASS")


if __name__ == "__main__":
    main()
