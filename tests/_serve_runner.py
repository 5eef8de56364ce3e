"""Subprocess body for multi-device serve tests (8 forced host devices, set
before jax initialises — hence not in-process). Gates two things the 1-device
suite cannot: rep>1 protocol meshes emitting replica-stacked checkpoints, and
multi-replica quorum serving under serve-mesh sharding rules."""
import os
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.exp as exp  # noqa: E402
from repro.checkpoint import checkpointer as ck  # noqa: E402
from repro.core.attacks import ByzantineSpec  # noqa: E402
from repro.launch.mesh import (make_mesh, make_serve_mesh,  # noqa: E402
                               use_mesh)
from repro.launch.steps import serve_rules  # noqa: E402
from repro.models.registry import get_bundle  # noqa: E402
from repro.serve import QuorumService, ReplicaPool  # noqa: E402


def main():
    assert jax.device_count() == 8

    # 1. protocol training on a rep=5 multi-device mesh emits replica-stacked
    #    checkpoints that restore straight into a pool
    with tempfile.TemporaryDirectory() as td:
        d = os.path.join(td, "ck")
        res = exp.run("serve/ckpt_smoke", ckpt_dir=d)
        assert res.provenance["mesh"]["rep"] == 5, res.provenance["mesh"]
        assert ck.latest_step(d) == exp.get("serve/ckpt_smoke").steps
        e = exp.get("serve/ckpt_smoke")
        init_fn, _, _ = e.build_problem()
        pool = ReplicaPool.from_checkpoint(d, init_fn, f=1)
        assert pool.n_replicas == 5
        assert all(bool(jnp.all(jnp.isfinite(l)))
                   for l in jax.tree.leaves(pool.params))
        for a, b in zip(jax.tree.leaves(pool.params),
                        jax.tree.leaves(res.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("protocol ckpt on rep=5 mesh OK")

    # 2. multi-replica transformer serving under the serve mesh's sharding
    #    rules: 1-of-4 Byzantine, continuations token-identical to honest
    base = make_mesh((4, 2), ("data", "model"))
    smesh = make_serve_mesh(base)
    bundle = get_bundle("phi4-mini-3.8b", reduced=True)
    rules = serve_rules(smesh, bundle.cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    prompts = [[3, 5, 7, 9], [11, 2, 4, 6]]
    with use_mesh(smesh):
        svc1 = QuorumService(ReplicaPool.from_params(params, 1, f=0), bundle,
                             n_slots=2, max_len=32, rules=rules)
        honest = svc1.generate(prompts, max_new=5)
        pool4 = ReplicaPool.from_params(params, 4, f=1).corrupt(
            ByzantineSpec(server_attack="reversed", n_byz_servers=1),
            jax.random.PRNGKey(7))
        svc4 = QuorumService(pool4, bundle, n_slots=2, max_len=32,
                             rules=rules)
        outs = svc4.generate(prompts, max_new=5)
    assert outs == honest, (outs, honest)
    rep = svc4.report()
    assert [i for _, i in rep["ejections"]] == [3]
    print(f"quorum serve on {jax.device_count()} devices OK "
          f"(tok/s {rep['tok_s']:.1f}, ejected {rep['ejections']})")
    print("SERVE_TESTS_PASS")


if __name__ == "__main__":
    main()
