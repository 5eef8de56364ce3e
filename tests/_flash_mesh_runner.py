"""Subprocess body for the sharded flash-attention check (needs 4 forced
host devices, which must be set before jax initialises).

``models.layers`` runs the flash kernels per shard inside a ``shard_map``
laid out by the installed ``act_heads``/``act_kv_heads`` rules. On a
``(fsdp=2, model=2)`` mesh, forward output and dq/dk/dv must match the
reference attention for each layout the rules can ask for: q and kv heads
both split, q heads split with kv heads whole (GQA, kv heads not divisible),
and no rules installed.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.kernels.flash_attention.ref import attention_ref  # noqa: E402
from repro.launch.mesh import make_mesh, use_mesh  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models.sharding import sharding_rules  # noqa: E402

HEADS = P("fsdp", None, "model", None)
CASES = [
    # name, H, kvH, installed rules
    ("q+kv heads split", 4, 2, {"act_heads": HEADS, "act_kv_heads": HEADS}),
    ("q heads split, kv whole", 8, 2, {"act_heads": HEADS}),
    ("q heads split, one kv head", 4, 1, {"act_heads": HEADS}),
    ("no rules", 4, 2, {}),
]


def _grads(fn, q, k, v, do):
    def scalar(q, k, v):
        return jnp.vdot(fn(q, k, v).astype(jnp.float32), do)
    return jax.grad(scalar, argnums=(0, 1, 2))(q, k, v)


def main():
    assert jax.device_count() == 4
    mesh = make_mesh((2, 2), ("fsdp", "model"))
    B, S, hd = 2, 64, 32
    for name, H, kvH, specs in CASES:
        ks = jax.random.split(jax.random.PRNGKey(H * 10 + kvH), 4)
        q = 0.5 * jax.random.normal(ks[0], (B, S, H, hd))
        k = 0.5 * jax.random.normal(ks[1], (B, S, kvH, hd))
        v = 0.5 * jax.random.normal(ks[2], (B, S, kvH, hd))
        do = jax.random.normal(ks[3], (B, S, H, hd))
        rules = {n: NamedSharding(mesh, s) for n, s in specs.items()}

        def flash(q, k, v):
            with sharding_rules(rules):
                return L._flash_attention(q, k, v, causal=True, window=0,
                                          q_block=32, kv_block=32)

        with use_mesh(mesh):
            fwd = jax.jit(flash)(q, k, v)
            got = jax.jit(lambda *a: _grads(flash, *a))(q, k, v, do)
            hlo = jax.jit(flash).lower(q, k, v).as_text()
        assert "shard_map" in hlo or "manual" in hlo, name
        want = _grads(attention_ref, q, k, v, do)
        np.testing.assert_allclose(fwd, attention_ref(q, k, v),
                                   rtol=3e-4, atol=3e-4, err_msg=name)
        for g_name, g, w in zip("qkv", got, want):
            g, w = np.asarray(g), np.asarray(w)
            rel = np.max(np.abs(g - w)) / (np.max(np.abs(w)) + 1e-6)
            assert rel < 3e-3, f"{name}: d{g_name} rel err {rel:.2e}"
        print(f"{name}: ok")
    print("FLASH_MESH_PASS")


if __name__ == "__main__":
    main()
