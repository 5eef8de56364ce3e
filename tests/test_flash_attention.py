"""Pallas flash-attention kernel vs the naive oracle: shapes x dtypes x
masking modes (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

KEY = jax.random.PRNGKey(0)

CASES = [
    # B, S, H, kvH, hd, causal, window, qb, kb
    (2, 37, 4, 2, 16, True, 0, 8, 16),
    (1, 64, 4, 4, 32, True, 7, 16, 16),
    (2, 50, 6, 2, 64, False, 0, 16, 8),
    (1, 130, 8, 8, 128, True, 0, 64, 64),
    (3, 24, 2, 1, 8, True, 0, 8, 8),
]


@pytest.mark.parametrize("B,S,H,kvH,hd,causal,window,qb,kb", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(B, S, H, kvH, hd, causal, window, qb, kb, dtype):
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, kvH, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, kvH, hd), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window, q_block=qb,
                          kv_block=kb, interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window)
    tol = 3e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=tol, atol=tol)


def test_flash_decode_shape():
    """Sq=1 against a long prefix (decode-style query)."""
    B, Skv, H, hd = 2, 96, 4, 32
    q = jax.random.normal(jax.random.fold_in(KEY, 4), (B, 1, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 5), (B, Skv, H, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (B, Skv, H, hd))
    got = flash_attention(q, k, v, causal=True, q_block=8, kv_block=32,
                          interpret=True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# gradient path: the custom_vjp backward kernels vs jax.grad of the oracle
# ---------------------------------------------------------------------------

GRAD_CASES = [
    # B, S, H, kvH, hd, causal, window, qb, kb — training shapes: causal,
    # GQA, sliding window, non-block-multiple lengths, a decoder-free case
    (2, 64, 4, 2, 32, True, 0, 64, 64),
    (1, 100, 4, 4, 32, True, 0, 32, 32),
    (2, 64, 4, 2, 32, True, 32, 64, 32),
    (2, 48, 4, 4, 16, False, 0, 16, 16),
]


def _grads(fn, q, k, v, do):
    def scalar(q, k, v):
        return jnp.vdot(fn(q, k, v).astype(jnp.float32),
                        do.astype(jnp.float32))

    return jax.grad(scalar, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("B,S,H,kvH,hd,causal,window,qb,kb", GRAD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_grads_match_ref(B, S, H, kvH, hd, causal, window, qb, kb,
                               dtype):
    """dq/dk/dv from the Pallas backward kernels match jax.grad through the
    naive reference — bf16 inputs ride f32 kernel accumulation, so the bf16
    tolerance is one rounding step, not a looser algorithm."""
    q = (0.5 * jax.random.normal(jax.random.fold_in(KEY, 7),
                                 (B, S, H, hd))).astype(dtype)
    k = (0.5 * jax.random.normal(jax.random.fold_in(KEY, 8),
                                 (B, S, kvH, hd))).astype(dtype)
    v = (0.5 * jax.random.normal(jax.random.fold_in(KEY, 9),
                                 (B, S, kvH, hd))).astype(dtype)
    do = (0.5 * jax.random.normal(jax.random.fold_in(KEY, 10),
                                  (B, S, H, hd))).astype(dtype)
    got = _grads(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        window=window, q_block=qb,
                                        kv_block=kb, interpret=True),
        q, k, v, do)
    want = _grads(
        lambda q, k, v: attention_ref(q, k, v, causal=causal, window=window),
        q, k, v, do)
    tol = 3e-3 if dtype == jnp.float32 else 5e-2
    for name, g, w in zip("qkv", got, want):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        rel = np.max(np.abs(g - w)) / (np.max(np.abs(w)) + 1e-6)
        assert rel < tol, f"d{name}: rel err {rel:.2e} (tol {tol})"


def test_flash_grad_dtypes():
    """Gradients come back in the input dtype (bf16 in -> bf16 grads)."""
    B, S, H, hd = 1, 32, 2, 16
    q = jax.random.normal(KEY, (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(KEY, (B, S, H, hd), jnp.bfloat16)
    v = jax.random.normal(KEY, (B, S, H, hd), jnp.bfloat16)
    dq, dk, dv = _grads(
        lambda q, k, v: flash_attention(q, k, v, interpret=True, q_block=16,
                                        kv_block=16),
        q, k, v, jnp.ones((B, S, H, hd), jnp.bfloat16))
    assert dq.dtype == dk.dtype == dv.dtype == jnp.bfloat16


def test_flash_sharded_under_mesh_rules():
    """Under a multi-device mesh the kernels run per shard, laid out by the
    installed activation rules; every layout matches the reference, forward
    and backward (subprocess: 4 forced host devices)."""
    import os
    import subprocess
    import sys
    here = os.path.dirname(__file__)
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.abspath(os.path.join(here, "..", "src"))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(here, "_flash_mesh_runner.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FLASH_MESH_PASS" in out.stdout, out.stdout
