"""RWKV6 Finch in the model zoo: the chunked WKV against the token recurrence
(values and gradients) and the ``wkv`` scope of its backward, and prefill
then decode through ``RwkvCache`` against the full forward pass's logits."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models import rwkv6
from repro.models.registry import get_bundle

KEY = jax.random.PRNGKey(11)


def _recurrence(r, k, v, lw, u, s0):
    """y_t = r_t (S_{t-1} + diag(u) k_t^T v_t), S_t = diag(e^lw_t) S_{t-1}
    + k_t^T v_t, one token at a time."""
    def step(s, x):
        rt, kt, vt, lt = x
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        y = jnp.einsum("bhk,bhkv->bhv", rt, s + u[None, :, :, None] * kv)
        return jnp.exp(lt)[..., None] * s + kv, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, lw))
    s, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1), s


def _inputs(S, decay):
    B, H, K = 2, 3, 8
    ks = jax.random.split(KEY, 6)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, K)) for i in range(3))
    if decay == "near_floor":   # log decays around the floor, some below it
        lw = -20.0 + 2.0 * jax.random.normal(ks[3], (B, S, H, K))
        lw = jnp.maximum(lw, rwkv6.LOG_DECAY_FLOOR)
    else:
        lw = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H, K)))
    u = 0.3 * jax.random.normal(ks[4], (H, K))
    s0 = jax.random.normal(ks[5], (B, H, K, K))
    return r, k, v, lw, u, s0


# S a multiple of the chunk (16) and not, over a few chunks and over many
# (300: 19 chunks, the last cut short); decays moderate and at the floor
@pytest.mark.parametrize("S", [48, 37, 300])
@pytest.mark.parametrize("decay", ["moderate", "near_floor"])
def test_wkv_chunked_matches_the_recurrence(S, decay):
    args = _inputs(S, decay)
    y, s = rwkv6.wkv_chunked(*args)
    y_ref, s_ref = _recurrence(*args)
    # float32 on both sides; the chunked form sums in another order
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, s_ref, rtol=2e-4, atol=2e-4)

    cy, cs = (jax.random.normal(jax.random.fold_in(KEY, i), a.shape)
              for i, a in enumerate((y, s)))

    def scalar(fn):
        def f(*a):
            yy, ss = fn(*a)
            return jnp.sum(yy * cy) + jnp.sum(ss * cs)
        return f

    g = jax.grad(scalar(rwkv6.wkv_chunked), argnums=range(6))(*args)
    g_ref = jax.grad(scalar(_recurrence), argnums=range(6))(*args)
    # relative to the largest gradient of the call: the chunked form reaches
    # lw through the transpose of a cumulative sum whose terms cancel, so
    # its rounding scales with the cotangents, not with lw's own gradient
    # (about e^-14 near the floor, where both read ~1e-6 of noise)
    scale = max(float(jnp.max(jnp.abs(b))) for b in g_ref)
    for name, a, b in zip(("r", "k", "v", "lw", "u", "s0"), g, g_ref):
        np.testing.assert_allclose(a / scale, b / scale, rtol=0, atol=3e-5,
                                   err_msg=name)


def _in_scope(op_name: str, scope: str) -> bool:
    """``scope`` is a component of the op_name's path, a transform's
    wrapper (``transpose(jvp(...))``) taken off."""
    for part in op_name.split(";", 1)[0].split("/")[:-1]:
        while (m := re.fullmatch(r"[\w.<>]*\((.*)\)", part)):
            part = m.group(1)
        if part == scope:
            return True
    return False


def test_the_wkv_backward_keeps_its_scope():
    """The gradient of one time-mix block, compiled: the WKV's backward ops
    (op_names under ``transpose(``), its loop over the chunks and the
    exponentials of the decays it recomputes, carry ``wkv`` in their path,
    so that a reading of the scope counts the backward as well."""
    cfg = get_bundle("rwkv6-1.6b", reduced=True, act_dtype="float32",
                     d_ff=448).cfg
    p = rwkv6.init_block(jax.random.fold_in(KEY, 3), cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 37, cfg.d_model))

    def f(p, x):
        return jnp.sum(rwkv6.time_mix(p, x, cfg, jnp.float32, None)[0])

    text = jax.jit(jax.grad(f)).lower(p, x).compile().as_text()
    backward = {n for n in re.findall(r'op_name="([^"]*)"', text)
                if "transpose(" in n}
    wkv = [n for n in backward
           if "/while" in n or n.split(";", 1)[0].endswith("/exp")]
    assert any("/while" in n for n in wkv) and any("exp" in n for n in wkv)
    assert all(_in_scope(n, "wkv") for n in wkv), wkv


def test_groupnorm_normalises_each_group():
    x = jax.random.normal(KEY, (2, 5, 12)) * 3.0 + 1.0
    p = L.init_layernorm(12)
    y = L.groupnorm(p, x, 3, eps=1e-6).reshape(2, 5, 3, 4)
    np.testing.assert_allclose(jnp.mean(y, -1), 0.0, atol=1e-5)
    np.testing.assert_allclose(jnp.var(y, -1), 1.0, atol=1e-4)


@pytest.mark.parametrize("S0", [16, 21])
def test_prefill_then_decode_matches_the_full_forward(S0):
    """Logits of a prefill of S0 tokens and of each token decoded after it
    equal the full forward pass's logits at those positions: ddlerp, ln0,
    the GroupNorm and the untied head through ``RwkvCache``."""
    bundle = get_bundle("rwkv6-1.6b", reduced=True, act_dtype="float32",
                        d_ff=448)
    cfg = bundle.cfg
    assert not cfg.tie_embeddings
    params = bundle.init(jax.random.fold_in(KEY, 1))
    # the LoRAs' first factors start at zero: make ddlerp data-dependent
    blocks = dict(params["blocks"])
    for name in ("maa_w1", "wB"):
        blocks[name] = 0.1 * jax.random.normal(
            jax.random.fold_in(KEY, len(name)), blocks[name].shape)
    params = dict(params, blocks=blocks)
    n_dec, B = 4, 2
    toks = jax.random.randint(jax.random.fold_in(KEY, 2), (B, S0 + n_dec),
                              0, cfg.vocab)
    hidden = rwkv6.forward(params, toks, cfg=cfg, remat=False)
    want = L.unembed(params["head"], hidden)                  # [B, S, V]

    caches = bundle.init_caches(B, max_len=S0 + n_dec, dtype=jnp.float32)
    got, caches = bundle.prefill(params, {"tokens": toks[:, :S0]}, caches)
    np.testing.assert_allclose(got, want[:, S0 - 1], rtol=1e-4, atol=1e-4)
    for i in range(n_dec):
        got, caches = bundle.decode(params, caches,
                                    {"token": toks[:, S0 + i:S0 + i + 1]})
        np.testing.assert_allclose(got, want[:, S0 + i], rtol=1e-4,
                                   atol=1e-4, err_msg=f"decode step {i}")


def test_finch_1b6_config():
    from repro.configs import rwkv6_1b6
    c = rwkv6_1b6.CONFIG
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab, c.ssm_head_dim,
            c.tie_embeddings) == (24, 2048, 7168, 65536, 64, False)
    assert rwkv6.dims(c) == (32, 64)
