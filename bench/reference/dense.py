"""Plain reference of a dense decoder (phi4-mini-3.8b family): float32,
straightforward ``jax.numpy``, no kernels, no remat, no sharding.

Per layer: RMSNorm, grouped-query attention with RoPE (rotate-half on the
first ``partial_rotary_factor`` of each head), causal softmax over the full
[S, S] scores, output projection, residual; RMSNorm, SwiGLU MLP, residual.
A final RMSNorm and the tied embedding table as the output head; the loss
is the mean next-token cross-entropy. Call under
``jax.default_matmul_precision("highest")``.

``init_params`` makes the weights that both the program and this reference
start from, from a key alone, laid out as the program's parameter tree:
matrices truncated-normal(-2, 2) / sqrt(fan_in), the table normal x 0.02,
norm scales 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _sizes(c):
    D, H = c["hidden_size"], c["num_attention_heads"]
    return (D, c["intermediate_size"], H, c["num_key_value_heads"],
            c.get("head_dim") or D // H, c["vocab_size"],
            c["num_hidden_layers"])


def init_params(key, c):
    D, F, H, kvH, hd, V, L = _sizes(c)
    ks = iter(jax.random.split(key, 8))

    def dense(fan_in, *shape):
        return (jax.random.truncated_normal(next(ks), -2.0, 2.0, shape)
                / np.sqrt(fan_in)).astype(jnp.float32)

    ones = jnp.ones((L, D), jnp.float32)
    return {
        "embed": {"table": 0.02 * jax.random.normal(next(ks), (V, D),
                                                    jnp.float32)},
        "blocks": {
            "ln_attn": {"scale": ones},
            "attn": {"wq": dense(D, L, D, H * hd),
                     "wk": dense(D, L, D, kvH * hd),
                     "wv": dense(D, L, D, kvH * hd),
                     "wo": dense(H * hd, L, H * hd, D)},
            "ln_mlp": {"scale": ones},
            "mlp": {"w_gate": dense(D, L, D, F), "w_up": dense(D, L, D, F),
                    "w_down": dense(F, L, F, D)},
        },
        "ln_f": {"scale": jnp.ones((D,), jnp.float32)},
    }


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta, rot):
    """Rotate-half RoPE on the first ``rot`` dims of each head; x [b,S,h,hd]."""
    S = x.shape[1]
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot)
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None]       # [S, rot/2]
    cos = jnp.asarray(np.cos(ang))[None, :, None]
    sin = jnp.asarray(np.sin(ang))[None, :, None]
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out, rest], -1)


def loss(params, tokens, labels, c):
    """Mean next-token cross-entropy of rows ``tokens`` [b, S]."""
    D, F, H, kvH, hd, V, L = _sizes(c)
    eps = c["rms_norm_eps"]
    rot = int(hd * c["partial_rotary_factor"])
    b, S = tokens.shape
    table = params["embed"]["table"]
    x = table[tokens]
    causal = np.tril(np.ones((S, S), bool))
    for l in range(L):
        p = jax.tree.map(lambda a: a[l], params["blocks"])
        h = _rmsnorm(x, p["ln_attn"]["scale"], eps)
        q = (h @ p["attn"]["wq"]).reshape(b, S, H, hd)
        k = (h @ p["attn"]["wk"]).reshape(b, S, kvH, hd)
        v = (h @ p["attn"]["wv"]).reshape(b, S, kvH, hd)
        q, k = _rope(q, c["rope_theta"], rot), _rope(k, c["rope_theta"], rot)
        k, v = jnp.repeat(k, H // kvH, 2), jnp.repeat(v, H // kvH, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + o.reshape(b, S, H * hd) @ p["attn"]["wo"]
        h = _rmsnorm(x, p["ln_mlp"]["scale"], eps)
        m = p["mlp"]
        x = x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
    h = _rmsnorm(x, params["ln_f"]["scale"], eps)
    logits = h @ table.T
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)
