"""Plain reference of RWKV-6 "Finch" (arXiv:2404.05892; RWKV-LM's
``RWKV_Tmix_x060`` and ``RWKV_CMix_x060``): float32, straightforward
``jax.numpy``, no kernels, no chunked algebra, no sharding. Call under
``jax.default_matmul_precision("highest")``.

The embedding goes through a LayerNorm (``ln0``). Per layer, on
h = LayerNorm(x), with xx = shift(h) - h (the previous token, zero before the
first):

- ddlerp: m = tanh((h + xx maa_x) W1), split into five rank-R parts, each
  times its W2; x_* = h + xx (maa_* + m_*) for * in w, k, v, r, g;
- r, k, v = x_r Wr, x_k Wk, x_v Wv in H heads of K; g = silu(x_g Wg);
  w = w0 + tanh(x_w A) B, the decay exp(-exp(w));
- the WKV recurrence, token by token, per head:
  y_t = r_t (S_{t-1} + diag(u) k_t^T v_t),
  S_t = diag(exp(-exp(w_t))) S_{t-1} + k_t^T v_t, S_0 = 0;
- GroupNorm of y over the H heads (eps = layer_norm_epsilon *
  head_size_divisor^2), times g, times Wo, added to x;
- channel mix on h = LayerNorm(x): x += sigmoid(x_r cWr) *
  (relu(x_k cWk)^2 cWv), with x_* = h + xx mu_c*.

A final LayerNorm and the untied head give the logits; the loss is the mean
next-token cross-entropy. The recurrence runs as a ``lax.scan`` over the
positions; the scan is cut into checkpointed blocks of positions, and each
layer is checkpointed, so that a gradient at 2048 positions fits one chip.
That changes what is kept for the backward pass, not what is computed.

The program departs from this in one place: it takes a log decay below
-20 (a decay below e^-20) as -20. Its WKV is a chunked form of the same
recurrence, in bfloat16 inputs and float32 state.

``init_params`` makes the weights that both the program and this reference
start from, from a key alone, laid out as the program's parameter tree:
matrices truncated-normal(-2, 2) / sqrt(fan_in) (the LoRAs' second factors
times 0.1), mixes uniform in [0, 1), the base decay w0 as RWKV-LM sets it
(-6 + 5 (i / (D - 1))^(0.7 + 1.3 l / (L - 1)) for channel i of layer l),
u normal x 0.1, the embedding normal x 0.02, norm scales 1 and biases 0.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MIXES = ("w", "k", "v", "r", "g")
BLOCK = 64                              # positions per checkpointed block


def _sizes(c):
    D, K = c["hidden_size"], c["head_size"]
    a = c["assumed"]
    return (D, c["attention_hidden_size"] // K, K, c["intermediate_size"],
            c["vocab_size"], c["num_hidden_layers"], a["time_mix_extra_dim"],
            a["time_decay_extra_dim"])


def init_params(key, c):
    D, H, K, F, V, L, R, Rd = _sizes(c)
    ks = iter(jax.random.split(key, 24))

    def dense(fan_in, *shape, scale=1.0):
        return (scale * jax.random.truncated_normal(next(ks), -2.0, 2.0,
                                                    (L,) + shape)
                / np.sqrt(fan_in)).astype(jnp.float32)

    def mix():
        return jax.random.uniform(next(ks), (L, D), jnp.float32)

    def norm(*lead):
        return {"scale": jnp.ones(lead + (D,), jnp.float32),
                "bias": jnp.zeros(lead + (D,), jnp.float32)}

    i = np.arange(D) / (D - 1)
    layer = np.arange(L)[:, None] / max(L - 1, 1)
    w0 = -6.0 + 5.0 * i[None] ** (0.7 + 1.3 * layer)
    blocks = {
        "ln1": norm(L), "ln2": norm(L),
        "maa_x": mix(), **{f"maa_{m}": mix() for m in MIXES},
        "maa_w1": dense(D, D, len(MIXES) * R),
        "maa_w2": dense(R, len(MIXES), R, D, scale=0.1),
        "Wr": dense(D, D, D), "Wk": dense(D, D, D), "Wv": dense(D, D, D),
        "Wg": dense(D, D, D),
        "w0": jnp.asarray(w0, jnp.float32),
        "wA": dense(D, D, Rd), "wB": dense(Rd, Rd, D, scale=0.1),
        "u": 0.1 * jax.random.normal(next(ks), (L, H, K), jnp.float32),
        "ln_x": norm(L),
        "Wo": dense(D, D, D),
        "mu_ck": mix(), "mu_cr": mix(),
        "cWk": dense(D, D, F), "cWv": dense(F, F, D), "cWr": dense(D, D, D),
    }
    return {
        "embed": {"table": 0.02 * jax.random.normal(next(ks), (V, D),
                                                    jnp.float32)},
        "ln0": norm(),
        "blocks": blocks,
        "ln_f": norm(),
        "head": {"table": (jax.random.truncated_normal(
            next(ks), -2.0, 2.0, (V, D)) / np.sqrt(D)).astype(jnp.float32)},
    }


def _layernorm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _groupnorm(p, x, groups, eps):
    g = x.reshape(*x.shape[:-1], groups, -1)
    mu = jnp.mean(g, -1, keepdims=True)
    var = jnp.mean((g - mu) ** 2, -1, keepdims=True)
    return ((g - mu) / jnp.sqrt(var + eps)).reshape(x.shape) * p["scale"] \
        + p["bias"]


def _shift_diff(h):
    """shift(h) - h over the positions of [b, S, D]."""
    prev = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return prev - h


def wkv(r, k, v, w, u):
    """The recurrence of one row: r, k, v, w [S, H, K] (w the log-log decay),
    u [H, K] -> y [S, H, K], S_0 = 0."""
    S, H, K = r.shape
    decay = jnp.exp(-jnp.exp(w))

    def step(s, x):
        rt, kt, vt, dt = x
        kv = kt[:, :, None] * vt[:, None, :]                  # [H, K, V]
        y = jnp.einsum("hk,hkv->hv", rt, s + u[:, :, None] * kv)
        return dt[:, :, None] * s + kv, y

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(step, s, xs)

    n = math.gcd(S, BLOCK)
    xs = tuple(a.reshape(S // n, n, H, K) for a in (r, k, v, decay))
    _, y = jax.lax.scan(block, jnp.zeros((H, K, K), r.dtype), xs)
    return y.reshape(S, H, K)


def _layer(p, x, c):
    D, H, K, F, V, L, R, Rd = _sizes(c)
    eps = c["layer_norm_epsilon"]
    b, S, _ = x.shape
    h = _layernorm(p["ln1"], x, eps)
    xx = _shift_diff(h)
    m = jnp.tanh((h + xx * p["maa_x"]) @ p["maa_w1"])
    m = jnp.einsum("bsfr,frd->fbsd", m.reshape(b, S, len(MIXES), R),
                   p["maa_w2"])
    xs = {n: h + xx * (p[f"maa_{n}"] + m[i]) for i, n in enumerate(MIXES)}
    r, k, v = ((xs[n] @ p[f"W{n}"]).reshape(b, S, H, K) for n in "rkv")
    g = jax.nn.silu(xs["g"] @ p["Wg"])
    w = (p["w0"] + jnp.tanh(xs["w"] @ p["wA"]) @ p["wB"]).reshape(b, S, H, K)
    y = jax.vmap(wkv, (0, 0, 0, 0, None))(r, k, v, w, p["u"])
    y = _groupnorm(p["ln_x"], y.reshape(b, S, D), H,
                   eps * c["head_size_divisor"] ** 2)
    x = x + (y * g) @ p["Wo"]
    h = _layernorm(p["ln2"], x, eps)
    xx = _shift_diff(h)
    kk = jax.nn.relu((h + xx * p["mu_ck"]) @ p["cWk"]) ** 2
    rr = jax.nn.sigmoid((h + xx * p["mu_cr"]) @ p["cWr"])
    return x + rr * (kk @ p["cWv"])


def loss(params, tokens, labels, c):
    """Mean next-token cross-entropy of rows ``tokens`` [b, S]."""
    eps = c["layer_norm_epsilon"]
    x = _layernorm(params["ln0"], params["embed"]["table"][tokens], eps)
    layer = jax.checkpoint(lambda p, x: _layer(p, x, c))
    for l in range(c["num_hidden_layers"]):
        x = layer(jax.tree.map(lambda a: a[l], params["blocks"]), x)
    h = _layernorm(params["ln_f"], x, eps)
    logits = h @ params["head"]["table"].T
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)
