"""Mamba2 (SSD) block — chunked selective-state-space scan in pure JAX.

State recurrence (per head h, headdim P, state N):
    h_t = a_t * h_{t-1} + (dt_t * x_t) outer B_t,   a_t = exp(-exp(A_log)*dt_t)
    y_t = C_t . h_t + D_skip * x_t
Chunked closed form: lax.scan over chunks carrying the [B, H, P, N] state; the
intra-chunk term is a masked [C, C] decay matrix per head (scalar decay => no
K-dim blowup), the inter-chunk term a single state contraction. Per-chunk
transients stay at tens of MB (DESIGN.md §Arch notes).

Decode is the O(1)-state single-token recurrence — this is what makes
500k-token decode sub-quadratic for zamba2 (and rwkv6, see rwkv6.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ArchConfig
from .sharding import shard

LOG_DECAY_FLOOR = -20.0  # exp(-20) ~ 2e-9: numerically zero decay, overflow-safe


class MambaCache(NamedTuple):
    conv: jax.Array   # [B, convw-1, conv_channels] rolling window
    ssm: jax.Array    # [B, H, P, N]


def dims(cfg: ArchConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    P = cfg.ssm_head_dim
    H = d_inner // P
    N = cfg.ssm_state
    return d_inner, H, P, N


def init_mamba_block(key, cfg: ArchConfig):
    d_inner, H, P, N = dims(cfg)
    D = cfg.d_model
    conv_ch = d_inner + 2 * N
    ks = jax.random.split(key, 4)
    return {
        "ln": L.init_rmsnorm(D),
        "in_proj": L._init_dense(ks[0], D, D, 2 * d_inner + 2 * N + H),
        "conv_w": (0.1 * jax.random.normal(ks[1], (cfg.ssm_conv, conv_ch))).astype(jnp.float32),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "A_log": jnp.zeros((H,), jnp.float32),          # exp(0)=1 decay rate
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),   # softplus(-2) ~ 0.13
        "D_skip": jnp.ones((H,), jnp.float32),
        "gate_ln": L.init_rmsnorm(d_inner),
        "out_proj": L._init_dense(ks[2], d_inner, d_inner, D),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv via static shifts. x: [B, S, C]; w: [K, C].
    state: [B, K-1, C] previous tokens (decode) or None (train, zero history).
    Returns (y, new_state)."""
    K = w.shape[0]
    B, S, C = x.shape
    if state is None:
        state = jnp.zeros((B, K - 1, C), x.dtype)
    xx = jnp.concatenate([state, x], axis=1)  # [B, S+K-1, C]
    y = sum(xx[:, i:i + S] * w[K - 1 - i].astype(x.dtype) for i in range(K))
    new_state = xx[:, -(K - 1):] if K > 1 else state
    return jax.nn.silu(y + b.astype(x.dtype)), new_state


def _split_proj(p, x, cfg: ArchConfig, dtype):
    d_inner, H, P, N = dims(cfg)
    proj = x @ p["in_proj"].astype(dtype)
    z = proj[..., :d_inner]
    xc = proj[..., d_inner:2 * d_inner]
    Bm = proj[..., 2 * d_inner:2 * d_inner + N]
    Cm = proj[..., 2 * d_inner + N:2 * d_inner + 2 * N]
    dt = proj[..., 2 * d_inner + 2 * N:]
    return z, xc, Bm, Cm, dt


def ssd_chunked(xh, la, Bm, Cm, h0, chunk: int):
    """Chunked SSD scan.
    xh: [B, S, H, P] (dt-scaled inputs); la: [B, S, H] log decays (<= 0);
    Bm, Cm: [B, S, N]; h0: [B, H, P, N]. Returns (y [B,S,H,P], h_final)."""
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    nch = -(-S // chunk)
    pad = nch * chunk - S
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        la = jnp.pad(la, ((0, 0), (0, pad), (0, 0)))  # log-decay 0 = no decay
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    xh = xh.reshape(Bsz, nch, chunk, H, P).transpose(1, 0, 2, 3, 4)
    la = la.reshape(Bsz, nch, chunk, H).transpose(1, 0, 2, 3)
    Bm = Bm.reshape(Bsz, nch, chunk, N).transpose(1, 0, 2, 3)
    Cm = Cm.reshape(Bsz, nch, chunk, N).transpose(1, 0, 2, 3)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(h, xs):
        u, lac, Bc, Cc = xs          # [B,C,H,P], [B,C,H], [B,C,N], [B,C,N]
        Lc = jnp.cumsum(lac, axis=1)  # inclusive [B,C,H]
        # inter-chunk: y_t += (C_t . h0) * exp(L_t)   (y reads the *inclusive*
        # state h_t, so the full decay through step t applies to h0)
        tmp = jnp.einsum("bcn,bhpn->bchp", Cc, h)
        y_inter = tmp * jnp.exp(Lc)[..., None]
        # intra-chunk: M[t,j] = (C_t.B_j) exp(L_t - L_j), j<=t
        G = jnp.einsum("bin,bjn->bij", Cc, Bc)          # [B,C,C]
        Dm = Lc[:, :, None, :] - Lc[:, None, :, :]       # [B,C,C,H]
        Dm = jnp.where(causal[None, :, :, None], Dm, -jnp.inf)
        M = G[..., None] * jnp.exp(Dm)                   # [B,C,C,H]
        y_intra = jnp.einsum("bijh,bjhp->bihp", M, u)
        # state update: h' = exp(L_C) h0 + sum_j exp(L_C - L_j) B_j x u_j
        wdec = jnp.exp(Lc[:, -1:, :] - Lc)               # [B,C,H]
        h_new = (jnp.exp(Lc[:, -1, :])[..., None, None] * h
                 + jnp.einsum("bjn,bjhp,bjh->bhpn", Bc, u, wdec))
        return h_new, (y_inter + y_intra)

    h_final, ys = jax.lax.scan(body, h0.astype(jnp.float32),
                               (xh.astype(jnp.float32), la,
                                Bm.astype(jnp.float32),
                                Cm.astype(jnp.float32)))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, nch * chunk, H, P)
    return y[:, :S], h_final


def mamba_block(p, x, cfg: ArchConfig, dtype, cache: MambaCache | None = None,
                chunk: int = 64):
    """x: [B, S, D] -> ([B, S, D], new_cache). cache==None => training (no cache
    out); cache given => decode/prefill with state carry."""
    d_inner, H, P, N = dims(cfg)
    h = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    z, xc, Bm, Cm, dt = _split_proj(p, h, cfg, dtype)
    conv_in = jnp.concatenate([xc, Bm, Cm], axis=-1)
    conv_state = cache.conv if cache is not None else None
    conv_out, new_conv = _causal_conv(conv_in, p["conv_w"], p["conv_b"], conv_state)
    xc = conv_out[..., :d_inner]
    Bm = conv_out[..., d_inner:d_inner + N]
    Cm = conv_out[..., d_inner + N:]

    dt_act = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # [B,S,H]
    la = jnp.maximum(-jnp.exp(p["A_log"]) * dt_act, LOG_DECAY_FLOOR)  # log decay
    xh = xc.reshape(*xc.shape[:2], H, P)
    u = xh.astype(jnp.float32) * dt_act[..., None]

    B_, S = x.shape[0], x.shape[1]
    h0 = cache.ssm if cache is not None else jnp.zeros((B_, H, P, N), jnp.float32)
    if S == 1 and cache is not None:  # decode fast path: single-step recurrence
        a = jnp.exp(la[:, 0])                                # [B,H]
        h_new = (a[..., None, None] * h0
                 + jnp.einsum("bhp,bn->bhpn", u[:, 0], Bm[:, 0].astype(jnp.float32)))
        y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0].astype(jnp.float32), h_new)[:, None]
        h_fin = h_new
    else:
        y, h_fin = ssd_chunked(u, la, Bm, Cm, h0, chunk)
    y = y + p["D_skip"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B_, S, d_inner).astype(dtype)
    y = L.rmsnorm(p["gate_ln"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ p["out_proj"].astype(dtype)
    new_cache = MambaCache(new_conv, h_fin) if cache is not None else None
    return x + shard(out, "act_btd"), new_cache


def init_cache(cfg: ArchConfig, batch: int, dtype=jnp.bfloat16) -> MambaCache:
    d_inner, H, P, N = dims(cfg)
    conv_ch = d_inner + 2 * N
    return MambaCache(jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dtype),
                      jnp.zeros((batch, H, P, N), jnp.float32))
