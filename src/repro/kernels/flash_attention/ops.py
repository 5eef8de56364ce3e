"""Jitted public wrapper around the Pallas flash-attention kernels.

``flash_attention`` is differentiable end-to-end: the forward kernel saves
the per-row log-sum-exp alongside the output, and a ``jax.custom_vjp`` pairs
it with two Pallas backward kernels (dq over a kv-innermost grid, dk/dv over
a q-innermost grid) that recompute the probability tiles from the saved lse
with the forward's exact padding/causal/window masks — so the training hot
path never materialises an [S, S] score matrix in either direction. All
kernel arithmetic accumulates in f32 regardless of the bf16 input dtype;
``delta = rowsum(do * o)`` is precomputed in plain JAX.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import (LANES, flash_bwd_dkv_call, flash_bwd_dq_call,
                     flash_pallas_call)

_LANE = 128


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, size, axis):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _blocking(Sq, Skv, hd, q_block, kv_block):
    q_block = min(q_block, max(Sq, 8))
    kv_block = min(kv_block, max(Skv, 8))
    sq_pad = -(-Sq // q_block) * q_block
    skv_pad = -(-Skv // kv_block) * kv_block
    hd_pad = -(-hd // _LANE) * _LANE
    return q_block, kv_block, sq_pad, skv_pad, hd_pad


def _to_bh(x, s_pad, hd_pad):
    """[B, S, H, hd] -> padded [B*H, s_pad, hd_pad]."""
    B, S, H, hd = x.shape
    x = jnp.moveaxis(x, 2, 1).reshape(B * H, S, hd)
    return _pad_to(_pad_to(x, s_pad, 1), hd_pad, 2)


def _from_bh(x, B, H, S, hd):
    """Padded [B*H, s_pad, hd_pad] -> [B, S, H, hd]."""
    x = x[:, :S, :hd].reshape(B, H, S, hd)
    return jnp.moveaxis(x, 1, 2)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, q_block, kv_block, interpret):
    out, _ = _flash_fwd(q, k, v, causal, window, q_block, kv_block, interpret)
    return out


def _flash_fwd(q, k, v, causal, window, q_block, kv_block, interpret):
    B, Sq, H, hd = q.shape
    Skv, kvH = k.shape[1], k.shape[2]
    rep = H // kvH
    scale = 1.0 / np.sqrt(hd)
    q_block, kv_block, sq_pad, skv_pad, hd_pad = _blocking(
        Sq, Skv, hd, q_block, kv_block)
    qb = _to_bh(q, sq_pad, hd_pad)
    kb = _to_bh(jnp.repeat(k, rep, axis=2), skv_pad, hd_pad)
    vb = _to_bh(jnp.repeat(v, rep, axis=2), skv_pad, hd_pad)
    ob, lse = flash_pallas_call(
        B * H, sq_pad, skv_pad, hd_pad, sq=Sq, skv=Skv, causal=causal,
        window=window, q_block=q_block, kv_block=kv_block, scale=scale,
        dtype=q.dtype, interpret=interpret)(qb, kb, vb)
    out = _from_bh(ob, B, H, Sq, hd)
    # keep one lane of the lane-replicated lse as the residual
    return out, (q, k, v, ob, lse[..., 0])


def _flash_bwd(causal, window, q_block, kv_block, interpret, res, do):
    q, k, v, ob, lse = res
    B, Sq, H, hd = q.shape
    Skv, kvH = k.shape[1], k.shape[2]
    rep = H // kvH
    scale = 1.0 / np.sqrt(hd)
    q_block, kv_block, sq_pad, skv_pad, hd_pad = _blocking(
        Sq, Skv, hd, q_block, kv_block)
    qb = _to_bh(q, sq_pad, hd_pad)
    kb = _to_bh(jnp.repeat(k, rep, axis=2), skv_pad, hd_pad)
    vb = _to_bh(jnp.repeat(v, rep, axis=2), skv_pad, hd_pad)
    dob = _to_bh(do, sq_pad, hd_pad)
    # delta_i = sum_d do_id * o_id (zero on padded rows since do is
    # zero-padded) — plain JAX, one [bh, sq_pad] vector
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1)
    # the kernels read both per-row statistics lane-replicated
    lse, delta = (jnp.broadcast_to(x[..., None], x.shape + (LANES,))
                  for x in (lse, delta))

    common = dict(sq=Sq, skv=Skv, causal=causal, window=window,
                  q_block=q_block, kv_block=kv_block, scale=scale,
                  dtype=q.dtype, interpret=interpret)
    dqb = flash_bwd_dq_call(B * H, sq_pad, skv_pad, hd_pad, **common)(
        qb, kb, vb, dob, lse, delta)
    dkb, dvb = flash_bwd_dkv_call(B * H, sq_pad, skv_pad, hd_pad, **common)(
        qb, dob, lse, delta, kb, vb)

    dq = _from_bh(dqb, B, H, Sq, hd)
    # un-repeat GQA heads: h = kvh * rep + r -> sum over r
    dk_full = _from_bh(dkb, B, H, Skv, hd)
    dv_full = _from_bh(dvb, B, H, Skv, hd)
    dk = dk_full.reshape(B, Skv, kvH, rep, hd).sum(axis=3).astype(k.dtype)
    dv = dv_full.reshape(B, Skv, kvH, rep, hd).sum(axis=3).astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "q_block", "kv_block",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_block: int = 512, kv_block: int = 512,
                    interpret: bool | None = None):
    """Fused attention, forward AND backward. q: [B, Sq, H, hd]; k, v:
    [B, Skv, kvH, hd] (GQA: kv heads repeated into H, gradients summed back).
    Returns [B, Sq, H, hd].

    A per-device op: XLA cannot partition a Mosaic kernel, so a caller under
    a multi-device mesh runs it per shard inside a ``shard_map``."""
    if interpret is None:
        interpret = _default_interpret()
    return _flash(q, k, v, causal, window, q_block, kv_block, interpret)
