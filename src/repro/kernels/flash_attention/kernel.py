"""Pallas TPU kernel: fused flash attention (online softmax).

§Perf motivation: the roofline memory term of every *_4k/32k cell is
dominated by HLO-visible [S, S] score traffic — the pure-JAX blocked
attention still materialises each [q_block, kv_block] score tile in HBM at
the HLO level. This kernel keeps the running (m, l, acc) statistics in VMEM
scratch across the kv-grid dimension, so scores never leave VMEM: HBM traffic
drops from O(S^2) to O(S * hd) per head — the single biggest lever on the
memory roofline term identified in EXPERIMENTS.md §Perf.

Layout: grid = (batch*heads, n_q_blocks, n_kv_blocks), kv innermost; the
output block index ignores the kv dim (revisited), and f32 scratch carries
the softmax state. MXU alignment: q_block/kv_block multiples of 128 on the
lane dim, hd padded to 128.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
# minor dim of the per-row statistics (m, l, lse, delta): one full lane
# register, so every block meets the TPU's (8, 128) tiling
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, causal: bool, window: int, sq: int, skv: int,
                  q_block: int, kv_block: int, n_kv: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # [qb, hd]
    k = k_ref[0].astype(jnp.float32)                  # [kb, hd]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [qb, kb]

    qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                   (q_block, kv_block), 0)
    kpos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32,
                                                    (q_block, kv_block), 1)
    mask = (kpos < skv) & (qpos < sq)
    if causal:
        off = skv - sq  # prefix length when kv longer than q
        mask &= kpos <= (qpos + off)
        if window > 0:
            mask &= kpos > (qpos + off - window)
    s = jnp.where(mask, s, NEG)

    # m/l are lane-replicated [qb, 128] (the TPU tiling refuses 1-D VMEM
    # vectors); column 0 is read back as a [qb, 1] broadcast operand
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = (acc_scr[...] * corr[:, :1]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        # log-sum-exp of the (scaled, masked) scores per q row, stored
        # lane-dense like m/l — the backward kernels re-derive
        # p = exp(s - lse) from it without re-running the online softmax.
        # Fully-masked (padded) rows get lse ~ NEG; their upstream do is
        # zero-padded, so their garbage p never contributes.
        lse_ref[0] = m_scr[...] + jnp.log(l)


def flash_pallas_call(bh: int, sq_pad: int, skv_pad: int, hd_pad: int, *,
                      sq: int, skv: int, causal: bool, window: int,
                      q_block: int, kv_block: int, scale: float, dtype,
                      interpret: bool = False):
    """Forward: (q, k, v) [bh, s_pad, hd_pad] -> (out, lse [bh, sq_pad, 128]);
    lse is replicated over its 128 lanes."""
    n_q = sq_pad // q_block
    n_kv = skv_pad // kv_block
    kern = partial(_flash_kernel, causal=causal, window=window, sq=sq,
                   skv=skv, q_block=q_block, kv_block=kv_block, n_kv=n_kv,
                   scale=scale)
    return pl.pallas_call(
        kern,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, q_block, hd_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_block, hd_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kv_block, hd_pad), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q_block, hd_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, q_block, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_pad, hd_pad), dtype),
            jax.ShapeDtypeStruct((bh, sq_pad, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_block, LANES), jnp.float32),
            pltpu.VMEM((q_block, LANES), jnp.float32),
            pltpu.VMEM((q_block, hd_pad), jnp.float32),
        ],
        interpret=interpret,
    )


def _bwd_mask_and_p(q, k, lse, qi, ki, *, causal, window, sq, skv,
                    q_block, kv_block, scale):
    """Recompute the [qb, kb] probability tile exactly as the forward masked
    it (padding + causal + window), from the saved per-row lse [qb, 1]."""
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                   (q_block, kv_block), 0)
    kpos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32,
                                                    (q_block, kv_block), 1)
    mask = (kpos < skv) & (qpos < sq)
    if causal:
        off = skv - sq
        mask &= kpos <= (qpos + off)
        if window > 0:
            mask &= kpos > (qpos + off - window)
    s = jnp.where(mask, s, NEG)
    return jnp.exp(s - lse)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, causal: bool, window: int,
                         sq: int, skv: int, q_block: int, kv_block: int,
                         n_kv: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    p = _bwd_mask_and_p(q, k, lse_ref[0][:, :1], qi, ki, causal=causal,
                        window=window, sq=sq, skv=skv, q_block=q_block,
                        kv_block=kv_block, scale=scale)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [qb, kb]
    ds = p * (dp - delta_ref[0][:, :1])
    dq_scr[...] += scale * jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                          window: int, sq: int, skv: int, q_block: int,
                          kv_block: int, n_q: int, scale: float):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    p = _bwd_mask_and_p(q, k, lse_ref[0][:, :1], qi, ki, causal=causal,
                        window=window, sq=sq, skv=skv, q_block=q_block,
                        kv_block=kv_block, scale=scale)
    dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0][:, :1])
    dk_scr[...] += scale * jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd_dq_call(bh: int, sq_pad: int, skv_pad: int, hd_pad: int, *,
                      sq: int, skv: int, causal: bool, window: int,
                      q_block: int, kv_block: int, scale: float, dtype,
                      interpret: bool = False):
    """dq: grid (bh, n_q, n_kv) — kv innermost, dq accumulated in VMEM."""
    n_q = sq_pad // q_block
    n_kv = skv_pad // kv_block
    kern = partial(_flash_bwd_dq_kernel, causal=causal, window=window, sq=sq,
                   skv=skv, q_block=q_block, kv_block=kv_block, n_kv=n_kv,
                   scale=scale)
    return pl.pallas_call(
        kern,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, q_block, hd_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_block, hd_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kv_block, hd_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, q_block, hd_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, q_block, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, q_block, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, hd_pad), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_pad, hd_pad), dtype),
        scratch_shapes=[pltpu.VMEM((q_block, hd_pad), jnp.float32)],
        interpret=interpret,
    )


def flash_bwd_dkv_call(bh: int, sq_pad: int, skv_pad: int, hd_pad: int, *,
                       sq: int, skv: int, causal: bool, window: int,
                       q_block: int, kv_block: int, scale: float, dtype,
                       interpret: bool = False):
    """(dk, dv): grid (bh, n_kv, n_q) — q innermost, dk/dv accumulated in
    VMEM. Mask positions are derived from (program_id(2)=q block,
    program_id(1)=kv block), matching the forward's tile masks exactly."""
    n_q = sq_pad // q_block
    n_kv = skv_pad // kv_block
    kern = partial(_flash_bwd_dkv_kernel, causal=causal, window=window,
                   sq=sq, skv=skv, q_block=q_block, kv_block=kv_block,
                   n_q=n_q, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(bh, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, q_block, hd_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, q_block, hd_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, q_block, LANES), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, q_block, LANES), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kv_block, hd_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_block, hd_pad), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, kv_block, hd_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_block, hd_pad), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skv_pad, hd_pad), dtype),
            jax.ShapeDtypeStruct((bh, skv_pad, hd_pad), dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((kv_block, hd_pad), jnp.float32),
            pltpu.VMEM((kv_block, hd_pad), jnp.float32),
        ],
        interpret=interpret,
    )
