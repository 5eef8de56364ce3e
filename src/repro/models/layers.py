"""Shared layer library (pure JAX, functional params-as-pytrees).

Conventions:
  * params are nested dicts of jnp arrays; init_* return params, apply_* are pure.
  * activations compute in bf16 (configurable), params stored f32 (the ByzSGD
    server replicas do f32 SGD math; casts happen on entry).
  * attention is *blocked* (online-softmax over KV chunks) so 32k-prefill
    never materialises an [S, S] score matrix — required for the dry-run
    memory envelope and the production memory roofline.
  * decode KV caches are stored chunk-sharded: [B, kvH, n_chunks, chunk, hd]
    with n_chunks mapped to the 'model' mesh axis (flash-decode with
    log-sum-exp merge across chunks => works for any kv-head count, incl.
    archs whose kv heads don't divide the TP degree).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def _init_dense(key, fan_in, *shape, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (scale * jax.random.truncated_normal(key, -2, 2, shape)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


def init_layernorm(d):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (incl. M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: tuple[int, ...] | None = None) -> jax.Array:
    """x: [B, S, H, hd]. positions: [B, S] (standard) or [3, B, S] (M-RoPE:
    temporal/height/width position ids; frontend stub emits equal ids for text).
    """
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # [hd/2]
    if positions.ndim == 3:  # M-RoPE: interleave per-section frequencies
        if mrope_sections is None:
            n = inv.shape[0]
            s0 = n - 2 * (n // 4)
            mrope_sections = (s0, n // 4, n // 4)
        sec_id = jnp.concatenate([jnp.full((s,), i, jnp.int32)
                                  for i, s in enumerate(mrope_sections)])  # [hd/2]
        pos = positions.astype(jnp.float32)  # [3, B, S]
        # per frequency j, use the position component sec_id[j]
        pos_sel = jnp.take(pos, sec_id, axis=0)  # [hd/2, B, S]
        ang = jnp.einsum("kbs,k->bsk", pos_sel, inv)
    else:
        ang = positions.astype(jnp.float32)[..., None] * inv  # [B, S, hd/2]
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)  # [B, S, 1, hd/2]
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# blocked causal attention (training / prefill)
# ---------------------------------------------------------------------------

NEG = jnp.float32(-1e30)


def _naive_attention(q, k, v, *, causal, window, cross):
    """Reference/full attention. Identical FLOP count to the blocked path
    (which also computes every masked block) but loop-free — used as the
    dry-run cost-probe path (unroll_ctx) so cost_analysis sees all the work,
    and as the test oracle."""
    B, Sq, H, hd = q.shape
    Skv, kvH = k.shape[1], k.shape[2]
    rep = H // kvH
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    if causal and not cross:
        off = Skv - Sq
        qi = jnp.arange(Sq)[:, None]
        ki = jnp.arange(Skv)[None, :]
        mask = ki <= (qi + off)
        if window > 0:
            mask &= ki > (qi + off - window)
        s = jnp.where(mask[None, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vr.dtype), vr,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _flash_attention(q, k, v, *, causal, window, q_block, kv_block):
    """The Pallas flash kernels under the ambient mesh. XLA cannot partition
    a Mosaic kernel, so on a multi-device mesh they run per shard inside a
    ``shard_map`` laid out by the installed ``act_heads``/``act_kv_heads``
    rules (replicated where none is installed; kv rows follow q's batch
    split). Where q's heads are split and kv's are whole, each shard takes
    the kv head of each of its q heads."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from ..kernels.flash_attention.ops import flash_attention
    from .sharding import rule
    fa = partial(flash_attention, causal=causal, window=window,
                 q_block=q_block, kv_block=kv_block)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fa(q, k, v)

    def spec4(name):
        r = rule(name)
        spec = tuple(r.spec) if r is not None else ()
        return spec + (None,) * (4 - len(spec))

    batch, _, q_heads, _ = spec4("act_heads")
    kv_heads = spec4("act_kv_heads")[2]
    if kv_heads is not None and kv_heads != q_heads:
        raise ValueError(f"kv heads split over {kv_heads!r} but q heads over "
                         f"{q_heads!r}: the kernel needs q's split or none")
    rep = q.shape[2] // k.shape[2]

    def attend(q, k, v):
        if q_heads is not None and kv_heads is None:
            h = q.shape[2]
            idx = (jax.lax.axis_index(q_heads) * h + jnp.arange(h)) // rep
            k, v = jnp.take(k, idx, axis=2), jnp.take(v, idx, axis=2)
        return fa(q, k, v)

    q_spec = P(batch, None, q_heads, None)
    kv_spec = P(batch, None, kv_heads, None)
    return jax.shard_map(attend, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


def blocked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      q_block: int = 512, kv_block: int = 512,
                      cross: bool = False) -> jax.Array:
    """Online-softmax blocked attention.

    q: [B, Sq, H, hd]; k, v: [B, Skv, kvH, hd] (GQA: H % kvH == 0).
    window > 0 => sliding-window causal attention (h2o-danube SWA).
    cross => no causal mask (whisper cross-attention / encoder).
    Never materialises more than [B, H, q_block, kv_block] scores.
    """
    from .unroll_ctx import active as _unroll_active
    if _unroll_active():
        return _naive_attention(q, k, v, causal=causal, window=window,
                                cross=cross)
    import os as _os
    if ((jax.default_backend() == "tpu"
         and _os.environ.get("REPRO_NO_FLASH") != "1")
            or _os.environ.get("REPRO_FLASH") == "1"):
        # production TPU path: fused Pallas flash attention, forward AND
        # backward (VMEM-resident scores — removes the O(S^2) HBM traffic
        # that dominates the memory roofline term; kernels/flash_attention
        # pairs the kernels via custom_vjp, so the training hot path runs
        # them too). REPRO_NO_FLASH=1 falls back to the blocked path;
        # REPRO_FLASH=1 forces the kernels elsewhere (Pallas interpret mode
        # off-TPU — the CI hot-path smoke).
        return _flash_attention(q, k, v, causal=causal and not cross,
                                window=window, q_block=q_block,
                                kv_block=kv_block)
    B, Sq, H, hd = q.shape
    Skv, kvH = k.shape[1], k.shape[2]
    rep = H // kvH
    scale = 1.0 / np.sqrt(hd)
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    nq, nk = -(-Sq // q_block), -(-Skv // kv_block)
    # pad to block multiples
    qp = jnp.pad(q, ((0, 0), (0, nq * q_block - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * kv_block - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kv_block - Skv), (0, 0), (0, 0)))
    qb = qp.reshape(B, nq, q_block, H, hd)
    kb = kp.reshape(B, nk, kv_block, kvH, hd)
    vb = vp.reshape(B, nk, kv_block, kvH, hd)

    q_pos_base = jnp.arange(q_block)
    k_pos_base = jnp.arange(kv_block)

    def q_chunk(qi, qc):  # qc: [B, q_block, H, hd]
        qc = qc * scale

        def kv_step(carry, ki_kc_vc):
            m, l, acc = carry
            ki, kc, vc = ki_kc_vc
            kcr = jnp.repeat(kc, rep, axis=2)  # [B, kv_block, H, hd]
            vcr = jnp.repeat(vc, rep, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kcr,
                           preferred_element_type=jnp.float32)
            qpos = qi * q_block + q_pos_base  # [q_block]
            kpos = ki * kv_block + k_pos_base
            mask = (kpos[None, :] <= Skv - 1) & (qpos[:, None] <= Sq - 1)
            if causal and not cross:
                off = Skv - Sq  # prefix (cache) length for decode-with-cache
                mask &= kpos[None, :] <= (qpos[:, None] + off)
                if window > 0:
                    mask &= kpos[None, :] > (qpos[:, None] + off - window)
            s = jnp.where(mask[None, None], s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(vcr.dtype), vcr,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_block), NEG, jnp.float32)
        l0 = jnp.zeros((B, H, q_block), jnp.float32)
        a0 = jnp.zeros((B, H, q_block, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 2, 1, 3)  # [B, q_block, H, hd]

    outs = jax.lax.map(lambda args: q_chunk(*args),
                       (jnp.arange(nq), qb.transpose(1, 0, 2, 3, 4)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_block, H, hd)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# chunk-sharded decode cache + flash-decode
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """k/v: [B, kvH, n_chunks, chunk, hd]; length: scalar tokens written."""
    k: jax.Array
    v: jax.Array
    length: jax.Array

    @staticmethod
    def create(batch, kv_heads, max_len, head_dim, n_chunks, dtype=jnp.bfloat16):
        chunk = max_len // n_chunks
        z = jnp.zeros((batch, kv_heads, n_chunks, chunk, head_dim), dtype)
        return KVCache(z, z, jnp.zeros((), jnp.int32))


def cache_insert(cache: KVCache, k_new, v_new) -> KVCache:
    """Append one token's k/v ([B, 1, kvH, hd]) at position cache.length."""
    B, kvH, nc, ck, hd = cache.k.shape
    pos = cache.length
    ci, co = pos // ck, pos % ck
    kn = k_new[:, 0].astype(cache.k.dtype)  # [B, kvH, hd]
    vn = v_new[:, 0].astype(cache.v.dtype)
    k = jax.lax.dynamic_update_slice(cache.k, kn[:, :, None, None],
                                     (0, 0, ci, co, 0))
    v = jax.lax.dynamic_update_slice(cache.v, vn[:, :, None, None],
                                     (0, 0, ci, co, 0))
    return KVCache(k, v, pos + 1)


def cache_prefill(cache: KVCache, k_all, v_all) -> KVCache:
    """Bulk-write a prefill of S tokens ([B, S, kvH, hd]) from position 0."""
    B, kvH, nc, ck, hd = cache.k.shape
    S = k_all.shape[1]
    k = k_all.transpose(0, 2, 1, 3)  # [B, kvH, S, hd]
    v = v_all.transpose(0, 2, 1, 3)
    pad = nc * ck - S
    k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(B, kvH, nc, ck, hd)
    v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(B, kvH, nc, ck, hd)
    return KVCache(k.astype(cache.k.dtype), v.astype(cache.v.dtype),
                   jnp.asarray(S, jnp.int32))


def flash_decode(q, cache: KVCache, *, window: int = 0) -> jax.Array:
    """One-token decode attention against a chunk-sharded cache.

    q: [B, 1, H, hd] -> [B, 1, H, hd]. Each chunk computes a partial softmax
    (out, lse); merging across the chunk axis is a small reduction — when the
    chunk axis is sharded over 'model', XLA lowers the merge to an all-reduce
    of [B, H, hd]-sized partials instead of gathering the whole cache.
    """
    B, _, H, hd = q.shape
    kvH = cache.k.shape[1]
    rep = H // kvH
    nc, ck = cache.k.shape[2], cache.k.shape[3]
    scale = 1.0 / np.sqrt(hd)
    qh = (q[:, 0] * scale)  # [B, H, hd]
    kr = jnp.repeat(cache.k, rep, axis=1)  # [B, H, nc, ck, hd]
    vr = jnp.repeat(cache.v, rep, axis=1)
    s = jnp.einsum("bhd,bhnkd->bhnk", qh, kr,
                   preferred_element_type=jnp.float32)  # [B, H, nc, ck]
    pos = jnp.arange(nc * ck).reshape(nc, ck)
    valid = pos < cache.length
    if window > 0:
        valid &= pos > (cache.length - window)
    s = jnp.where(valid[None, None], s, NEG)
    m = jnp.max(s, axis=-1)                              # [B, H, nc]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                              # [B, H, nc]
    part = jnp.einsum("bhnk,bhnkd->bhnd", p.astype(vr.dtype), vr,
                      preferred_element_type=jnp.float32)
    # merge partials over the (sharded) chunk axis
    g = jnp.max(m, axis=-1, keepdims=True)               # [B, H, 1]
    w = jnp.exp(m - g) * l                               # [B, H, nc]
    den = jnp.sum(w, axis=-1)
    num = jnp.sum(part * jnp.exp(m - g)[..., None], axis=2)  # [B, H, hd]
    out = num / jnp.maximum(den[..., None], 1e-30)
    return out[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block + SwiGLU MLP
# ---------------------------------------------------------------------------

def init_attention(key, d_model, n_heads, n_kv_heads, head_dim):
    ks = jax.random.split(key, 4)
    return {
        "wq": _init_dense(ks[0], d_model, d_model, n_heads * head_dim),
        "wk": _init_dense(ks[1], d_model, d_model, n_kv_heads * head_dim),
        "wv": _init_dense(ks[2], d_model, d_model, n_kv_heads * head_dim),
        "wo": _init_dense(ks[3], n_heads * head_dim, n_heads * head_dim, d_model),
    }


def attention_qkv(p, x, n_heads, n_kv_heads, head_dim, positions, theta,
                  mrope: bool = False, dtype=jnp.bfloat16):
    B, S, _ = x.shape
    q = (x @ p["wq"].astype(dtype)).reshape(B, S, n_heads, head_dim)
    k = (x @ p["wk"].astype(dtype)).reshape(B, S, n_kv_heads, head_dim)
    v = (x @ p["wv"].astype(dtype)).reshape(B, S, n_kv_heads, head_dim)
    if positions is not None:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def attention_out(p, attn, dtype=jnp.bfloat16):
    B, S, H, hd = attn.shape
    return attn.reshape(B, S, H * hd) @ p["wo"].astype(dtype)


def init_swiglu(key, d_model, d_ff):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _init_dense(ks[0], d_model, d_model, d_ff),
        "w_up": _init_dense(ks[1], d_model, d_model, d_ff),
        "w_down": _init_dense(ks[2], d_ff, d_ff, d_model),
    }


def swiglu(p, x, dtype=jnp.bfloat16):
    g = x @ p["w_gate"].astype(dtype)
    u = x @ p["w_up"].astype(dtype)
    return (jax.nn.silu(g) * u) @ p["w_down"].astype(dtype)


def init_gelu_mlp(key, d_model, d_ff):
    ks = jax.random.split(key, 2)
    return {"w_up": _init_dense(ks[0], d_model, d_model, d_ff),
            "b_up": jnp.zeros((d_ff,), jnp.float32),
            "w_down": _init_dense(ks[1], d_ff, d_ff, d_model),
            "b_down": jnp.zeros((d_model,), jnp.float32)}


def gelu_mlp(p, x, dtype=jnp.bfloat16):
    h = jax.nn.gelu(x @ p["w_up"].astype(dtype) + p["b_up"].astype(dtype))
    return h @ p["w_down"].astype(dtype) + p["b_down"].astype(dtype)


# ---------------------------------------------------------------------------
# embeddings / lm head / loss
# ---------------------------------------------------------------------------

def init_embedding(key, vocab, d_model):
    return {"table": (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(jnp.float32)}


def embed(p, tokens, dtype=jnp.bfloat16):
    return jnp.take(p["table"].astype(dtype), tokens, axis=0)


def unembed(p, x):
    return jnp.einsum("bsd,vd->bsv", x, p["table"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def cross_entropy(logits, labels):
    """logits [B,S,V] f32, labels [B,S] -> mean NLL."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def cross_entropy_chunked(hidden, table_params, labels, chunk: int = 512):
    """Sequence-chunked CE: [B,S,D] hidden x [V,D] table -> mean NLL without
    ever materialising the [B,S,V] logits (remat per chunk). This is what
    keeps the train-step memory envelope vocab-independent."""
    from .sharding import shard as _shard
    from .unroll_ctx import scan as _uscan
    B, S, D = hidden.shape
    table = table_params["table"]
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    h = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
    lbl = jnp.pad(labels, ((0, 0), (0, pad)))
    valid = jnp.pad(jnp.ones((B, S), bool), ((0, 0), (0, pad)))
    hb = jnp.moveaxis(h.reshape(B, nc, chunk, D), 1, 0)      # [nc, B, c, D]
    lb = jnp.moveaxis(lbl.reshape(B, nc, chunk), 1, 0)
    vb = jnp.moveaxis(valid.reshape(B, nc, chunk), 1, 0)

    @jax.checkpoint
    def chunk_nll(hc, lc, vc):
        logits = jnp.einsum("bcd,vd->bcv", hc, table.astype(hc.dtype),
                            preferred_element_type=jnp.float32)
        logits = _shard(logits, "logits")
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - ll) * vc)

    from .unroll_ctx import active as _unroll_active
    if _unroll_active():  # cost-probe: loop-free, flop-identical
        tot = jnp.sum(jax.vmap(chunk_nll)(hb, lb, vb))
        return tot / (B * S)

    def body(acc, xs):
        return acc + chunk_nll(*xs), None

    tot, _ = jax.lax.scan(body, jnp.float32(0.0), (hb, lb, vb))
    return tot / (B * S)
