"""Dense decoder-only transformer (GQA + RoPE/M-RoPE + SwiGLU + optional SWA).

Covers: phi3-medium-14b, phi4-mini-3.8b, internlm2-20b, h2o-danube-3-4b (SWA),
qwen2-vl-7b (M-RoPE + stub patch-embedding inputs). Also the attention/FFN
backbone reused by the MoE and hybrid families.

Layers are scanned (single-block compile) with remat on the block body for
training. Decode uses the chunk-sharded flash-decode cache from layers.py.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp


from . import layers as L
from .config import ArchConfig
from .sharding import shard


def _norm_fns(cfg: ArchConfig):
    if cfg.norm == "layernorm":
        return L.init_layernorm, partial(L.layernorm, eps=cfg.norm_eps)
    return L.init_rmsnorm, partial(L.rmsnorm, eps=cfg.norm_eps)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(key, cfg: ArchConfig):
    init_norm, _ = _norm_fns(cfg)
    k1, k2 = jax.random.split(key)
    return {
        "ln_attn": init_norm(cfg.d_model),
        "attn": L.init_attention(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.hd),
        "ln_mlp": init_norm(cfg.d_model),
        "mlp": L.init_swiglu(k2, cfg.d_model, cfg.d_ff),
    }


def init(key, cfg: ArchConfig):
    ke, kb, kf = jax.random.split(key, 3)
    init_norm, _ = _norm_fns(cfg)
    bkeys = jax.random.split(kb, cfg.n_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg))(bkeys)  # leaves [L, ...]
    params = {"embed": L.init_embedding(ke, cfg.vocab, cfg.d_model),
              "blocks": blocks,
              "ln_f": init_norm(cfg.d_model)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"table": L._init_dense(kf, cfg.d_model, cfg.vocab,
                                                    cfg.d_model)}
    return params


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _block_train(blk, x, positions, cfg: ArchConfig, dtype):
    _, norm = _norm_fns(cfg)
    h = norm(blk["ln_attn"], x)
    q, k, v = L.attention_qkv(blk["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                              cfg.hd, positions, cfg.rope_theta, dtype=dtype)
    q = shard(q, "act_heads")
    k = shard(k, "act_kv_heads")
    v = shard(v, "act_kv_heads")
    attn = L.blocked_attention(q, k, v, causal=True, window=cfg.sliding_window,
                               q_block=cfg.q_block, kv_block=cfg.kv_block)
    x = x + shard(L.attention_out(blk["attn"], attn, dtype), "act_btd")
    h = norm(blk["ln_mlp"], x)
    x = x + shard(L.swiglu(blk["mlp"], h, dtype), "act_btd")
    return x


def forward(params, tokens=None, *, cfg: ArchConfig, embeds=None,
            positions=None, remat: bool = True):
    """[B, S] tokens (or [B, S, D] stub embeds for VLM) -> [B, S, D] hidden."""
    dtype = jnp.dtype(cfg.act_dtype)
    if embeds is None:
        x = L.embed(params["embed"], tokens, dtype)
    else:
        x = embeds.astype(dtype)
    x = shard(x, "act_btd")
    B, S = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    body = partial(_block_train, positions=positions, cfg=cfg, dtype=dtype)
    if remat:
        body = jax.checkpoint(body)

    def scan_body(x, blk):
        return body(blk, x), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    _, norm = _norm_fns(cfg)
    return norm(params["ln_f"], x)


def logits_fn(params, hidden, cfg: ArchConfig):
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    out = L.unembed(table, hidden)
    return shard(out, "logits")


def loss(params, batch, *, cfg: ArchConfig):
    hidden = forward(params, batch.get("tokens"), cfg=cfg,
                     embeds=batch.get("embeds"), positions=batch.get("positions"))
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return L.cross_entropy_chunked(hidden, table, batch["labels"])


# ---------------------------------------------------------------------------
# inference: prefill + decode
# ---------------------------------------------------------------------------

def init_caches(cfg: ArchConfig, batch: int, max_len: int, n_chunks: int,
                dtype=jnp.bfloat16):
    def one(_):
        return L.KVCache.create(batch, cfg.n_kv_heads, max_len, cfg.hd,
                                n_chunks, dtype)
    return jax.vmap(one)(jnp.arange(cfg.n_layers))  # leaves [L, ...]


def _block_prefill(blk, x, positions, cfg: ArchConfig, dtype, cache: L.KVCache):
    _, norm = _norm_fns(cfg)
    h = norm(blk["ln_attn"], x)
    q, k, v = L.attention_qkv(blk["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                              cfg.hd, positions, cfg.rope_theta, dtype=dtype)
    cache = L.cache_prefill(cache, k, v)
    cache = L.KVCache(shard(cache.k, "kv_cache"), shard(cache.v, "kv_cache"),
                      cache.length)
    attn = L.blocked_attention(q, k, v, causal=True, window=cfg.sliding_window,
                               q_block=cfg.q_block, kv_block=cfg.kv_block)
    x = x + shard(L.attention_out(blk["attn"], attn, dtype), "act_btd")
    h = norm(blk["ln_mlp"], x)
    x = x + shard(L.swiglu(blk["mlp"], h, dtype), "act_btd")
    return x, cache


def prefill(params, batch, caches, *, cfg: ArchConfig):
    """Returns (last-token logits [B, V], filled caches)."""
    dtype = jnp.dtype(cfg.act_dtype)
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    x = L.embed(params["embed"], tokens, dtype) if embeds is None else embeds.astype(dtype)
    x = shard(x, "act_btd")
    B, S = x.shape[0], x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def scan_body(x, blk_cache):
        blk, cache = blk_cache
        x, cache = _block_prefill(blk, x, positions, cfg, dtype, cache)
        return x, cache

    x, caches = jax.lax.scan(scan_body, x, (params["blocks"], caches))
    _, norm = _norm_fns(cfg)
    hidden = norm(params["ln_f"], x[:, -1:])
    lg = logits_fn(params, hidden, cfg)
    return lg[:, 0], caches


def _block_decode(blk, x, positions, cfg: ArchConfig, dtype, cache: L.KVCache):
    _, norm = _norm_fns(cfg)
    h = norm(blk["ln_attn"], x)
    q, k, v = L.attention_qkv(blk["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                              cfg.hd, positions, cfg.rope_theta, dtype=dtype)
    cache = L.cache_insert(cache, k, v)
    attn = L.flash_decode(q, cache, window=cfg.sliding_window)
    x = x + L.attention_out(blk["attn"], attn, dtype)
    h = norm(blk["ln_mlp"], x)
    x = x + L.swiglu(blk["mlp"], h, dtype)
    return x, cache


def decode_step(params, caches, batch, *, cfg: ArchConfig):
    """batch: {"token": [B,1] (or "embeds" [B,1,D]), optional "positions"}.
    Returns (logits [B, V], updated caches). One new token vs the KV cache."""
    dtype = jnp.dtype(cfg.act_dtype)
    tok = batch.get("token")
    embeds = batch.get("embeds")
    x = L.embed(params["embed"], tok, dtype) if embeds is None else embeds.astype(dtype)
    x = shard(x, "act_btd")
    B = x.shape[0]
    pos_scalar = batch.get("pos")
    if pos_scalar is None:
        # use cache length of layer 0
        pos_scalar = caches.length[0]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(pos_scalar[None, None], (B, 1)).astype(jnp.int32)

    def scan_body(x, blk_cache):
        blk, cache = blk_cache
        x, cache = _block_decode(blk, x, positions, cfg, dtype, cache)
        return x, cache

    x, caches = jax.lax.scan(scan_body, x, (params["blocks"], caches))
    _, norm = _norm_fns(cfg)
    hidden = norm(params["ln_f"], x)
    lg = logits_fn(params, hidden, cfg)
    return lg[:, 0], caches
