"""Mixture-of-Experts decoder (dbrx-132b: 16e top-4; qwen3-moe: 128e top-8).

Routing: token-choice top-k with per-expert capacity (C = ceil(T * top_k / E *
capacity_factor)); tokens beyond capacity are dropped (standard practice, keeps
compute static). Dispatch is index-gather based (no [T, E, C]
one-hot tensors — at 1M tokens those are infeasible): for each expert we take
the top-C tokens by router weight, process [E, C, D] with batched per-expert
matmuls, and scatter-add back.

Expert parallelism: expert weights carry a leading E axis annotated with the
'expert_weights' logical rule -> sharded over the 'model' mesh axis (EP reuses
the TP axis; dbrx 16e/16 = 1 expert per chip, qwen3 128e/16 = 8). The combine
scatter-add reduces over the model axis (XLA lowers it to the EP all-reduce).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import layers as L
from . import transformer as TF
from .config import ArchConfig
from .sharding import shard


def init_moe_ffn(key, cfg: ArchConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": L._init_dense(k1, D, D, E),
        "w_gate": (0.02 * jax.random.normal(k2, (E, D, F))).astype(jnp.float32),
        "w_up": (0.02 * jax.random.normal(k3, (E, D, F))).astype(jnp.float32),
        "w_down": (0.02 * jax.random.normal(k4, (E, F, D))).astype(jnp.float32),
    }


MOE_CHUNK_TOKENS = 131_072  # dispatch in token chunks beyond this (prefill)


def moe_ffn(p, x, cfg: ArchConfig, dtype):
    """x: [B, S, D] -> [B, S, D].

    Long-prefill inputs are dispatched in token chunks (capacity enforced
    per chunk — standard practice; keeps the [E, C, D] gather transient
    bounded instead of O(T) — the 120 GiB dbrx-prefill buffer of the §Perf
    log)."""
    B, S, D = x.shape
    T = B * S
    if T > MOE_CHUNK_TOKENS:
        nc = -(-T // MOE_CHUNK_TOKENS)
        while T % nc:
            nc += 1
        xt = x.reshape(nc, T // nc, 1, D)  # chunks as [b=Tc, s=1] pseudo-batch
        def body(_, c):
            return None, _moe_tokens(p, c, cfg, dtype)
        _, out = jax.lax.scan(body, None, xt)
        return out.reshape(B, S, D)
    return _moe_tokens(p, x, cfg, dtype)


def _moe_tokens(p, x, cfg: ArchConfig, dtype):
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    cap = max(int(T * K / E * cfg.capacity_factor), 1)
    cap = min(cap, T)
    xt = x.reshape(T, D)

    logits = (xt @ p["router"].astype(dtype)).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)                            # [T, K]
    topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)

    # dense [T, E] weight map of the top-k choices (0 elsewhere)
    wmap = jnp.zeros((T, E), jnp.float32)
    wmap = wmap.at[jnp.arange(T)[:, None], topi].set(topw)          # [T, E]

    # per-expert capacity selection: top-C tokens by routing weight
    wcap, tok_idx = jax.lax.top_k(wmap.T, cap)                      # [E, C]
    keep = wcap > 0.0

    we_g = shard(p["w_gate"].astype(dtype), "expert_w_in")   # F over 'model'
    we_u = shard(p["w_up"].astype(dtype), "expert_w_in")
    we_d = shard(p["w_down"].astype(dtype), "expert_w_out")  # F over 'model'

    gathered = jnp.take(xt, tok_idx.reshape(-1), axis=0).reshape(E, cap, D)
    gathered = shard(gathered, "expert_tokens")  # D over 'fsdp' = w_gate's D
    g = jnp.einsum("ecd,edf->ecf", gathered, we_g)
    u = jnp.einsum("ecd,edf->ecf", gathered, we_u)
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, we_d)                       # [E, C, D]
    out = out * (wcap * keep)[..., None].astype(dtype)

    # combine: scatter-add expert outputs back to token positions
    flat_idx = jnp.where(keep, tok_idx, T).reshape(-1)              # dropped -> OOB
    combined = jnp.zeros((T + 1, D), dtype).at[flat_idx].add(
        out.reshape(E * cap, D))[:T]
    return combined.reshape(B, S, D)


# -- blocks ------------------------------------------------------------------

def init_block(key, cfg: ArchConfig):
    init_norm, _ = TF._norm_fns(cfg)
    k1, k2 = jax.random.split(key)
    return {
        "ln_attn": init_norm(cfg.d_model),
        "attn": L.init_attention(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.hd),
        "ln_mlp": init_norm(cfg.d_model),
        "moe": init_moe_ffn(k2, cfg),
    }


def init(key, cfg: ArchConfig):
    ke, kb = jax.random.split(key)
    init_norm, _ = TF._norm_fns(cfg)
    bkeys = jax.random.split(kb, cfg.n_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg))(bkeys)
    return {"embed": L.init_embedding(ke, cfg.vocab, cfg.d_model),
            "blocks": blocks, "ln_f": init_norm(cfg.d_model)}


def _block_train(blk, x, positions, cfg: ArchConfig, dtype):
    _, norm = TF._norm_fns(cfg)
    h = norm(blk["ln_attn"], x)
    q, k, v = L.attention_qkv(blk["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                              cfg.hd, positions, cfg.rope_theta, dtype=dtype)
    q, k, v = shard(q, "act_heads"), shard(k, "act_kv_heads"), shard(v, "act_kv_heads")
    attn = L.blocked_attention(q, k, v, causal=True, window=cfg.sliding_window,
                               q_block=cfg.q_block, kv_block=cfg.kv_block)
    x = x + shard(L.attention_out(blk["attn"], attn, dtype), "act_btd")
    h = norm(blk["ln_mlp"], x)
    x = x + shard(moe_ffn(blk["moe"], h, cfg, dtype), "act_btd")
    return x


def forward(params, tokens, *, cfg: ArchConfig, remat: bool = True):
    dtype = jnp.dtype(cfg.act_dtype)
    x = shard(L.embed(params["embed"], tokens, dtype), "act_btd")
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    body = partial(_block_train, positions=positions, cfg=cfg, dtype=dtype)
    if remat:
        body = jax.checkpoint(body)

    def scan_body(x, blk):
        return body(blk, x), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    _, norm = TF._norm_fns(cfg)
    return norm(params["ln_f"], x)


def loss(params, batch, *, cfg: ArchConfig):
    hidden = forward(params, batch["tokens"], cfg=cfg)
    return L.cross_entropy_chunked(hidden, params["embed"], batch["labels"])


init_caches = TF.init_caches


def prefill(params, batch, caches, *, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.act_dtype)
    tokens = batch["tokens"]
    x = shard(L.embed(params["embed"], tokens, dtype), "act_btd")
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    _, norm = TF._norm_fns(cfg)

    def scan_body(x, blk_cache):
        blk, cache = blk_cache
        h = norm(blk["ln_attn"], x)
        q, k, v = L.attention_qkv(blk["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                                  cfg.hd, positions, cfg.rope_theta, dtype=dtype)
        cache = L.cache_prefill(cache, k, v)
        cache = L.KVCache(shard(cache.k, "kv_cache"), shard(cache.v, "kv_cache"),
                          cache.length)
        attn = L.blocked_attention(q, k, v, causal=True,
                                   window=cfg.sliding_window,
                                   q_block=cfg.q_block, kv_block=cfg.kv_block)
        x = x + shard(L.attention_out(blk["attn"], attn, dtype), "act_btd")
        h = norm(blk["ln_mlp"], x)
        x = x + shard(moe_ffn(blk["moe"], h, cfg, dtype), "act_btd")
        return x, cache

    x, caches = jax.lax.scan(scan_body, x, (params["blocks"], caches))
    hidden = norm(params["ln_f"], x[:, -1:])
    lg = TF.logits_fn(params, hidden, cfg)
    return lg[:, 0], caches


def decode_step(params, caches, batch, *, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.act_dtype)
    x = shard(L.embed(params["embed"], batch["token"], dtype), "act_btd")
    B = x.shape[0]
    pos_scalar = batch.get("pos")
    if pos_scalar is None:
        pos_scalar = caches.length[0]
    positions = jnp.broadcast_to(pos_scalar[None, None], (B, 1)).astype(jnp.int32)
    _, norm = TF._norm_fns(cfg)

    def scan_body(x, blk_cache):
        blk, cache = blk_cache
        h = norm(blk["ln_attn"], x)
        q, k, v = L.attention_qkv(blk["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                                  cfg.hd, positions, cfg.rope_theta, dtype=dtype)
        cache = L.cache_insert(cache, k, v)
        attn = L.flash_decode(q, cache, window=cfg.sliding_window)
        x = x + L.attention_out(blk["attn"], attn, dtype)
        h = norm(blk["ln_mlp"], x)
        x = x + moe_ffn(blk["moe"], h, cfg, dtype)
        return x, cache

    x, caches = jax.lax.scan(scan_body, x, (params["blocks"], caches))
    hidden = norm(params["ln_f"], x)
    lg = TF.logits_fn(params, hidden, cfg)
    return lg[:, 0], caches
