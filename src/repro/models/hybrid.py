"""Zamba2-style hybrid: Mamba2 backbone + ONE shared attention block applied
every `shared_attn_every` SSM layers (arXiv:2411.15242).

The shared block's *parameters* are reused at every application site, but each
site keeps its own KV cache (different depths see different activations).
500k-token decode: SSM state is O(1); the shared-attention sites keep
seq-length caches — chunk-sharded over 'model', so the per-chip footprint is
(sites * 500k * d_kv / 16), which is what makes this arch long-context-serveable.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import mamba2 as M
from .config import ArchConfig
from .sharding import shard


def n_shared_sites(cfg: ArchConfig) -> int:
    return cfg.n_layers // cfg.shared_attn_every


def _attn_cfg_dims(cfg: ArchConfig):
    heads = cfg.shared_attn_heads or cfg.n_heads
    return heads, cfg.d_model // heads


def init_shared_block(key, cfg: ArchConfig):
    heads, hd = _attn_cfg_dims(cfg)
    k1, k2 = jax.random.split(key)
    return {
        "ln_attn": L.init_rmsnorm(cfg.d_model),
        "attn": L.init_attention(k1, cfg.d_model, heads, heads, hd),  # MHA
        "ln_mlp": L.init_rmsnorm(cfg.d_model),
        "mlp": L.init_swiglu(k2, cfg.d_model, cfg.shared_attn_d_ff or cfg.d_ff),
    }


def init(key, cfg: ArchConfig):
    ke, km, ks = jax.random.split(key, 3)
    mkeys = jax.random.split(km, cfg.n_layers)
    mamba_blocks = jax.vmap(lambda k: M.init_mamba_block(k, cfg))(mkeys)
    return {"embed": L.init_embedding(ke, cfg.vocab, cfg.d_model),
            "mamba": mamba_blocks,
            "shared": init_shared_block(ks, cfg),
            "ln_f": L.init_rmsnorm(cfg.d_model)}


def _shared_apply_train(p, x, cfg: ArchConfig, dtype):
    heads, hd = _attn_cfg_dims(cfg)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = L.rmsnorm(p["ln_attn"], x, cfg.norm_eps)
    q, k, v = L.attention_qkv(p["attn"], h, heads, heads, hd, positions,
                              cfg.rope_theta, dtype=dtype)
    attn = L.blocked_attention(q, k, v, causal=True, q_block=cfg.q_block,
                               kv_block=cfg.kv_block)
    x = x + shard(L.attention_out(p["attn"], attn, dtype), "act_btd")
    h = L.rmsnorm(p["ln_mlp"], x, cfg.norm_eps)
    return x + shard(L.swiglu(p["mlp"], h, dtype), "act_btd")


def _segment_scan(params, x, cfg: ArchConfig, dtype, remat: bool):
    """Scan mamba layers in groups of `shared_attn_every`, interleaving the
    shared attention block between groups."""
    every = cfg.shared_attn_every
    n_full = cfg.n_layers // every
    rest = cfg.n_layers - n_full * every

    def mamba_body(blk, x):
        return M.mamba_block(blk, x, cfg, dtype)[0]

    if remat:
        mamba_body = jax.checkpoint(mamba_body)

    def seg_scan(x, blocks_slice):
        def sb(x, blk):
            return mamba_body(blk, x), None
        x, _ = jax.lax.scan(sb, x, blocks_slice)
        return x

    take = lambda tree, lo, hi: jax.tree.map(lambda l: l[lo:hi], tree)
    for g in range(n_full):
        x = seg_scan(x, take(params["mamba"], g * every, (g + 1) * every))
        x = _shared_apply_train(params["shared"], x, cfg, dtype)
    if rest:
        x = seg_scan(x, take(params["mamba"], n_full * every, cfg.n_layers))
    return x


def forward(params, tokens, *, cfg: ArchConfig, remat: bool = True):
    dtype = jnp.dtype(cfg.act_dtype)
    x = shard(L.embed(params["embed"], tokens, dtype), "act_btd")
    x = _segment_scan(params, x, cfg, dtype, remat)
    return L.rmsnorm(params["ln_f"], x, cfg.norm_eps)


def loss(params, batch, *, cfg: ArchConfig):
    hidden = forward(params, batch["tokens"], cfg=cfg)
    return L.cross_entropy_chunked(hidden, params["embed"], batch["labels"])


class HybridCaches(NamedTuple):
    mamba: M.MambaCache          # leaves [L, ...]
    attn: L.KVCache              # leaves [n_sites, ...]


def init_caches(cfg: ArchConfig, batch: int, max_len: int, n_chunks: int,
                dtype=jnp.bfloat16) -> HybridCaches:
    heads, hd = _attn_cfg_dims(cfg)
    mam = jax.vmap(lambda _: M.init_cache(cfg, batch, dtype))(
        jnp.arange(cfg.n_layers))
    sites = max(n_shared_sites(cfg), 1)
    att = jax.vmap(lambda _: L.KVCache.create(batch, heads, max_len, hd,
                                              n_chunks, dtype))(jnp.arange(sites))
    return HybridCaches(mam, att)


def _shared_apply_cached(p, x, cfg: ArchConfig, dtype, cache: L.KVCache,
                         prefill_mode: bool):
    heads, hd = _attn_cfg_dims(cfg)
    B, S, _ = x.shape
    if prefill_mode:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    else:
        positions = jnp.broadcast_to(cache.length[None, None], (B, 1)).astype(jnp.int32)
    h = L.rmsnorm(p["ln_attn"], x, cfg.norm_eps)
    q, k, v = L.attention_qkv(p["attn"], h, heads, heads, hd, positions,
                              cfg.rope_theta, dtype=dtype)
    if prefill_mode:
        cache = L.cache_prefill(cache, k, v)
        attn = L.blocked_attention(q, k, v, causal=True, q_block=cfg.q_block,
                                   kv_block=cfg.kv_block)
    else:
        cache = L.cache_insert(cache, k, v)
        attn = L.flash_decode(q, cache)
    x = x + L.attention_out(p["attn"], attn, dtype)
    h = L.rmsnorm(p["ln_mlp"], x, cfg.norm_eps)
    return x + L.swiglu(p["mlp"], h, dtype), cache


def _run_cached(params, x, caches: HybridCaches, cfg: ArchConfig, dtype,
                prefill_mode: bool):
    every = cfg.shared_attn_every
    n_full = cfg.n_layers // every
    rest = cfg.n_layers - n_full * every
    take = lambda tree, lo, hi: jax.tree.map(lambda l: l[lo:hi], tree)
    put = lambda tree, sub, lo: jax.tree.map(
        lambda l, s: l.at[lo:lo + s.shape[0]].set(s), tree, sub)

    def seg(x, pslice, cslice):
        def sb(xc, blk_cache):
            blk, cache = blk_cache
            xc, cache = M.mamba_block(blk, xc, cfg, dtype, cache)
            return xc, cache
        x, new_caches = jax.lax.scan(sb, x, (pslice, cslice))
        return x, new_caches

    mam, att = caches.mamba, caches.attn
    for g in range(n_full):
        lo, hi = g * every, (g + 1) * every
        x, seg_c = seg(x, take(params["mamba"], lo, hi), take(mam, lo, hi))
        mam = put(mam, seg_c, lo)
        site = jax.tree.map(lambda l: l[g], att)
        x, site = _shared_apply_cached(params["shared"], x, cfg, dtype, site,
                                       prefill_mode)
        att = jax.tree.map(lambda l, s: l.at[g].set(s), att, site)
    if rest:
        lo = n_full * every
        x, seg_c = seg(x, take(params["mamba"], lo, cfg.n_layers),
                       take(mam, lo, cfg.n_layers))
        mam = put(mam, seg_c, lo)
    return x, HybridCaches(mam, att)


def prefill(params, batch, caches: HybridCaches, *, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.act_dtype)
    x = shard(L.embed(params["embed"], batch["tokens"], dtype), "act_btd")
    x, caches = _run_cached(params, x, caches, cfg, dtype, prefill_mode=True)
    hidden = L.rmsnorm(params["ln_f"], x[:, -1:], cfg.norm_eps)
    lg = L.unembed(params["embed"], hidden)
    return lg[:, 0], caches


def decode_step(params, caches: HybridCaches, batch, *, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.act_dtype)
    x = L.embed(params["embed"], batch["token"], dtype)
    x, caches = _run_cached(params, x, caches, cfg, dtype, prefill_mode=False)
    hidden = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    lg = L.unembed(params["embed"], hidden)
    return lg[:, 0], caches
