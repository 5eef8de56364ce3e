"""Layer-level oracles: blocked attention, flash decode, chunked scans,
chunked cross-entropy, token-chunked MoE dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models import moe
from repro.models.mamba2 import ssd_chunked
from repro.models.rwkv6 import wkv_chunked

KEY = jax.random.PRNGKey(0)


def k(i):
    return jax.random.fold_in(KEY, i)


@pytest.mark.parametrize("window", [0, 7])
@pytest.mark.parametrize("S,qb,kb", [(37, 8, 16), (64, 64, 64), (50, 16, 8)])
def test_blocked_attention_vs_naive(window, S, qb, kb):
    B, H, kvH, hd = 2, 4, 2, 16
    q = jax.random.normal(k(1), (B, S, H, hd))
    kk = jax.random.normal(k(2), (B, S, kvH, hd))
    vv = jax.random.normal(k(3), (B, S, kvH, hd))
    got = L.blocked_attention(q, kk, vv, window=window, q_block=qb, kv_block=kb)
    want = L._naive_attention(q, kk, vv, causal=True, window=window,
                              cross=False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_cross_attention():
    B, Sq, Skv, H, hd = 2, 9, 21, 3, 8
    q = jax.random.normal(k(4), (B, Sq, H, hd))
    kk = jax.random.normal(k(5), (B, Skv, H, hd))
    vv = jax.random.normal(k(6), (B, Skv, H, hd))
    got = L.blocked_attention(q, kk, vv, causal=False, cross=True, q_block=4,
                              kv_block=8)
    want = L._naive_attention(q, kk, vv, causal=False, window=0, cross=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_decode_matches_full_attention():
    B, S, H, kvH, hd = 2, 40, 4, 2, 16
    kk = jax.random.normal(k(7), (B, S, kvH, hd))
    vv = jax.random.normal(k(8), (B, S, kvH, hd))
    cache = L.KVCache.create(B, kvH, 48, hd, n_chunks=4, dtype=jnp.float32)
    cache = L.cache_prefill(cache, kk, vv)
    q = jax.random.normal(k(9), (B, 1, H, hd))
    got = L.flash_decode(q, cache)
    want = L._naive_attention(q, kk, vv, causal=True, window=0, cross=False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_cache_insert_then_decode():
    B, kvH, hd = 1, 2, 8
    cache = L.KVCache.create(B, kvH, 16, hd, n_chunks=4, dtype=jnp.float32)
    ks_, vs_ = [], []
    for i in range(5):
        kn = jax.random.normal(k(10 + i), (B, 1, kvH, hd))
        vn = jax.random.normal(k(20 + i), (B, 1, kvH, hd))
        cache = L.cache_insert(cache, kn, vn)
        ks_.append(kn)
        vs_.append(vn)
    assert int(cache.length) == 5
    q = jax.random.normal(k(30), (B, 1, 4, hd))
    got = L.flash_decode(q, cache)
    want = L._naive_attention(q, jnp.concatenate(ks_, 1),
                              jnp.concatenate(vs_, 1), causal=True, window=0,
                              cross=False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_wkv_chunked_vs_naive():
    B, S, H, K = 2, 37, 3, 8
    r, kk, v = (jax.random.normal(k(40 + i), (B, S, H, K)) for i in range(3))
    lw = -jax.nn.softplus(jax.random.normal(k(43), (B, S, H, K)))
    u = 0.3 * jax.random.normal(k(44), (H, K))
    s0 = jax.random.normal(k(45), (B, H, K, K))
    yc, sc = wkv_chunked(r, kk, v, lw, u, s0, chunk=16)
    s = s0
    ys = []
    for t in range(S):
        rt, kt, vt = r[:, t], kk[:, t], v[:, t]
        y = jnp.einsum("bhk,bhkv->bhv", rt,
                       s + u[None, :, :, None] * jnp.einsum("bhk,bhv->bhkv",
                                                            kt, vt))
        ys.append(y)
        s = jnp.exp(lw[:, t])[..., None] * s + jnp.einsum("bhk,bhv->bhkv",
                                                          kt, vt)
    np.testing.assert_allclose(yc, jnp.stack(ys, 1), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(sc, s, rtol=3e-4, atol=3e-4)


def test_ssd_chunked_vs_naive():
    B, S, H, P, N = 2, 29, 3, 4, 5
    xh = jax.random.normal(k(50), (B, S, H, P))
    la = -jax.nn.softplus(jax.random.normal(k(51), (B, S, H)))
    Bm = jax.random.normal(k(52), (B, S, N))
    Cm = jax.random.normal(k(53), (B, S, N))
    h0 = jax.random.normal(k(54), (B, H, P, N))
    yc, hc = ssd_chunked(xh, la, Bm, Cm, h0, chunk=8)
    h = h0
    ys = []
    for t in range(S):
        h = jnp.exp(la[:, t])[..., None, None] * h + jnp.einsum(
            "bhp,bn->bhpn", xh[:, t], Bm[:, t])
        ys.append(jnp.einsum("bn,bhpn->bhp", Cm[:, t], h))
    np.testing.assert_allclose(yc, jnp.stack(ys, 1), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(hc, h, rtol=3e-4, atol=3e-4)


def test_chunked_cross_entropy_matches_full():
    B, S, D, V = 2, 23, 16, 97
    hidden = jax.random.normal(k(60), (B, S, D), jnp.bfloat16)
    table = {"table": jax.random.normal(k(61), (V, D))}
    labels = jax.random.randint(k(62), (B, S), 0, V)
    got = L.cross_entropy_chunked(hidden, table, labels, chunk=8)
    logits = L.unembed(table, hidden)
    want = L.cross_entropy(logits, labels)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_mrope_matches_rope_for_equal_ids():
    """Text-only M-RoPE (all three components equal) == standard RoPE."""
    B, S, H, hd = 2, 11, 3, 16
    x = jax.random.normal(k(70), (B, S, H, hd))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    pos3 = jnp.broadcast_to(pos[None], (3, B, S))
    a = L.apply_rope(x, pos, 1e4)
    b = L.apply_rope(x, pos3, 1e4)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("S", [8, 9], ids=["chunk_divides", "chunks_raised"])
def test_moe_token_chunks_match_one_dispatch(S, monkeypatch):
    """Past ``MOE_CHUNK_TOKENS`` tokens, ``moe_ffn`` dispatches the tokens
    in equal chunks through a scan. T = 16 takes 4 chunks of 4; T = 18
    takes 6 of 3, since 5 does not divide it. With capacity for every token
    (capacity_factor = E / top_k), the chunks give one dispatch's result."""
    from repro.models.registry import get_config
    cfg = get_config("dbrx-132b").reduced()
    cfg = cfg.reduced(capacity_factor=cfg.n_experts / cfg.top_k)
    p = moe.init_moe_ffn(k(80), cfg)
    x = jax.random.normal(k(81), (2, S, cfg.d_model))
    want = moe._moe_tokens(p, x, cfg, jnp.float32)
    monkeypatch.setattr(moe, "MOE_CHUNK_TOKENS", 4)
    fn = jax.jit(lambda p, x: moe.moe_ffn(p, x, cfg, jnp.float32))
    assert "scan[" in str(jax.make_jaxpr(fn)(p, x))
    np.testing.assert_allclose(fn(p, x), want, rtol=1e-5, atol=1e-5)
