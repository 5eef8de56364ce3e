"""RWKV6 ("Finch") — attention-free RNN with data-dependent per-channel decay
(arXiv:2404.05892; RWKV-LM's ``RWKV_Tmix_x060`` / ``RWKV_CMix_x060``).

Per head (K = V = head dim):
    y_t = r_t . (S_{t-1} + diag(u * k_t) v_t),   S_t = diag(d_t) S_{t-1} + k_t (x) v_t
with d_t = exp(-exp(w_t)) and w_t = w0 + tanh(x^w_t A_w) B_w — the *data-
dependent decay*. The token shift is Finch's ddlerp: with xx = shift(x) - x,
m = tanh((x + xx * maa_x) W1) split into five rank-32 parts, each times its
W2, and x^* = x + xx * (maa_* + m_*) for * in w, k, v, r, g. The WKV output
goes through a per-head GroupNorm (eps 1e-5 * head_size_divisor^2) and the
gate silu(x^g Wg) before the output projection. The model puts a LayerNorm
(``ln0``) on the embedding before the first block and reads its logits from
an untied head unless ``tie_embeddings``.

Training runs the WKV chunk-parallel, in chunks of 16 tokens laid out
[N,B,H,C,K]. Everything that does not read the carried state runs for all
chunks at once: the chunk's cumulative log decay L, the intra-chunk
attention with its pairwise decays exp(L_{t-1} - L_j), exact in log space
(the exponent is <= 0 for j < t, so nothing overflows), its product with v
and the current token's bonus. Its backward (a custom VJP) recomputes the
pairwise decays inside each reduction that reads them, so no [C,C,K] tensor
is kept for all chunks. Only the [B,H,K,V] state passes from chunk to
chunk, in a scan whose step reads it for the chunk's inter-chunk output and
adds the chunk's own k (x) v. Decode is the O(1)-state recurrence =>
500k-token decode is sub-quadratic.

Departure from the published model: a log decay below ``LOG_DECAY_FLOOR``
(-20) is taken as -20, i.e. a decay below e^-20 ~ 2e-9 is taken as e^-20.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from .config import ArchConfig
from .sharding import shard

LOG_DECAY_FLOOR = -20.0
MIX_LORA = 32            # rank of each of ddlerp's five mixes
DECAY_LORA = 64          # rank of the decay's LoRA
HEAD_SIZE_DIVISOR = 8    # ln_x's eps is norm_eps * HEAD_SIZE_DIVISOR ** 2
MIXES = ("w", "k", "v", "r", "g")


class RwkvCache(NamedTuple):
    shift_t: jax.Array   # [B, D] last token entering time-mix
    shift_c: jax.Array   # [B, D] last token entering channel-mix
    wkv: jax.Array       # [B, H, K, V] state


def dims(cfg: ArchConfig):
    K = cfg.ssm_head_dim
    H = cfg.d_model // K
    return H, K


def init_block(key, cfg: ArchConfig):
    D, F = cfg.d_model, cfg.d_ff
    H, K = dims(cfg)
    ks = iter(jax.random.split(key, 20))
    mu = lambda: jax.random.uniform(next(ks), (D,), jnp.float32)
    return {
        "ln1": L.init_layernorm(D),
        "ln2": L.init_layernorm(D),
        # ddlerp: RWKV-LM's init, W1 zero and W2 small
        "maa_x": mu(), **{f"maa_{m}": mu() for m in MIXES},
        "maa_w1": jnp.zeros((D, len(MIXES) * MIX_LORA), jnp.float32),
        "maa_w2": jax.random.uniform(next(ks), (len(MIXES), MIX_LORA, D),
                                     jnp.float32, -0.01, 0.01),
        "Wr": L._init_dense(next(ks), D, D, D),
        "Wk": L._init_dense(next(ks), D, D, D),
        "Wv": L._init_dense(next(ks), D, D, D),
        "Wg": L._init_dense(next(ks), D, D, D),
        "w0": jnp.full((D,), 1.0, jnp.float32),   # exp(1) ~ strong decay init
        "wA": L._init_dense(next(ks), D, D, DECAY_LORA),
        "wB": jnp.zeros((DECAY_LORA, D), jnp.float32),
        "u": (0.1 * jax.random.normal(next(ks), (H, K))).astype(jnp.float32),
        "ln_x": L.init_layernorm(D),
        "Wo": L._init_dense(next(ks), D, D, D),
        # channel mix
        "mu_ck": mu(),
        "mu_cr": mu(),
        "cWk": L._init_dense(next(ks), D, D, F),
        "cWv": L._init_dense(next(ks), F, F, D),
        "cWr": L._init_dense(next(ks), D, D, D),
    }


def _shift(x, last):
    """Token shift: [B,S,D] -> previous token per position; last: [B,D]."""
    return jnp.concatenate([last[:, None].astype(x.dtype), x[:, :-1]], axis=1)


@partial(jax.jit, static_argnames="axis")
def _dot(a, b, axis: int):
    """sum(a * b) over ``axis``, a and b broadcast against each other. A jit
    of its own: the computation that adds a reduction's terms, and its
    transpose's, carry the op_name of the innermost traced function alone,
    so inside this jit they do not name the ``wkv`` scope without the
    stage around it."""
    return jnp.sum(a * b, axis=axis)


def _cumsum(x, axis: int):
    """Inclusive sum along ``axis`` (a chunk's tokens), as one reduction
    over a [j <= t] mask."""
    C = x.shape[axis]
    shape = [1] * (x.ndim + 1)
    shape[axis] = shape[axis + 1] = C
    upper = np.triu(np.ones((C, C), np.float32)).reshape(shape)  # [j, t]
    return _dot(jnp.expand_dims(x, axis + 1), upper, axis=axis)


def _decay(D, lower):
    """exp(D) where ``lower`` is 1 (j < t), else 0. D <= 0 where it is
    kept; where it is not, exp(min(D, 0)) is at most 1 and ``lower`` 0."""
    return jnp.exp(jnp.minimum(D, 0.0)) * lower


def _lower(C: int):
    return np.tril(np.ones((C, C), np.float32), k=-1)    # [t, j]: j < t


@jax.custom_vjp
def _intra(r, k, Lp, Lc):
    """A[t,j] = sum_k r_t k_j exp(L_{t-1} - L_j) for j < t, else 0, for
    every chunk at once. r, k, Lp (L_{t-1}) and Lc (L_t): [N,B,H,C,K]; A:
    [N,B,H,C,C]. The pairwise tensor [N,B,H,C,C,K] exists only inside the
    reduction over K."""
    with jax.named_scope("wkv"):
        lower = _lower(Lp.shape[3])[:, :, None]             # [t, j, 1]
        E = _decay(Lp[:, :, :, :, None] - Lc[:, :, :, None], lower)
        return _dot(r[:, :, :, :, None] * k[:, :, :, None], E, axis=5)


def _intra_fwd(r, k, Lp, Lc):
    return _intra(r, k, Lp, Lc), (r, k, Lp, Lc)


def _intra_bwd(res, dA):
    """dr[t] = sum_j dA[t,j] k_j E[t,j] and dk[j] = sum_t dA[t,j] r_t E[t,j];
    the log decays' cotangents follow, dLp = r dr and dLc = -k dk. Each sum
    recomputes E in an arrangement of its own (the summed axis first of the
    pair), so XLA fuses the exponentials into it and no pairwise tensor is
    kept."""
    with jax.named_scope("wkv"):
        r, k, Lp, Lc = res
        lower = _lower(Lp.shape[3])
        # [N,B,H,j,t,K], summed over j
        dr = _dot(dA.swapaxes(3, 4)[..., None] * k[:, :, :, :, None],
                  _decay(Lp[:, :, :, None] - Lc[:, :, :, :, None],
                         lower.T[:, :, None]), axis=3)
        # [N,B,H,t,j,K], summed over t
        dk = _dot(dA[..., None] * r[:, :, :, :, None],
                  _decay(Lp[:, :, :, :, None] - Lc[:, :, :, None],
                         lower[:, :, None]), axis=3)
        return dr, dk, r * dr, -k * dk


_intra.defvjp(_intra_fwd, _intra_bwd)


@partial(jax.jit, static_argnames="chunk")
def _chunk_parts(r, k, v, lw, u, chunk: int):
    """What does not read the carried state, for every chunk at once, all
    [N,B,H,C,K]: the intra-chunk output with the current token's bonus
    (r_t . (u * k_t)) v_t, and the inputs of the state's scan,
    q_t = r_t exp(L_{t-1}), the chunk's own kw_j = k_j exp(L_C - L_j) and v,
    with its decay exp(L_C) [N,B,H,K]."""
    Bsz, S, H, K = r.shape
    nch = S // chunk
    rc, kc, vc, lwc = (a.reshape(Bsz, nch, chunk, H, K).transpose(1, 0, 3, 2, 4)
                       .astype(jnp.float32) for a in (r, k, v, lw))
    Lc = _cumsum(lwc, 3)                                   # inclusive L_t
    Lp = Lc - lwc                                          # L_{t-1}
    LC = Lc[:, :, :, -1:]                                  # the chunk's own
    A = _intra(rc, kc, Lp, Lc)                             # [N,B,H,t,j]
    bonus = _dot(rc * kc, u[None, None, :, None], axis=4)  # [N,B,H,t]
    y = jnp.einsum("nbhtj,nbhjv->nbhtv", A, vc) + bonus[..., None] * vc
    return (y, rc * jnp.exp(Lp), kc * jnp.exp(LC - Lc), vc,
            jnp.exp(LC[:, :, :, 0]))


def wkv_chunked(r, k, v, lw, u, s0, chunk: int = 16):
    """r,k,v: [B,S,H,K]; lw: [B,S,H,K] log decays (<=0); u: [H,K];
    s0: [B,H,K,V]. Returns (y [B,S,H,K], s_final), float32.

    Chunk-parallel: ``_chunk_parts`` runs what does not read the carried
    state for every chunk at once; only the [B,H,K,V] state then passes
    from chunk to chunk, in a scan whose step reads it for the chunk's
    y_t += (r_t exp(L_{t-1})) s and adds the chunk's own
    sum_j exp(L_C - L_j) k_j (x) v_j to its decayed self."""
    Bsz, S, H, K = r.shape
    nch = -(-S // chunk)
    pad = nch * chunk - S
    if pad:
        z4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v, lw = (jnp.pad(a, z4) for a in (r, k, v, lw))
    y, q, kw, vc, a = _chunk_parts(r, k, v, lw, u, chunk=chunk)

    def step(s, xs):
        q_c, kw_c, v_c, a_c = xs
        return (a_c[..., None] * s
                + jnp.einsum("bhck,bhcv->bhkv", kw_c, v_c),
                jnp.einsum("bhck,bhkv->bhcv", q_c, s))

    s_fin, y_inter = jax.lax.scan(step, s0.astype(jnp.float32), (q, kw, vc, a))
    y = (y + y_inter).transpose(1, 0, 3, 2, 4).reshape(Bsz, nch * chunk, H, K)
    return y[:, :S], s_fin


def _ddlerp(p, x, xx, dtype):
    """Finch's data-dependent token shift: x + xx * (maa_* + m_*) for each
    of ``MIXES``, where xx = shift(x) - x."""
    B, S, D = x.shape
    xxx = x + xx * p["maa_x"].astype(dtype)
    m = jnp.tanh(xxx @ p["maa_w1"].astype(dtype))
    m = jnp.einsum("bsfr,frd->fbsd", m.reshape(B, S, len(MIXES), MIX_LORA),
                   p["maa_w2"].astype(dtype))
    return {n: x + xx * (p[f"maa_{n}"].astype(dtype) + m[i])
            for i, n in enumerate(MIXES)}


def time_mix(p, x, cfg: ArchConfig, dtype, cache: RwkvCache | None):
    B, S, D = x.shape
    H, K = dims(cfg)
    last = cache.shift_t if cache is not None else jnp.zeros((B, D), x.dtype)
    xs = _ddlerp(p, x, _shift(x, last) - x, dtype)
    r = (xs["r"] @ p["Wr"].astype(dtype)).reshape(B, S, H, K)
    k = (xs["k"] @ p["Wk"].astype(dtype)).reshape(B, S, H, K)
    v = (xs["v"] @ p["Wv"].astype(dtype)).reshape(B, S, H, K)
    g = xs["g"] @ p["Wg"].astype(dtype)
    xw = xs["w"].astype(jnp.float32)
    wlog = p["w0"] + jnp.tanh(xw @ p["wA"]) @ p["wB"]          # [B,S,D]
    lw = jnp.maximum(-jnp.exp(wlog), LOG_DECAY_FLOOR).reshape(B, S, H, K)

    s0 = (cache.wkv if cache is not None
          else jnp.zeros((B, H, K, K), jnp.float32))
    if S == 1 and cache is not None:  # decode: exact single-step recurrence
        rr, kk, vv = (a[:, 0].astype(jnp.float32) for a in (r, k, v))
        y = jnp.einsum("bhk,bhkv->bhv", rr,
                       s0 + p["u"][None, :, :, None] * jnp.einsum(
                           "bhk,bhv->bhkv", kk, vv))
        s_fin = (jnp.exp(lw[:, 0])[..., None] * s0
                 + jnp.einsum("bhk,bhv->bhkv", kk, vv))
        y = y[:, None]
    else:
        with jax.named_scope("wkv"):
            y, s_fin = wkv_chunked(r, k, v, lw, p["u"], s0)
    y = L.groupnorm(p["ln_x"], y.reshape(B, S, D), H,
                    cfg.norm_eps * HEAD_SIZE_DIVISOR ** 2).astype(dtype)
    out = (y * jax.nn.silu(g)) @ p["Wo"].astype(dtype)
    new_shift = x[:, -1]
    return out, new_shift, s_fin


def channel_mix(p, x, dtype, cache: RwkvCache | None):
    B, S, D = x.shape
    last = cache.shift_c if cache is not None else jnp.zeros((B, D), x.dtype)
    xp = _shift(x, last)
    xk = x + (xp - x) * p["mu_ck"].astype(dtype)
    xr = x + (xp - x) * p["mu_cr"].astype(dtype)
    k = jnp.square(jax.nn.relu(xk @ p["cWk"].astype(dtype)))
    out = jax.nn.sigmoid(xr @ p["cWr"].astype(dtype)) * (k @ p["cWv"].astype(dtype))
    return out, x[:, -1]


def block(p, x, cfg: ArchConfig, dtype, cache: RwkvCache | None = None):
    att, shift_t, wkv = time_mix(p, L.layernorm(p["ln1"], x, cfg.norm_eps),
                                 cfg, dtype, cache)
    x = x + shard(att, "act_btd")
    ffn, shift_c = channel_mix(p, L.layernorm(p["ln2"], x, cfg.norm_eps),
                               dtype, cache)
    x = x + shard(ffn, "act_btd")
    new_cache = (RwkvCache(shift_t.astype(x.dtype), shift_c.astype(x.dtype), wkv)
                 if cache is not None else None)
    return x, new_cache


def init_cache(cfg: ArchConfig, batch: int, dtype=jnp.bfloat16) -> RwkvCache:
    H, K = dims(cfg)
    return RwkvCache(jnp.zeros((batch, cfg.d_model), dtype),
                     jnp.zeros((batch, cfg.d_model), dtype),
                     jnp.zeros((batch, H, K, K), jnp.float32))


# -- full model ---------------------------------------------------------------

def init(key, cfg: ArchConfig):
    ke, kb, kh = jax.random.split(key, 3)
    bkeys = jax.random.split(kb, cfg.n_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg))(bkeys)
    params = {"embed": L.init_embedding(ke, cfg.vocab, cfg.d_model),
              "ln0": L.init_layernorm(cfg.d_model),
              "blocks": blocks, "ln_f": L.init_layernorm(cfg.d_model)}
    if not cfg.tie_embeddings:
        params["head"] = {"table": L._init_dense(kh, cfg.d_model, cfg.vocab,
                                                 cfg.d_model)}
    return params


def _head(params, cfg: ArchConfig):
    return params["embed"] if cfg.tie_embeddings else params["head"]


def _embed(params, tokens, cfg: ArchConfig, dtype):
    x = L.embed(params["embed"], tokens, dtype)
    return shard(L.layernorm(params["ln0"], x, cfg.norm_eps), "act_btd")


def forward(params, tokens, *, cfg: ArchConfig, remat: bool = True):
    dtype = jnp.dtype(cfg.act_dtype)
    x = _embed(params, tokens, cfg, dtype)

    def body(blk, x):
        return block(blk, x, cfg, dtype)[0]

    if remat:
        body = jax.checkpoint(body)

    def scan_body(x, blk):
        return body(blk, x), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    return L.layernorm(params["ln_f"], x, cfg.norm_eps)


def loss(params, batch, *, cfg: ArchConfig):
    hidden = forward(params, batch["tokens"], cfg=cfg)
    return L.cross_entropy_chunked(hidden, _head(params, cfg), batch["labels"])


def init_caches(cfg: ArchConfig, batch: int, max_len: int, n_chunks: int,
                dtype=jnp.bfloat16):
    del max_len, n_chunks  # O(1) state — the point of the architecture
    return jax.vmap(lambda _: init_cache(cfg, batch, dtype))(
        jnp.arange(cfg.n_layers))


def _run_with_cache(params, x, caches, cfg: ArchConfig, dtype):
    def scan_body(x, blk_cache):
        blk, cache = blk_cache
        x, cache = block(blk, x, cfg, dtype, cache)
        return x, cache

    x, caches = jax.lax.scan(scan_body, x, (params["blocks"], caches))
    return L.layernorm(params["ln_f"], x, cfg.norm_eps), caches


def prefill(params, batch, caches, *, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.act_dtype)
    x = _embed(params, batch["tokens"], cfg, dtype)
    hidden, caches = _run_with_cache(params, x, caches, cfg, dtype)
    lg = L.unembed(_head(params, cfg), hidden[:, -1:])
    return lg[:, 0], caches


def decode_step(params, caches, batch, *, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.act_dtype)
    x = _embed(params, batch["token"], cfg, dtype)
    hidden, caches = _run_with_cache(params, x, caches, cfg, dtype)
    lg = L.unembed(_head(params, cfg), hidden)
    return lg[:, 0], caches
