"""Pure-jnp reference implementations of the aggregation rules.

This module is the *reference backend* of :mod:`repro.agg`: every rule here is
plain jnp, jit/vmap/grad-compatible, and is what the Pallas kernels under
``repro.kernels`` are numerically checked against (tests/test_agg_backends.py).
Flat rules operate on a stack ``x`` of shape ``[n, d]`` with a declared number
of Byzantine inputs ``f``; each rule's natural arity is declared in the
registry (``repro.agg.registry``), so rules that ignore ``f`` simply do not
take it.

The paper's rules:
  * MDA   (Minimum-Diameter Averaging)  — tolerates f Byzantine among n >= 2f+1.
  * Median (coordinate-wise)            — tolerates f among n >= 2f+1.
  * MeaMed (mean-around-median)         — used by the synchronous worker gather.
Baselines the paper compares against / cites:
  * Krum, Multi-Krum (Blanchard et al. 2017), Bulyan, trimmed mean, plain mean.

Masked-delivery semantics
-------------------------
``masked_*`` variants and the ``*_weights_from_d2(..., mask=...)`` selection
helpers aggregate only the *delivered* subset indicated by a boolean ``[n]``
mask, with the delivered count ``q = sum(mask)`` allowed to be a traced value
(they are used inside jit where quorums are sampled on-device). For rules with
an order statistic this is done with sort tricks (non-delivered entries pushed
past the delivered ones) rather than dynamic gathers, so shapes stay static.
"""
from __future__ import annotations

import itertools
import math
import os
from contextlib import contextmanager
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

_BIG = jnp.float32(3.4e38)     # sorts after every real value, stays finite
_LATE = jnp.float32(1e30)      # "selectable, but after all delivered" score

# ---------------------------------------------------------------------------
# distances
# ---------------------------------------------------------------------------


def pairwise_sqdists(x: jax.Array) -> jax.Array:
    """Exact pairwise squared L2 distances via the Gram matrix. [n,d] -> [n,n].

    The Gram formulation is what makes the *sharded* distributed MDA possible:
    partial Grams over coordinate shards sum to the full Gram (see protocol.py).
    """
    x = x.astype(jnp.float32)
    sq = jnp.sum(x * x, axis=-1)
    gram = x @ x.T
    d2 = sq[:, None] + sq[None, :] - 2.0 * gram
    return jnp.maximum(d2, 0.0)


def sqdists_from_gram(gram: jax.Array) -> jax.Array:
    """[n,n] Gram -> [n,n] squared distances (used by the sharded protocol)."""
    sq = jnp.diagonal(gram)
    return jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)


# ---------------------------------------------------------------------------
# MDA — Minimum-Diameter Averaging (the paper's worker-side GAR)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def subset_masks(n: int, f: int) -> np.ndarray:
    """All C(n, n-f) subsets of size n-f as a static bool mask array [S, n]."""
    if not 0 <= f < n:
        raise ValueError(f"need 0 <= f < n, got n={n} f={f}")
    masks = np.zeros((math.comb(n, n - f), n), dtype=bool)
    for i, c in enumerate(itertools.combinations(range(n), n - f)):
        masks[i, list(c)] = True
    return masks


def n_subsets(n: int, f: int) -> int:
    return math.comb(n, n - f)


def subset_diameters(d2: jax.Array, masks: jax.Array) -> jax.Array:
    """Max in-subset squared distance for each subset mask. [n,n],[S,n] -> [S]."""
    pair = masks[:, :, None] & masks[:, None, :]  # [S, n, n]
    return jnp.max(jnp.where(pair, d2[None], -jnp.inf), axis=(1, 2))


def mda_select_exact(d2: jax.Array, f: int, *,
                     diameters_fn=subset_diameters) -> jax.Array:
    """Exact minimum-diameter subset selection -> bool mask [n].

    ``diameters_fn`` lets the dispatch layer substitute the Pallas
    subset-diameter kernel while the enumeration stays here.
    """
    n = d2.shape[0]
    masks = jnp.asarray(subset_masks(n, f))
    diam = diameters_fn(d2, masks)
    return masks[jnp.argmin(diam)]


def mda_select_greedy(d2: jax.Array, f: int) -> jax.Array:
    """Greedy 2-approximation of the min-diameter subset -> bool mask [n].

    Seeds with the closest pair, then repeatedly adds the vector whose inclusion
    minimises the resulting diameter. O(n^2) selection given the distance matrix.
    Used when C(n, f) exceeds ``exact_limit`` (e.g. the 32-worker multi-pod
    mesh). DESIGN.md §2 discusses why Lemma 4.6 still holds up to a factor 2.
    """
    n = d2.shape[0]
    big = jnp.inf
    d2m = jnp.where(jnp.eye(n, dtype=bool), big, d2)
    ij = jnp.argmin(d2m)
    i, j = ij // n, ij % n
    sel = jnp.zeros((n,), bool).at[i].set(True).at[j].set(True)
    for _ in range(n - f - 2):
        # new diameter if k joined = max(current max dist to sel, in-sel diameter)
        dist_to_sel = jnp.max(jnp.where(sel[None, :], d2, -big), axis=1)  # [n]
        cand = jnp.where(sel, big, dist_to_sel)
        k = jnp.argmin(cand)
        sel = sel.at[k].set(True)
    return sel


def mda_select_greedy_masked(d2: jax.Array, f: int,
                             delivered: jax.Array) -> jax.Array:
    """Greedy min-diameter selection restricted to a delivered subset.

    Returns float32 weights [n] summing to 1 over the selected q-f delivered
    vectors (q = sum(delivered), allowed to be traced). The greedy order visits
    every delivered vector before any non-delivered one (their distances are
    pushed to a large finite sentinel), and the selection keeps the first
    q - f additions — with a full mask this reproduces ``mda_select_greedy``.
    """
    n = d2.shape[0]
    delivered = delivered.astype(bool)
    q = jnp.sum(delivered)
    pair_ok = delivered[:, None] & delivered[None, :]
    eye = jnp.eye(n, dtype=bool)
    d2d = jnp.where(pair_ok, d2, _LATE)          # undelivered pairs sort last
    ij = jnp.argmin(jnp.where(eye, jnp.inf, d2d))
    i, j = ij // n, ij % n
    sel0 = jnp.zeros((n,), bool).at[i].set(True).at[j].set(True)
    order0 = jnp.full((n,), n, jnp.int32).at[i].set(0).at[j].set(1)

    def body(s, carry):
        sel, order = carry
        dist_to_sel = jnp.max(jnp.where(sel[None, :], d2d, -jnp.inf), axis=1)
        cand = jnp.where(sel, jnp.inf, dist_to_sel)
        k = jnp.argmin(cand)
        return sel.at[k].set(True), order.at[k].set(s)

    _, order = jax.lax.fori_loop(2, n, body, (sel0, order0))
    keep = (q - f).astype(jnp.int32)
    sel = (order < jnp.maximum(keep, 1)) & delivered
    return sel.astype(jnp.float32) / jnp.maximum(jnp.sum(sel), 1)


def mda(x: jax.Array, f: int, *, exact_limit: int = 200_000,
        d2: jax.Array | None = None) -> jax.Array:
    """Minimum-Diameter Averaging. [n,d] -> [d].

    Average of the size-(n-f) subset with minimal L2 diameter (exact when the
    subset count is tractable, greedy otherwise).
    """
    n = x.shape[0]
    if n < 2 * f + 1:
        raise ValueError(f"MDA needs n >= 2f+1 (n={n}, f={f})")
    if f == 0:
        return jnp.mean(x, axis=0)
    if d2 is None:
        d2 = pairwise_sqdists(x)
    if n_subsets(n, f) <= exact_limit:
        sel = mda_select_exact(d2, f)
    else:
        sel = mda_select_greedy(d2, f)
    w = sel.astype(x.dtype) / (n - f)
    return w @ x


def mda_selection(d2: jax.Array, f: int, *, exact_limit: int = 200_000,
                  diameters_fn=subset_diameters) -> jax.Array:
    """Subset mask only (used by the sharded protocol where averaging is local)."""
    n = d2.shape[0]
    if f == 0:
        return jnp.ones((n,), bool)
    if n_subsets(n, f) <= exact_limit:
        return mda_select_exact(d2, f, diameters_fn=diameters_fn)
    return mda_select_greedy(d2, f)


def mda_weights_from_d2(d2: jax.Array, f: int, *, mask: jax.Array | None = None,
                        exact_limit: int = 200_000,
                        diameters_fn=subset_diameters) -> jax.Array:
    """[n,n] distances -> [n] float32 averaging weights (rows of the GAR).

    The d2-level entry point used by both the flat rule and the pytree /
    sharded-protocol paths (which build d2 from leaf-partial Grams). With a
    ``mask``, selection is restricted to delivered senders via the greedy
    scan (traced-q compatible).
    """
    n = d2.shape[0]
    if mask is not None:
        return mda_select_greedy_masked(d2, f, mask)
    sel = mda_selection(d2, f, exact_limit=exact_limit,
                        diameters_fn=diameters_fn)
    return sel.astype(jnp.float32) / (n - f if f else n)


# ---------------------------------------------------------------------------
# small-stack sorting network (hot-path optimization)
# ---------------------------------------------------------------------------

_NETWORK_MAX_N = 32

# Escape hatch: REPRO_SORT_NETWORK=0 (or use_sort_network(False)) routes the
# order-statistic rules back through XLA's jnp.sort — bitwise jnp.sort
# semantics for debugging, and the honest "seed hot path" lane of
# benchmarks/exp_throughput.py. Flipping it only affects traces compiled
# afterwards. The env var is resolved at CALL time (an import-time read
# would freeze the flag before tests/overrides can set it and poison the
# engines' compile-cache keys — REPRO-ENV-IMPORT); use_sort_network()
# takes precedence over the environment while active.
_SORT_NETWORK: bool | None = None    # None = defer to the environment


def sort_network_enabled() -> bool:
    """Current sort-network setting: the use_sort_network() override if one
    is active, else the REPRO_SORT_NETWORK environment default. Engines fold
    this into their compile-cache keys."""
    if _SORT_NETWORK is not None:
        return _SORT_NETWORK
    return os.environ.get("REPRO_SORT_NETWORK", "1") != "0"


@contextmanager
def use_sort_network(on: bool):
    global _SORT_NETWORK
    prev, _SORT_NETWORK = _SORT_NETWORK, bool(on)
    try:
        yield
    finally:
        _SORT_NETWORK = prev


@lru_cache(maxsize=None)
def oddeven_pairs(n: int) -> tuple[tuple[int, int], ...]:
    """Batcher odd-even merge-sort compare-exchange schedule for arbitrary n."""
    pairs = []
    p = 1
    while p < n:
        k = p
        while k >= 1:
            for j in range(k % p, n - k, 2 * k):
                for i in range(min(k, n - j - k)):
                    if (i + j) // (2 * p) == (i + j + k) // (2 * p):
                        pairs.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return tuple(pairs)


def network_sorts(n: int) -> bool:
    """Whether :func:`sort_stack` sorts ``n`` rows with the compare-exchange
    network (else with ``jnp.sort``, whose NaNs sort last as NaN)."""
    return n <= _NETWORK_MAX_N and sort_network_enabled()


def sort_stack(x: jax.Array) -> jax.Array:
    """``jnp.sort(x, axis=0)`` for a small static stack, as a compare-exchange
    network of vectorized min/max pairs.

    XLA lowers a generic sort to a per-coordinate comparator loop on CPU,
    which costs ~ms for the [n_quorum, d_model] stacks every protocol step
    sorts (the coordinate-wise Median pull is the single hottest op in the
    simulator). The Batcher network is pure elementwise min/max over full
    rows — order-of-magnitude faster on CPU and fusion-friendly inside the
    scanned epoch (repro.core.engine). Sorted *values* are identical to
    ``jnp.sort`` (value sorts are tie-insensitive); rules that need argsort
    keep the XLA sort for its stable tie-breaking. Falls back to ``jnp.sort``
    beyond n=32 (use the Pallas kernel there).
    """
    n = x.shape[0]
    if n <= 1:
        return x
    if not network_sorts(n):
        return jnp.sort(x, axis=0)
    # min/max would smear a single NaN across every rank; map NaN to the
    # finite _BIG sentinel first so Byzantine NaN payloads sort last exactly
    # like jnp.sort's NaN ordering (and get trimmed/outranked, not returned).
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = jnp.where(jnp.isnan(x), jnp.asarray(_BIG, x.dtype), x)
    rows = list(x)
    for i, j in oddeven_pairs(n):
        a, b = rows[i], rows[j]
        rows[i] = jnp.minimum(a, b)
        rows[j] = jnp.maximum(a, b)
    return jnp.stack(rows, axis=0)


def median_stack(x: jax.Array) -> jax.Array:
    """``jnp.median(x, axis=0)`` via :func:`sort_stack`."""
    n = x.shape[0]
    xs = sort_stack(x)
    if n % 2:
        return xs[n // 2]
    return 0.5 * (xs[n // 2 - 1] + xs[n // 2])


# ---------------------------------------------------------------------------
# coordinate-wise rules
# ---------------------------------------------------------------------------


def coordinate_median(x: jax.Array) -> jax.Array:
    """Coordinate-wise median ("Median" in the paper). [n,d] -> [d]."""
    return median_stack(x)


def masked_coordinate_median(x: jax.Array, delivered: jax.Array) -> jax.Array:
    """Median over the delivered subset only (asynchrony). [n,d],[n] -> [d].

    Non-delivered entries are pushed to +/-inf in equal numbers so the median of
    the remaining q values is recovered exactly for any q (sort-based).
    """
    q = jnp.sum(delivered)
    big = jnp.asarray(3.4e38, x.dtype)
    mask = delivered.reshape((-1,) + (1,) * (x.ndim - 1))
    xs = sort_stack(jnp.where(mask, x, big))  # delivered entries sort first
    lo = ((q - 1) // 2).astype(jnp.int32)
    hi = (q // 2).astype(jnp.int32)
    return 0.5 * (jnp.take(xs, lo, axis=0) + jnp.take(xs, hi, axis=0))


def vote(x: jax.Array) -> jax.Array:
    """Coordinate-wise plurality vote: per coordinate, the value held by the
    most inputs (ties break toward the lowest input index). [n, ...] -> [...].

    The read-quorum rule for *discrete* outputs (serving: argmax token ids):
    with n >= 2f+1 identical honest values, f arbitrary corruptions can never
    outvote the honest majority. Exact on any dtype — no averaging, the answer
    is always one of the inputs."""
    eq = (x[None, ...] == x[:, None, ...])          # [n, n, ...] pairwise
    counts = jnp.sum(eq, axis=1)                    # [n, ...] per coordinate
    win = jnp.argmax(counts, axis=0)                # [...] first max
    return jnp.take_along_axis(x, win[None, ...], axis=0)[0]


def masked_vote(x: jax.Array, delivered: jax.Array) -> jax.Array:
    """Plurality vote over the delivered subset only. [n, ...],[n] -> [...].

    Pairs are counted only between delivered inputs and undelivered rows get
    count -1, so the winner is exactly ``vote(x[delivered])`` (first-index tie
    break included: the subset gather preserves input order)."""
    m = delivered.astype(bool)
    shape = (-1,) + (1,) * (x.ndim - 1)
    pair = (m[:, None] & m[None, :]).reshape(m.shape * 2 + (1,) * (x.ndim - 1))
    eq = (x[None, ...] == x[:, None, ...]) & pair
    counts = jnp.where(m.reshape(shape), jnp.sum(eq, axis=1), -1)
    win = jnp.argmax(counts, axis=0)
    return jnp.take_along_axis(x, win[None, ...], axis=0)[0]


def mean(x: jax.Array) -> jax.Array:
    """Vanilla averaging (not Byzantine resilient — the paper's strawman)."""
    return jnp.mean(x, axis=0)


def masked_mean(x: jax.Array, delivered: jax.Array) -> jax.Array:
    """Mean of the delivered subset. [n,d],[n] -> [d]."""
    w = delivered.astype(jnp.float32)
    shape = (-1,) + (1,) * (x.ndim - 1)
    num = jnp.sum(x.astype(jnp.float32) * w.reshape(shape), axis=0)
    return (num / jnp.maximum(jnp.sum(w), 1.0)).astype(x.dtype)


def trimmed_mean(x: jax.Array, f: int) -> jax.Array:
    """Coordinate-wise trimmed mean: drop f lowest and f highest per coordinate."""
    n = x.shape[0]
    if n <= 2 * f:
        raise ValueError("trimmed_mean needs n > 2f")
    xs = sort_stack(x)
    return jnp.mean(xs[f:n - f], axis=0)


def masked_trimmed_mean(x: jax.Array, f: int, delivered: jax.Array) -> jax.Array:
    """Trimmed mean over the delivered subset: drop the f lowest and f highest
    of the q delivered values per coordinate (q may be traced)."""
    n = x.shape[0]
    q = jnp.sum(delivered)
    shape = (-1,) + (1,) * (x.ndim - 1)
    big = jnp.asarray(_BIG, x.dtype)
    xs = sort_stack(jnp.where(delivered.reshape(shape), x, big))
    rank = jnp.arange(n).reshape(shape)
    keep = (rank >= f) & (rank < q - f)
    num = jnp.sum(jnp.where(keep, xs.astype(jnp.float32), 0.0), axis=0)
    return (num / jnp.maximum(q - 2 * f, 1)).astype(x.dtype)


def meamed(x: jax.Array, f: int) -> jax.Array:
    """Mean-around-Median (Xie et al. 2018): per coordinate, mean of the n-f
    values closest to the coordinate median."""
    n = x.shape[0]
    med = median_stack(x)[None]
    dist = jnp.abs(x - med)
    idx = jnp.argsort(dist, axis=0)[: n - f]  # [n-f, d]
    vals = jnp.take_along_axis(x, idx, axis=0)
    return jnp.mean(vals, axis=0)


def masked_meamed(x: jax.Array, f: int, delivered: jax.Array) -> jax.Array:
    """Mean-around-Median over the delivered subset: per coordinate, mean of
    the q-f delivered values closest to the delivered median."""
    n = x.shape[0]
    q = jnp.sum(delivered)
    shape = (-1,) + (1,) * (x.ndim - 1)
    med = masked_coordinate_median(x, delivered)[None]
    dist = jnp.where(delivered.reshape(shape), jnp.abs(x - med), _BIG)
    order = jnp.argsort(dist, axis=0)                       # delivered first
    vals = jnp.take_along_axis(x, order, axis=0)
    rank = jnp.arange(n).reshape(shape)
    keep = rank < jnp.maximum(q - f, 1)
    num = jnp.sum(jnp.where(keep, vals.astype(jnp.float32), 0.0), axis=0)
    return (num / jnp.maximum(q - f, 1)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Krum family (baselines)
# ---------------------------------------------------------------------------


def _krum_scores(d2: jax.Array, f: int) -> jax.Array:
    """Krum score: sum of the n-f-2 smallest squared distances to neighbours."""
    n = d2.shape[0]
    m = n - f - 2
    if m < 1:
        raise ValueError(f"Krum needs n >= f+3 (n={n}, f={f})")
    d2nd = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, d2)
    srt = jnp.sort(d2nd, axis=1)
    return jnp.sum(srt[:, :m], axis=1)


def _krum_scores_masked(d2: jax.Array, f: int, delivered: jax.Array) -> jax.Array:
    """Krum scores over the delivered subset: each delivered vector scores the
    sum of its q-f-2 smallest distances to delivered neighbours (q traced);
    non-delivered vectors score +inf."""
    n = d2.shape[0]
    delivered = delivered.astype(bool)
    q = jnp.sum(delivered)
    ok = delivered[:, None] & delivered[None, :] & ~jnp.eye(n, dtype=bool)
    srt = jnp.sort(jnp.where(ok, d2, jnp.inf), axis=1)
    m = jnp.maximum(q - f - 2, 1)
    keep = jnp.arange(n)[None, :] < m
    scores = jnp.sum(jnp.where(keep & jnp.isfinite(srt), srt, 0.0), axis=1)
    return jnp.where(delivered, scores, jnp.inf)


def krum_weights_from_d2(d2: jax.Array, f: int,
                         *, mask: jax.Array | None = None) -> jax.Array:
    """One-hot [n] float32 weights on the best-scored vector."""
    scores = (_krum_scores(d2, f) if mask is None
              else _krum_scores_masked(d2, f, mask))
    return jax.nn.one_hot(jnp.argmin(scores), d2.shape[0], dtype=jnp.float32)


def multi_krum_weights_from_d2(d2: jax.Array, f: int, *,
                               mask: jax.Array | None = None,
                               m: int | None = None) -> jax.Array:
    """[n] float32 averaging weights over the m best-scored vectors
    (default m = n - f, or q - f under a delivery mask)."""
    n = d2.shape[0]
    if mask is None:
        scores = _krum_scores(d2, f)
        mm = n - f if m is None else m
        sel = jnp.zeros((n,), bool).at[jnp.argsort(scores)[:mm]].set(True)
    else:
        scores = _krum_scores_masked(d2, f, mask)
        q = jnp.sum(mask.astype(jnp.int32))
        mm = jnp.maximum(q - f, 1) if m is None else m
        rank = jnp.argsort(jnp.argsort(scores))
        sel = rank < mm
    return sel.astype(jnp.float32) / jnp.maximum(jnp.sum(sel), 1)


def krum(x: jax.Array, f: int) -> jax.Array:
    """Krum (Blanchard et al. 2017): the single vector with the best score."""
    scores = _krum_scores(pairwise_sqdists(x), f)
    return x[jnp.argmin(scores)]


def multi_krum(x: jax.Array, f: int, m: int | None = None) -> jax.Array:
    """Multi-Krum: average of the m best-scored vectors (default m = n - f)."""
    n = x.shape[0]
    m = n - f if m is None else m
    scores = _krum_scores(pairwise_sqdists(x), f)
    idx = jnp.argsort(scores)[:m]
    return jnp.mean(x[idx], axis=0)


def bulyan(x: jax.Array, f: int) -> jax.Array:
    """Bulyan (El Mhamdi et al. 2018): n-2f rounds of Krum selection, then
    coordinate-wise trimmed aggregation around the median. Needs n >= 4f+3."""
    n = x.shape[0]
    theta = n - 2 * f
    if theta < 1:
        raise ValueError(f"Bulyan needs n >= 4f+3 (n={n}, f={f})")
    d2 = pairwise_sqdists(x)
    alive = jnp.ones((n,), bool)
    picks = []
    for _ in range(theta):
        d2a = jnp.where(alive[None, :] & alive[:, None] & ~jnp.eye(n, dtype=bool),
                        d2, jnp.inf)
        srt = jnp.sort(d2a, axis=1)
        m = max(n - f - 2, 1)
        scores = jnp.sum(jnp.where(jnp.isinf(srt[:, :m]), 0.0, srt[:, :m]), axis=1)
        scores = jnp.where(alive, scores, jnp.inf)
        k = jnp.argmin(scores)
        picks.append(x[k])
        alive = alive.at[k].set(False)
    sel = jnp.stack(picks)  # [theta, d]
    beta = theta - 2 * f
    med = jnp.median(sel, axis=0, keepdims=True)
    idx = jnp.argsort(jnp.abs(sel - med), axis=0)[:max(beta, 1)]
    return jnp.mean(jnp.take_along_axis(sel, idx, axis=0), axis=0)


# ---------------------------------------------------------------------------
# variance-to-norm bounds (Appendix D / Fig. 7 reproduction)
# ---------------------------------------------------------------------------


def mda_variance_threshold(n: int, f: int) -> float:
    """Eq. (3)/(7): MDA is safe while stddev/||grad|| <= (n-f) / (2f)."""
    return float(n - f) / (2.0 * f) if f > 0 else float("inf")


def krum_variance_threshold(n: int, f: int) -> float:
    """Blanchard et al. 2017 condition: eta(n,f) * sigma < ||grad||, i.e. the
    usable stddev/norm ratio is 1/eta with
    eta(n,f) = sqrt(2 (n - f + f(n-f-2) + f^2 (n-f-1) / (n-2f-2)))."""
    if f == 0:
        return float("inf")
    if n - 2 * f - 2 <= 0:
        return 0.0
    eta2 = 2.0 * (n - f + (f * (n - f - 2) + f * f * (n - f - 1)) / (n - 2 * f - 2))
    return 1.0 / math.sqrt(eta2)
