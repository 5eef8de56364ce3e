"""Pallas TPU kernels: coordinate-wise order statistics over a replica stack.

The DMC gather phase and every worker model-pull apply a coordinate-wise
order-statistic rule (Median / MeaMed / trimmed mean) over n <= 64
parameter/model vectors of dimension d (up to 1e11 here) — pure memory-bound
streaming ops (paper complexity O(n_ps * d)). All three kernels stream
[n, block_d] VMEM tiles and share ONE static bitonic sorting network built
from jnp.minimum/maximum (vector ops only; no data-dependent control flow,
so it maps to the VPU with full lanes); the rules differ only in how they
reduce the sorted rows.

n is padded to the next power of two with +inf rows; since pads sort last,
the statistics of the n real rows live in the first n sorted rows.

The masked Median (``masked_median_pallas_call``) is the protocol's pull and
DMC gather: for every receiver at once, the median of the senders its
delivery mask holds, over a whole leaf. It shares the compare-exchange loop
and sorts with Batcher's odd-even network of ``agg.rules.sort_stack``, which
needs no padding rows, so its views equal the jnp route's bit for bit.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...agg.rules import oddeven_pairs

_BIG = 3.4e38  # finite sentinel (f32 max ~3.4e38): NaN, pad lanes and
               # undelivered rows sort last


def bitonic_pairs(n_pow2: int):
    """Static compare-exchange schedule of the bitonic sorting network."""
    pairs = []
    k = 2
    while k <= n_pow2:
        j = k // 2
        while j >= 1:
            stage = []
            for i in range(n_pow2):
                l = i ^ j
                if l > i:
                    ascending = (i & k) == 0
                    stage.append((i, l) if ascending else (l, i))
            pairs.append(stage)
            j //= 2
        k *= 2
    return pairs


def _compare_exchange(rows, pairs):
    """Sort equal-shaped arrays elementwise through a static network of
    (lower, upper) index pairs: the lower takes the min, the upper the max."""
    rows = list(rows)
    for lo_i, hi_i in pairs:
        a, b = rows[lo_i], rows[hi_i]
        rows[lo_i] = jnp.minimum(a, b)
        rows[hi_i] = jnp.maximum(a, b)
    return rows


def _sorted_rows(x_ref, n_pow2: int):
    """Sort the tile's row axis through the shared bitonic network."""
    return _compare_exchange(
        [x_ref[i, :] for i in range(n_pow2)],  # each [block_d]
        [pair for stage in bitonic_pairs(n_pow2) for pair in stage])


def _median_kernel(x_ref, o_ref, *, n: int, n_pow2: int):
    rows = _sorted_rows(x_ref, n_pow2)
    med = 0.5 * (rows[(n - 1) // 2] + rows[n // 2])
    o_ref[0, :] = med


def _trimmed_mean_kernel(x_ref, o_ref, *, n: int, n_pow2: int, f: int):
    """Mean of sorted rows f..n-f-1 (drop the f lowest and f highest)."""
    rows = _sorted_rows(x_ref, n_pow2)
    acc = rows[f]
    for i in range(f + 1, n - f):
        acc = acc + rows[i]
    o_ref[0, :] = acc / (n - 2 * f)


def _meamed_kernel(x_ref, o_ref, *, n: int, n_pow2: int, f: int):
    """Mean-around-Median: per coordinate, mean of the n-f values closest to
    the median. In sorted order those values form a contiguous window
    [i, i+n-f), i <= f, whose max distance to the median is attained at an
    endpoint — so the selection is a running elementwise argmin over f+1
    window candidates, all on sorted rows from the shared network.

    Windows can TIE on the max endpoint distance (duplicate values — e.g.
    colluding Byzantine payloads), and the max alone cannot discriminate
    them; ties break toward the smaller in-window distance *sum*, which is
    what "the n-f smallest distances" (the jnp reference's argsort) uniquely
    minimizes.

    Tie contract: the selected window always matches the reference's
    selection *quality* exactly — same max distance and same distance sum,
    the quantities the robustness analysis depends on (gated by
    tests/test_agg_backends.py on tie-heavy integer stacks). When two values
    sit at exactly the same distance on opposite sides of the median, the
    reference breaks the tie by input position, which sorted tiles cannot
    observe — the kernel then averages the equidistant value from the
    leftmost (smaller-valued) best window instead; on continuous data such
    ties have probability zero."""
    rows = _sorted_rows(x_ref, n_pow2)
    med = 0.5 * (rows[(n - 1) // 2] + rows[n // 2])
    m = n - f
    dist = [jnp.abs(rows[j] - med) for j in range(n)]
    win_sum = rows[0]
    win_dsum = dist[0]
    for j in range(1, m):
        win_sum = win_sum + rows[j]
        win_dsum = win_dsum + dist[j]
    best_sum, best_dsum = win_sum, win_dsum
    best_d = jnp.maximum(med - rows[0], rows[m - 1] - med)
    for i in range(1, f + 1):
        win_sum = win_sum - rows[i - 1] + rows[i + m - 1]
        win_dsum = win_dsum - dist[i - 1] + dist[i + m - 1]
        d = jnp.maximum(med - rows[i], rows[i + m - 1] - med)
        take = (d < best_d) | ((d == best_d) & (win_dsum < best_dsum))
        best_sum = jnp.where(take, win_sum, best_sum)
        best_dsum = jnp.where(take, win_dsum, best_dsum)
        best_d = jnp.minimum(best_d, d)
    o_ref[0, :] = best_sum / m


def _rule_pallas_call(kernel, n_pow2: int, d_pad: int, block_d: int,
                      interpret: bool, **kw):
    return pl.pallas_call(
        partial(kernel, n_pow2=n_pow2, **kw),
        grid=(d_pad // block_d,),
        in_specs=[pl.BlockSpec((n_pow2, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
        interpret=interpret,
    )


def median_pallas_call(n: int, n_pow2: int, d_pad: int, block_d: int,
                       interpret: bool = False):
    return _rule_pallas_call(_median_kernel, n_pow2, d_pad, block_d,
                             interpret, n=n)


def trimmed_mean_pallas_call(n: int, f: int, n_pow2: int, d_pad: int,
                             block_d: int, interpret: bool = False):
    return _rule_pallas_call(_trimmed_mean_kernel, n_pow2, d_pad, block_d,
                             interpret, n=n, f=f)


def meamed_pallas_call(n: int, f: int, n_pow2: int, d_pad: int,
                       block_d: int, interpret: bool = False):
    return _rule_pallas_call(_meamed_kernel, n_pow2, d_pad, block_d,
                             interpret, n=n, f=f)


def _masked_median_kernel(masks_ref, x_ref, o_ref, *, n_send: int,
                          n_recv: int, sub_rows: int, sub_cols: int):
    """Every receiver's masked coordinate-wise median of one tile.

    ``masks_ref`` (SMEM, int32 [n_recv * n_send]) holds the delivery masks
    row by row; ``x_ref`` the senders' [n_send, br, bc] tile, read once;
    ``o_ref`` the receivers' [n_recv, br, bc] views. The arithmetic is
    ``agg.rules.masked_coordinate_median``'s: NaN and undelivered values
    become the finite sentinel, Batcher's network sorts the n_send rows,
    and the mean of ranks (q-1)//2 and q//2 (q the receiver's delivered
    count) is cast once to the output dtype. The tile is walked in
    [sub_rows, sub_cols] pieces, so the rows in flight stay in registers."""
    pairs = oddeven_pairs(n_send)
    big = jnp.float32(_BIG)
    delivered, lo, hi = [], [], []
    for r in range(n_recv):
        m = [masks_ref[r * n_send + s] for s in range(n_send)]
        q = sum(m[1:], m[0])
        delivered.append([v != 0 for v in m])
        lo.append((q - 1) >> 1)             # floor((q-1)/2), -1 for q = 0
        hi.append(q >> 1)
    n_rows, n_cols = x_ref.shape[1] // sub_rows, x_ref.shape[2] // sub_cols

    def start(k, size, n):      # static where the tile is one piece wide
        return 0 if n == 1 else pl.multiple_of(k * size, size)

    def piece(i, carry):
        at = (pl.ds(start(i // n_cols, sub_rows, n_rows), sub_rows),
              pl.ds(start(i % n_cols, sub_cols, n_cols), sub_cols))
        xs = []
        for s in range(n_send):
            v = x_ref[(s,) + at].astype(jnp.float32)
            xs.append(jnp.where(jnp.isnan(v), big, v))
        for r in range(n_recv):
            rows = _compare_exchange(
                [jnp.where(delivered[r][s], xs[s], big)
                 for s in range(n_send)], pairs)
            a = b = rows[0]
            for k in range(1, n_send):
                a = jnp.where(lo[r] == k, rows[k], a)
                b = jnp.where(hi[r] == k, rows[k], b)
            o_ref[(r,) + at] = (0.5 * (a + b)).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_rows * n_cols, piece, 0)


def masked_median_pallas_call(n_send: int, n_recv: int, rows: int,
                              cols: int, block: tuple[int, int],
                              sub: tuple[int, int], out_dtype,
                              in_place: bool = False,
                              interpret: bool = False):
    """[n_recv * n_send] int32 masks, [n_send, rows, cols] senders ->
    [n_recv, rows, cols] views in ``out_dtype``. ``block`` (br, bc) is a
    grid step's tile; ragged edge tiles are clipped by the pipeline;
    ``sub`` divides ``block``. ``in_place`` (senders and views of one shape
    and dtype) writes the views over the senders' buffer: a grid step reads
    its tile whole before it writes the tile's views, and tiles do not
    overlap. Where the senders are dead after the call, as the replicas
    after the DMC gather, no copy of them is made."""
    br, bc = block
    return pl.pallas_call(
        partial(_masked_median_kernel, n_send=n_send, n_recv=n_recv,
                sub_rows=sub[0], sub_cols=sub[1]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, br), pl.cdiv(cols, bc)),
            in_specs=[pl.BlockSpec((n_send, br, bc),
                                   lambda i, j, m: (0, i, j))],
            out_specs=pl.BlockSpec((n_recv, br, bc),
                                   lambda i, j, m: (0, i, j))),
        out_shape=jax.ShapeDtypeStruct((n_recv, rows, cols), out_dtype),
        input_output_aliases={1: 0} if in_place else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="masked_median",
    )
