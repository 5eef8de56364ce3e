"""Jitted wrappers around the coordinate-wise order-statistic Pallas kernels.

The Pallas backends of the ``median``, ``trimmed_mean`` and ``meamed``
aggregators (one shared bitonic sorting network, three reductions); call
sites reach them through ``repro.agg`` dispatch (``backend="pallas"`` or
auto on TPU), which falls back to the jnp reference for stacks larger than
the kernels' n <= 64 limit. ``masked_median_views`` is the ``median`` rule's
masked form over a whole leaf: every receiver's view in one pass.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .kernel import (_BIG, masked_median_pallas_call, meamed_pallas_call,
                     median_pallas_call, trimmed_mean_pallas_call)

_LANE = 128
# the masked Median's tiles: a leaf's minor dim up to this many lanes stays
# whole in a tile, and a grid step's input and output tiles, double-buffered,
# take at most this many bytes of VMEM
_MAX_LANES = 4096
_TILE_BYTES = 8 * 2**20


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tile(x: jax.Array, block_d: int):
    """Pad the stack to (next-pow2 rows of ``_BIG``, lane-aligned d) for
    the sorting-network kernels; pads sort last. NaN payloads are mapped
    to ``_BIG`` too — NaN poisons the jnp.minimum/maximum
    compare-exchanges (every comparison involving it is False, so it
    drifts arbitrarily instead of sorting last), and a Byzantine replica
    sending NaN would otherwise corrupt the whole coordinate. Mirrors
    ``agg.rules.sort_stack``."""
    n, d = x.shape
    if n > 64:
        raise ValueError("cwise order-statistic kernels are sized for "
                         "replica stacks n <= 64")
    n_pow2 = 1
    while n_pow2 < n:
        n_pow2 *= 2
    block_d = min(block_d, -(-d // _LANE) * _LANE)
    block_d = -(-block_d // _LANE) * _LANE
    d_pad = -(-d // block_d) * block_d
    xf = x.astype(jnp.float32)
    xf = jnp.where(jnp.isnan(xf), jnp.float32(_BIG), xf)
    xp = jnp.full((n_pow2, d_pad), jnp.float32(_BIG), jnp.float32)
    xp = xp.at[:n, :d].set(xf)
    return xp, n_pow2, d_pad, block_d


@partial(jax.jit, static_argnames=("block_d", "interpret"))
def cwise_median(x: jax.Array, *, block_d: int = 1024,
                 interpret: bool | None = None) -> jax.Array:
    """[n, d] -> [d] f32 coordinate-wise median (n <= 64)."""
    if interpret is None:
        interpret = _default_interpret()
    n, d = x.shape
    xp, n_pow2, d_pad, block_d = _tile(x, block_d)
    out = median_pallas_call(n, n_pow2, d_pad, block_d, interpret)(xp)
    return out[0, :d]


@partial(jax.jit, static_argnames=("f", "block_d", "interpret"))
def cwise_trimmed_mean(x: jax.Array, f: int, *, block_d: int = 1024,
                       interpret: bool | None = None) -> jax.Array:
    """[n, d] -> [d] f32 trimmed mean (drop f lowest/highest; n <= 64)."""
    if interpret is None:
        interpret = _default_interpret()
    n, d = x.shape
    if n <= 2 * f:
        raise ValueError(f"trimmed_mean needs n > 2f (n={n}, f={f})")
    xp, n_pow2, d_pad, block_d = _tile(x, block_d)
    out = trimmed_mean_pallas_call(n, f, n_pow2, d_pad, block_d,
                                   interpret)(xp)
    return out[0, :d]


@partial(jax.jit, static_argnames=("f", "block_d", "interpret"))
def cwise_meamed(x: jax.Array, f: int, *, block_d: int = 1024,
                 interpret: bool | None = None) -> jax.Array:
    """[n, d] -> [d] f32 mean-around-median (n <= 64)."""
    if interpret is None:
        interpret = _default_interpret()
    n, d = x.shape
    if n <= f:
        raise ValueError(f"meamed needs n > f (n={n}, f={f})")
    xp, n_pow2, d_pad, block_d = _tile(x, block_d)
    out = meamed_pallas_call(n, f, n_pow2, d_pad, block_d, interpret)(xp)
    return out[0, :d]


def _masked_tiles(rows: int, cols: int, n_send: int, n_recv: int,
                  in_bytes: int, out_bytes: int, tile_bytes: int):
    """(block, sub) of the masked Median over a [rows, cols] view: the
    grid step's (br, bc) tile and the [sub_rows, sub_cols] piece the kernel
    computes at a time. A minor dim up to ``_MAX_LANES`` stays whole, a
    wider one is cut at a lane-aligned divisor (or at ``_MAX_LANES``, with
    a ragged edge tile); rows fill the VMEM budget in multiples of 16 (a
    bfloat16 tile's sublanes), or are taken whole."""
    if cols <= _MAX_LANES:
        bc = cols
    elif cols % _LANE == 0:
        bc = max(c for c in range(_LANE, _MAX_LANES + 1, _LANE)
                 if cols % c == 0)
    else:
        bc = _MAX_LANES
    per_row = 2 * bc * (n_send * in_bytes + n_recv * out_bytes)
    br = max(tile_bytes // per_row // 16 * 16, 16)
    if rows <= br:
        br = rows
    sub_rows = next((r for r in (16, 8) if br % r == 0), br)
    sub_cols = next((c for c in (512, 256, _LANE) if bc % c == 0), bc)
    return (br, bc), (sub_rows, sub_cols)


@partial(jax.jit, static_argnames=("out_dtype", "interpret", "tile_bytes"))
def masked_median_views(x: jax.Array, masks: jax.Array, out_dtype, *,
                        interpret: bool | None = None,
                        tile_bytes: int = _TILE_BYTES) -> jax.Array:
    """[G_send, ...] float stack, [G_recv, G_send] bool delivery masks ->
    [G_recv, ...] in ``out_dtype``: each receiver's coordinate-wise median
    of the senders it got (``agg.rules.masked_coordinate_median``, then the
    cast). The leaf is viewed as [G_send, R, C], its minor dim kept and the
    leading body dims merged, so the view needs no relayout."""
    if interpret is None:
        interpret = _default_interpret()
    n_send, body = x.shape[0], x.shape[1:]
    n_recv = masks.shape[0]
    rows, cols = math.prod(body[:-1]), (body[-1] if body else 1)
    out_dtype = jnp.dtype(out_dtype)
    block, sub = _masked_tiles(rows, cols, n_send, n_recv, x.dtype.itemsize,
                               out_dtype.itemsize, tile_bytes)
    in_place = n_recv == n_send and x.dtype == out_dtype
    out = masked_median_pallas_call(n_send, n_recv, rows, cols, block, sub,
                                    out_dtype, in_place, interpret)(
        masks.astype(jnp.int32).reshape(-1), x.reshape(n_send, rows, cols))
    return out.reshape((n_recv,) + body)
