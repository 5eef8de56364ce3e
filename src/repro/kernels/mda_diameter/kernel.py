"""Pallas TPU kernel: MDA subset-diameter scan.

Exact MDA evaluates, for every size-(n-f) subset of the n inputs, the max
pairwise distance inside the subset, then picks the argmin — C(n, f) masked
max-reductions over the [n, n] distance matrix (paper complexity O(C(n_w,f_w))).
The kernel tiles the static subset-mask table [S, n] over the grid and keeps
the distance matrix resident in VMEM. Every value stays 2-D: each grid step
loops over the n real rows i, reduces row i of the distance matrix under the
[block_s, n] pair mask (subsets holding i) on the VPU, and keeps a running
[block_s, 1] max. The result is stored lane-replicated, one full vreg row
per subset, so every block meets the (8, 128) tiling.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
_NEG = -3.4e38


def _diam_kernel(d2_ref, masks_ref, o_ref, *, n: int):
    m = masks_ref[...] > 0                     # [block_s, n_pad]
    acc = jnp.full((m.shape[0], 1), _NEG, jnp.float32)
    for i in range(n):                         # static: n real inputs
        row = d2_ref[i:i + 1, :]               # [1, n_pad]
        pair = m & m[:, i:i + 1]               # subsets holding i and j
        vals = jnp.where(pair, row, _NEG)      # [block_s, n_pad]
        acc = jnp.maximum(acc, jnp.max(vals, axis=-1, keepdims=True))
    o_ref[...] = jnp.broadcast_to(acc, o_ref.shape)


def diam_pallas_call(n: int, n_pad: int, s_pad: int, block_s: int,
                     interpret: bool = False):
    """[n_pad, n_pad] dists + [s_pad, n_pad] masks -> [s_pad, 128] (lane-
    replicated diameters)."""
    return pl.pallas_call(
        partial(_diam_kernel, n=n),
        grid=(s_pad // block_s,),
        in_specs=[
            pl.BlockSpec((n_pad, n_pad), lambda i: (0, 0)),
            pl.BlockSpec((block_s, n_pad), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s_pad, LANES), jnp.float32),
        interpret=interpret,
    )
