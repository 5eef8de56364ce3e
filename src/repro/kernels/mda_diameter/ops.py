"""Jitted wrapper around the subset-diameter Pallas kernel.

This is the Pallas *backend* for exact MDA selection, reached through
``repro.agg`` dispatch (``backend="pallas"`` or auto on TPU); the full
MDA entry point lives in the registry (``repro.agg.get("mda")``), which
composes the Gram kernel, this diameter scan, and the selection logic of
``repro.agg.rules``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import diam_pallas_call

_LANE = 128
_SUBLANE = 8


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("block_s", "interpret"))
def subset_diameters(d2: jax.Array, masks: jax.Array, *, block_s: int = 512,
                     interpret: bool | None = None) -> jax.Array:
    """[n,n] dists + [S,n] bool masks -> [S] subset diameters."""
    if interpret is None:
        interpret = _default_interpret()
    s, n = masks.shape
    n_pad = -(-n // _LANE) * _LANE
    block_s = min(block_s, -(-s // _SUBLANE) * _SUBLANE)
    block_s = -(-block_s // _SUBLANE) * _SUBLANE
    s_pad = -(-s // block_s) * block_s
    d2p = jnp.zeros((n_pad, n_pad), jnp.float32).at[:n, :n].set(d2)
    mp = jnp.zeros((s_pad, n_pad), jnp.float32).at[:s, :n].set(
        masks.astype(jnp.float32))
    out = diam_pallas_call(n, n_pad, s_pad, block_s, interpret)(d2p, mp)
    return out[:s, 0]


def mda(x: jax.Array, f: int, *, interpret: bool | None = None) -> jax.Array:
    """Full MDA on the Pallas backend: [n,d] -> [d] (registry-routed)."""
    from ... import agg
    return agg.get("mda")(x, f, backend="pallas", interpret=interpret)
