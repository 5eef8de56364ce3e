"""Gradient compression hooks for the exchange path (beyond-paper).

Composable with MDA because MDA's subset selection needs only pairwise
distances: distances computed on compressed gradients preserve the
honest/Byzantine separation as long as compression is *unbiased on honest
inputs* (random-k) or sign-consistent (signSGD — itself majority-vote
Byzantine-tolerant, Bernstein et al. 2018, cited by the paper as [9]).

Provided operators (pytree-aware, jit-able):
  * topk_compress     — keep the k largest-|.| coordinates per leaf
  * randk_compress    — keep a random k-subset (unbiased w/ 1/p rescale)
  * sign_compress     — sign(g) * mean|g| per leaf
Each returns a same-structure pytree (dense representation with zeros — the
wire format on a real deployment would be (indices, values); the dense form
keeps the protocol path unchanged; byte ratios are read from the exchange
dtype).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _leaf_topk(l, frac: float):
    n = l.size
    k = max(int(n * frac), 1)
    flat = l.reshape(-1)
    thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
    return jnp.where(jnp.abs(flat) >= thresh, flat, 0).reshape(l.shape)


def topk_compress(grads, frac: float = 0.01):
    return jax.tree.map(partial(_leaf_topk, frac=frac), grads)


def randk_compress(grads, key, frac: float = 0.01):
    leaves, treedef = jax.tree.flatten(grads)
    out = []
    for i, l in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        keep = jax.random.bernoulli(k, frac, l.shape)
        out.append(jnp.where(keep, l / frac, 0).astype(l.dtype))  # unbiased
    return jax.tree.unflatten(treedef, out)


def sign_compress(grads):
    return jax.tree.map(
        lambda l: (jnp.sign(l) * jnp.mean(jnp.abs(l))).astype(l.dtype), grads)


COMPRESSORS = {"none": None, "topk": topk_compress, "randk": randk_compress,
               "sign": sign_compress}
