"""Traffic: a pool of token rows, made on the device from the seed.

One general generator reads a traffic file's parameters. The generator
``zipf_rows`` is a copy of the program's ``DeviceTokenStream`` sampling
(``repro.data.pipeline.sample_token_batch``): every token is drawn from a
Zipf law over the vocabulary (``p(i) ~ (i + 1) ** -zipf``), labels are the
tokens shifted by one. Parameters:

- ``seq``: tokens per row; ``rows_per_group``: rows each of the G groups
  trains on per step;
- ``zipf``: the exponent;
- ``domains`` (default 1): the groups' data comes from this many domains,
  as it does where parties hold data of their own. Group g draws from
  domain ``g % domains``, whose ids are the Zipf ids shifted by
  ``d * (vocab // domains)`` (modulo the vocabulary): the same law over
  other tokens;
- ``pool_epochs``: how many distinct T-step epochs of rows the pool holds.
  The window cycles through them; the first is the one the check follows.

Every seed gives the same sizes; only the token ids differ.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def run_keys(seed: int) -> tuple:
    """(weights, protocol, rows) keys of a run: the weights' and the
    protocol's keys are the program's initial state, the rows' the pool."""
    k_weights, k_rows = jax.random.split(seed_key(seed))
    k_model, k_run = jax.random.split(k_weights)
    return k_model, k_run, k_rows


def _zipf_rows(key, *, vocab, seq, zipf, shape):
    logits = -zipf * jnp.log(jnp.arange(1, vocab + 1, dtype=jnp.float32))
    toks = jax.random.categorical(key, logits, shape=shape + (seq + 1,))
    return toks.astype(jnp.int32)


def make_pool(key, traffic: dict, *, vocab: int, T: int, groups: int,
              sharding=None) -> list[dict]:
    """``pool_epochs`` batches of ``{"tokens", "labels"}``, leaves
    ``[T, groups, rows_per_group, seq]``, from one jitted call."""
    if traffic["generator"] != "zipf_rows":
        raise ValueError(f"unknown traffic generator {traffic['generator']!r}")
    P = int(traffic["pool_epochs"])
    shape = (P, T, groups, int(traffic["rows_per_group"]))

    domains = int(traffic.get("domains", 1))
    shift = (jnp.arange(groups) % domains) * (vocab // domains)

    def gen(key):
        toks = _zipf_rows(key, vocab=vocab, seq=int(traffic["seq"]),
                          zipf=float(traffic["zipf"]), shape=shape)
        toks = (toks + shift[:, None, None]) % vocab
        return [{"tokens": toks[p, ..., :-1], "labels": toks[p, ..., 1:]}
                for p in range(P)]

    return jax.jit(gen, out_shardings=sharding)(key)
