"""Deterministic synthetic data pipelines.

The paper trains MNIST/CIFAR-10 on a CPU cluster; neither dataset is vendored
offline here, so the reproduction experiments use a synthetic Gaussian-mixture
classification task with controllable difficulty (documented deviation —
EXPERIMENTS.md §Repro). Properties preserved:

* i.i.d. across workers (paper Assumption, §2.5) — every worker samples from the
  same distribution with decorrelated seeds.
* mini-batch SGD noise scales as 1/sqrt(b) — the variance-to-norm experiments
  (Appendix D) depend on this and reproduce cleanly.

For the LM architectures, ``token_stream`` yields deterministic pseudo-random
token batches for smoke tests and the end-to-end examples.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclass(frozen=True)
class MixtureSpec:
    n_classes: int = 10
    dim: int = 64
    sep: float = 2.5      # class-centre separation (controls task difficulty)
    noise: float = 1.0


def make_mixture(spec: MixtureSpec, key: jax.Array):
    """Class centres for a Gaussian mixture classification task."""
    centres = spec.sep * jax.random.normal(key, (spec.n_classes, spec.dim))
    return centres


@partial(jax.jit, static_argnames=("spec", "n_workers", "batch_per_worker"))
def sample_classification_batch(key: jax.Array, centres: jax.Array,
                                spec: MixtureSpec, n_workers: int,
                                batch_per_worker: int):
    """Returns (x [n_w, b, dim], y [n_w, b]) — i.i.d. across workers."""
    ky, kx = jax.random.split(key)
    shape = (n_workers, batch_per_worker)
    y = jax.random.randint(ky, shape, 0, spec.n_classes)
    noise = spec.noise * jax.random.normal(kx, shape + (spec.dim,))
    x = centres[y] + noise
    return x, y


def classification_stream(seed: int, spec: MixtureSpec, n_workers: int,
                          batch_per_worker: int, steps: int):
    """Generator of per-worker-sharded batches + a held-out eval set maker."""
    key = jax.random.PRNGKey(seed)
    kc, key = jax.random.split(key)
    centres = make_mixture(spec, kc)
    def gen():
        k = key
        for _ in range(steps):
            k, kb = jax.random.split(k)
            yield sample_classification_batch(kb, centres, spec, n_workers,
                                              batch_per_worker)
    def eval_set(n: int = 2048, eval_seed: int = 10_007):
        x, y = sample_classification_batch(jax.random.PRNGKey(eval_seed),
                                           centres, spec, 1, n)
        return x[0], y[0]
    return gen(), eval_set


@partial(jax.jit, static_argnames=("spec", "n_workers", "batch_per_worker",
                                   "length"))
def sample_classification_epoch(key: jax.Array, centres: jax.Array,
                                spec: MixtureSpec, n_workers: int,
                                batch_per_worker: int, length: int):
    """``length`` stacked batches from one device-side PRNG call.

    Walks the same key chain as :func:`classification_stream` (one split per
    step), so the produced ``(x [L, n_w, b, dim], y [L, n_w, b])`` tensor is
    bit-identical to ``length`` host-iterator batches. Returns
    ``(next_key, (x, y))``.
    """
    def split_one(k, _):
        k, kb = jax.random.split(k)
        return k, kb

    key, kbs = lax.scan(split_one, key, None, length=length)
    x, y = jax.vmap(lambda kb: sample_classification_batch(
        kb, centres, spec, n_workers, batch_per_worker))(kbs)
    return key, (x, y)


@partial(jax.jit, static_argnames=("length",))
def _advance_key(key: jax.Array, length: int) -> jax.Array:
    """The carried key after ``length`` stream steps (one split per step —
    the same walk as :func:`sample_classification_epoch`, batches discarded)."""
    def split_one(k, _):
        return jax.random.split(k)[0], None

    key, _ = lax.scan(split_one, key, None, length=length)
    return key


class DeviceBatchStream:
    """Device-resident data stream for the fused epoch engine.

    Unlike :func:`classification_stream` (a host generator dispatching one
    sampling kernel per step), ``next(L)`` produces the whole epoch's batches
    as one ``[L, n_w, b, ...]`` device tensor from a single jitted call, so
    the training hot path stays trace-closed with no host iterator in the
    loop. Same seed => the concatenation of successive ``next`` calls equals
    the host stream's batch sequence exactly.
    """

    def __init__(self, seed: int, spec: MixtureSpec, n_workers: int,
                 batch_per_worker: int):
        key = jax.random.PRNGKey(seed)
        kc, key = jax.random.split(key)
        self.spec = spec
        self.n_workers = n_workers
        self.batch_per_worker = batch_per_worker
        self.centres = make_mixture(spec, kc)
        self._key = key

    def next(self, length: int, n_workers: int | None = None):
        """Next ``length`` batches: ``(x [L, n_w, b, dim], y [L, n_w, b])``.

        ``n_workers`` overrides the stream width for this call (the elastic
        runner draws narrower batches while the fleet is shrunk). The carried
        key chain advances one split per *step* regardless of width, so a
        width change never desynchronizes the stream from a full-width run —
        the basis of the elastic runner's resume/bit-identity guarantees."""
        nw = self.n_workers if n_workers is None else n_workers
        self._key, batches = sample_classification_epoch(
            self._key, self.centres, self.spec, nw,
            self.batch_per_worker, length)
        return batches

    def skip(self, length: int):
        """Advance the key chain ``length`` steps without sampling — exactly
        the splits ``next`` would have consumed (checkpointed-resume
        fast-forward)."""
        if length:
            self._key = _advance_key(self._key, length)

    def place(self, sharding):
        """Commit the carried key to ``sharding``. A draw under one ambient
        mesh commits the key to that mesh's devices; a stream that outlives
        its mesh (the elastic runner re-forms the fleet) is moved onto the
        next one before drawing under it."""
        self._key = jax.device_put(self._key, sharding)

    def eval_set(self, n: int = 2048, eval_seed: int = 10_007):
        """Held-out eval set, identical to ``classification_stream``'s."""
        x, y = sample_classification_batch(jax.random.PRNGKey(eval_seed),
                                           self.centres, self.spec, 1, n)
        return x[0], y[0]


@dataclass(frozen=True)
class TokenSpec:
    """Synthetic LM data spec (the token analogue of :class:`MixtureSpec`).

    Zipf-distributed tokens (``zipf > 0``) keep the unigram statistics
    learnable — uniform tokens pin the cross-entropy at ``ln vocab`` and no
    training signal exists; ``zipf = 0`` gives uniform tokens."""
    vocab: int = 512
    seq: int = 64
    zipf: float = 1.2


def _token_logits(spec: TokenSpec):
    return -spec.zipf * jnp.log(jnp.arange(1, spec.vocab + 1,
                                           dtype=jnp.float32))


@partial(jax.jit, static_argnames=("spec", "n_workers", "batch_per_worker"))
def sample_token_batch(key: jax.Array, spec: TokenSpec, n_workers: int,
                       batch_per_worker: int):
    """One next-token batch: dict(tokens, labels), leaves [n_w, b, seq]."""
    shape = (n_workers, batch_per_worker, spec.seq + 1)
    if spec.zipf > 0:
        toks = jax.random.categorical(key, _token_logits(spec),
                                      shape=shape).astype(jnp.int32)
    else:
        toks = jax.random.randint(key, shape, 0, spec.vocab)
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}


@partial(jax.jit, static_argnames=("spec", "n_workers", "batch_per_worker",
                                   "length"))
def sample_token_epoch(key: jax.Array, spec: TokenSpec, n_workers: int,
                       batch_per_worker: int, length: int):
    """``length`` stacked token batches from one device-side call. Walks the
    same key chain as :func:`token_stream` (one split per step, identical
    sampling), so the concatenation of successive calls is bit-identical to
    the host generator's batch sequence. Returns ``(next_key, batches)`` with
    leaves ``[L, n_w, b, seq]``."""
    def split_one(k, _):
        k, kb = jax.random.split(k)
        return k, kb

    key, kbs = lax.scan(split_one, key, None, length=length)
    batches = jax.vmap(lambda kb: sample_token_batch(
        kb, spec, n_workers, batch_per_worker))(kbs)
    return key, batches


class DeviceTokenStream:
    """Device-resident LM data stream with the :class:`DeviceBatchStream`
    interface (``next``/``skip``/``eval_set``), so the fused protocol engine
    drives token models exactly like the mixture task. Same seed => the
    concatenation of ``next`` calls equals :func:`token_stream`'s sequence."""

    def __init__(self, seed: int, spec: TokenSpec, n_workers: int,
                 batch_per_worker: int):
        self.spec = spec
        self.n_workers = n_workers
        self.batch_per_worker = batch_per_worker
        self._key = jax.random.PRNGKey(seed)

    def next(self, length: int, n_workers: int | None = None):
        nw = self.n_workers if n_workers is None else n_workers
        self._key, batches = sample_token_epoch(
            self._key, self.spec, nw, self.batch_per_worker, length)
        return batches

    def skip(self, length: int):
        if length:
            self._key = _advance_key(self._key, length)

    def eval_set(self, n: int = 256, eval_seed: int = 10_007):
        """Held-out eval batch: ``(tokens [n, seq], labels [n, seq])``."""
        b = sample_token_batch(jax.random.PRNGKey(eval_seed), self.spec, 1, n)
        return b["tokens"][0], b["labels"][0]


def token_stream(seed: int, vocab: int, n_workers: int, batch_per_worker: int,
                 seq_len: int, steps: int, zipf: float = 1.2):
    """Deterministic LM token batches: dict(tokens, labels) with leaves
    [n_w, b, L]. Labels are next-token shifted. Tokens are Zipf-distributed
    (zipf > 0) so the unigram statistics are learnable (uniform tokens pin the
    loss at ln V); zipf=0 gives uniform."""
    key = jax.random.PRNGKey(seed)
    if zipf > 0:
        logits = -zipf * jnp.log(jnp.arange(1, vocab + 1, dtype=jnp.float32))
    for _ in range(steps):
        key, kb = jax.random.split(key)
        shape = (n_workers, batch_per_worker, seq_len + 1)
        if zipf > 0:
            toks = jax.random.categorical(kb, logits, shape=shape).astype(jnp.int32)
        else:
            toks = jax.random.randint(kb, shape, 0, vocab)
        yield {"tokens": toks[..., :-1], "labels": toks[..., 1:]}


def host_token_batch(seed: int, vocab: int, batch: int, seq_len: int):
    """Single unsharded batch (numpy) for smoke tests."""
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, (batch, seq_len + 1), dtype=np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]), "labels": jnp.asarray(toks[:, 1:])}
