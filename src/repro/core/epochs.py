"""Shared fused-epoch machinery — one scan/donation core, every engine.

Extracted from ``repro.core.engine`` (PR 3) so the single-host
:class:`~repro.core.engine.EpochEngine` and the distributed
:class:`~repro.core.protocol.ProtocolEngine` build on the same scaffolding
instead of duplicating it:

* **semantic compile cache** — epoch executables live in a bounded
  module-level cache keyed on the engine's *semantic* static configuration
  (config dataclass + callable ``cache_key``s + delivery model + metric
  flags), so parameter sweeps that rebuild engines per point reuse the
  compiled epoch instead of re-tracing (:func:`fn_cache_key`,
  :func:`delivery_cache_key`);
* **donated scan epochs** — subclasses provide ``_build()`` returning ONE
  jitted ``epoch(state, batches[L], *extras) -> (state, metrics_buf)``;
  :meth:`EpochRunner.run_epoch` invokes it with the carried state donated
  (and the donation-is-a-no-op-on-CPU warning suppressed per call);
* **chunked full runs with one host transfer** — :meth:`EpochRunner.run`
  drives any number of steps through compiled epochs from either a stacked
  batch pytree or a device stream, concatenating the on-device metric
  buffers with a single ``device_get`` at the end. Any ``epoch_steps`` chunk
  length is correct because the engines drive their gather boundary off the
  *carried* step counter, never the chunking.

The gather-boundary ``lax.cond`` logic itself stays with each engine (the
single-host engine distinguishes async/sync off-by-ones, the protocol always
gathers post-step), but both ride on this module's cache + run loop.
"""
from __future__ import annotations

import functools
import warnings
import weakref
from typing import Any, Callable

import jax
import numpy as np

from .quorum import UniformDelivery


def fn_cache_key(fn: Callable | None) -> tuple:
    """A hashable key identifying a callable's *semantics* for compile-cache
    reuse. ``functools.partial`` trees and callables exposing ``cache_key``
    (the repro.optim.schedules factories) key structurally — two sweep points
    built from the same factory with equal arguments share an executable.
    Anything else keys on object identity (always correct, never shared)."""
    if fn is None:
        return ("none",)
    ck = getattr(fn, "cache_key", None)
    if ck is not None:
        return ("ck", ck)
    if isinstance(fn, functools.partial):
        return ("partial", fn_cache_key(fn.func), fn.args,
                tuple(sorted(fn.keywords.items())))
    return ("fn", fn)


def delivery_cache_key(delivery) -> tuple:
    """UniformDelivery keys structurally; trace-backed models carry device
    arrays and key on identity."""
    if isinstance(delivery, UniformDelivery):
        return ("uniform", delivery.n_workers, delivery.n_servers,
                delivery.q_workers, delivery.q_servers)
    return (type(delivery).__name__, id(delivery))


# Semantic-key -> jitted epoch executable. Entries close over their engine's
# step functions (and, for TraceDelivery, staged trace arrays), so the cache
# is bounded: oldest entries are evicted past _EPOCH_CACHE_MAX to keep long
# sweeps over identity-keyed deliveries from pinning memory for the process
# lifetime. Single-host and protocol engines share the one cache (their keys
# are tagged differently).
_EPOCH_CACHE: dict[Any, Callable] = {}
_EPOCH_CACHE_MAX = 64

# Monotone count of cache MISSES (actual `_build` invocations = re-traces).
# The compiled-artifact auditor (repro.analyze, REPRO-HLO-RECOMPILE) sweeps
# semantically-identical and semantically-distinct engine configs against
# this sentinel to prove the cache key is complete end-to-end: identical
# configs must not increment it, distinct ones must.
_BUILD_COUNT = 0


def epoch_cache_size() -> int:
    return len(_EPOCH_CACHE)


def epoch_build_count() -> int:
    return _BUILD_COUNT


def clear_epoch_cache() -> None:
    _EPOCH_CACHE.clear()


# The runner that dispatched the process's last epoch, held weakly. A profiler
# trace names each device op by its HLO instruction only; a reader of the
# trace lowers this runner's program again (``lower().compile().as_text()``)
# for the op_name metadata, and so the ByzSGD stage, of each instruction.
_last_dispatched: weakref.ref | None = None


def last_dispatched() -> "EpochRunner | None":
    """The runner whose epoch was dispatched last, if it is still alive."""
    return _last_dispatched() if _last_dispatched is not None else None


def _arg_type(x):
    """An argument as jit sees it: shape, dtype, weak type and, when
    committed, placement."""
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, weak_type=x.aval.weak_type,
        sharding=x.sharding if x.committed else None)


class EpochRunner:
    """Scan/donation epoch scaffolding shared by the engines.

    Subclass contract:

    * ``_build() -> Callable`` — construct the jitted epoch function
      ``epoch(state, batches, *extras) -> (state, metrics_buf)`` with the
      state argument donated;
    * ``_cache_key() -> tuple`` — the semantic cache key (may contain
      unhashable parts; the base class falls back to a private
      instance-identity key);
    * ``_instance_key() -> tuple`` — the fallback identity key;
    * ``_extra_args() -> tuple`` — per-call epoch extras (e.g. eval sets);
    * ``default_epoch_steps -> int`` — the scan chunk when none is given.
    """

    def _build(self) -> Callable:
        raise NotImplementedError

    def _cache_key(self) -> tuple:
        raise NotImplementedError

    def _instance_key(self) -> tuple:
        return ("epoch-inst", id(self))

    def _extra_args(self) -> tuple:
        return ()

    @property
    def default_epoch_steps(self) -> int:
        return self.cfg.T

    def _get_or_build(self) -> Callable:
        try:
            key = self._cache_key()
            hash(key)
        except TypeError:  # unhashable closure args: private executable
            key = self._instance_key()
        fn = _EPOCH_CACHE.get(key)
        if fn is None:
            global _BUILD_COUNT
            _BUILD_COUNT += 1
            fn = self._build()
            while len(_EPOCH_CACHE) >= _EPOCH_CACHE_MAX:
                _EPOCH_CACHE.pop(next(iter(_EPOCH_CACHE)))
            _EPOCH_CACHE[key] = fn
        return fn

    # -- epoch-at-a-time API -------------------------------------------------
    def run_epoch(self, state, batches):
        """One compiled epoch over ``batches`` (leaves ``[L, n_w, ...]``).
        ``state`` is donated. Metrics stay on device (dict of ``[L]`` bufs).

        The dispatch is the profiler span ``repro/run_epoch``: on a compile
        cache miss it holds the epoch's trace, lowering and compile."""
        global _last_dispatched
        with warnings.catch_warnings(), \
                jax.profiler.TraceAnnotation("repro/run_epoch"):
            # donation is a no-op on CPU; keep that per-executable warning out
            # of benchmark output without touching the global filter state
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            args = (state, batches, *self._extra_args())
            self._dispatched = (jax.sharding.get_mesh(),
                                jax.tree.map(_arg_type, args))
            _last_dispatched = weakref.ref(self)
            return self._epoch(*args)

    def lower(self):
        """The program of the last dispatched epoch, lowered again from the
        argument types and ambient mesh it ran with: ``.compile()`` it for
        its text or memory analysis (a persistent compile cache serves that
        compile from the run's own entry)."""
        mesh, args = self._dispatched
        with jax.set_mesh(mesh):
            return self._epoch.lower(*args)

    # -- full-run API --------------------------------------------------------
    def run(self, state, batches=None, *, stream=None,
            steps: int | None = None, epoch_steps: int | None = None
            ) -> tuple[Any, dict[str, np.ndarray]]:
        """Run ``steps`` protocol steps in compiled epochs.

        Feed either ``batches`` — a pytree with ``[steps, n_w, ...]`` leaves —
        or ``stream`` — an object with ``next(L)`` returning device batches
        (see ``DeviceBatchStream``). ``epoch_steps`` sets the scan length per
        dispatch (default: ``cfg.T``); any value is correct because the gather
        boundary is driven by the carried step counter, not the chunking.
        Returns the final state and the host metrics buffers (one transfer).
        """
        if (batches is None) == (stream is None):
            raise ValueError("provide exactly one of batches/stream")
        if steps is None:
            if batches is None:
                raise ValueError("steps is required with stream input")
            steps = jax.tree.leaves(batches)[0].shape[0]
        L = epoch_steps or self.default_epoch_steps
        bufs, done = [], 0
        while done < steps:
            n = min(L, steps - done)
            if batches is not None:
                chunk = jax.tree.map(lambda l: l[done:done + n], batches)
            else:
                chunk = stream.next(n)
            state, mbuf = self.run_epoch(state, chunk)
            bufs.append(mbuf)
            done += n
        if not bufs or not bufs[0]:
            return state, {}
        host = jax.device_get(bufs)  # ONE device->host transfer
        metrics = {k: np.concatenate([np.asarray(b[k]) for b in host])
                   for k in host[0]}
        return state, metrics


def stack_batches(batch_iter) -> Any:
    """Stack a host batch iterable into the ``[steps, ...]`` pytree the
    engines consume (for driving an engine from a legacy host stream in
    tests)."""
    import jax.numpy as jnp
    batches = list(batch_iter)
    return jax.tree.map(lambda *ls: jnp.stack(ls), *batches)
