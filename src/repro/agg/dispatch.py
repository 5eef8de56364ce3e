"""Backend dispatch: route each aggregation primitive to its pure-jnp
reference or its Pallas kernel.

Three primitives have Pallas implementations under ``repro.kernels``:

  * ``pairwise_sqdist``  — Gram-matrix kernel, feeds every distance-based rule
  * ``mda_diameter``     — subset-diameter scan for exact MDA selection
  * ``cwise_median``     — per-coordinate median over a replica stack (n <= 64)

``backend`` is one of:

  * ``"auto"`` (default) — Pallas on TPU, jnp elsewhere (the kernels run in
    interpret mode off-TPU, which is correct but slow — useful for tests, not
    for the hot path);
  * ``"jnp"`` — always the reference implementation;
  * ``"pallas"`` — always the kernel (interpret mode is auto-enabled off-TPU,
    or forced with ``interpret=True``).

The ``REPRO_AGG_BACKEND`` environment variable overrides the default for a
whole process. Numerical equivalence of both backends is enforced by
``tests/test_agg_backends.py``.
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp

from . import rules

_VALID = ("auto", "jnp", "pallas")

# cwise_median kernel is sized for replica stacks (sorting network in regs)
_MEDIAN_KERNEL_MAX_N = 64

# primitive -> the backends it resolved to, recorded at trace time
_RESOLVED: dict[str, set[str]] = {}


def default_backend() -> str:
    return os.environ.get("REPRO_AGG_BACKEND", "auto")


@contextmanager
def backend_override(backend: str | None):
    """Exception-safe process-default backend override.

    Sets ``REPRO_AGG_BACKEND`` for the dynamic extent of the block and
    restores the previous value (or absence) on ANY exit path. This is the
    sanctioned way to scope the default — bare ``os.environ[...] =``
    mutations leak state across runs when the block raises, and are linted
    against (REPRO-ENV-MUTATE). ``backend=None`` is a no-op, so callers can
    pass an optional spec field straight through.
    """
    if backend is None:
        yield
        return
    if backend not in _VALID:
        raise ValueError(f"unknown backend {backend!r}; choose from {_VALID}")
    prev = os.environ.get("REPRO_AGG_BACKEND")
    os.environ["REPRO_AGG_BACKEND"] = backend
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_AGG_BACKEND", None)
        else:
            os.environ["REPRO_AGG_BACKEND"] = prev


def resolve_backend(backend: str | None = None, *,
                    pallas_ok: bool = True) -> str:
    """Concrete backend for this call. ``pallas_ok=False`` marks shapes the
    kernel cannot take (auto falls back to jnp; explicit 'pallas' raises)."""
    b = backend or default_backend()
    if b not in _VALID:
        raise ValueError(f"unknown backend {b!r}; choose from {_VALID}")
    if b == "auto":
        return "pallas" if (pallas_ok and jax.default_backend() == "tpu") \
            else "jnp"
    if b == "pallas" and not pallas_ok:
        raise ValueError("shape not supported by the Pallas kernel "
                         f"(cwise_median needs a [n <= {_MEDIAN_KERNEL_MAX_N},"
                         " d] stack)")
    return b


def resolved_backends(reset: bool = False) -> dict[str, list[str]]:
    """Primitive -> the backends it resolved to in the programs traced since
    the last reset. Resolution happens at trace time, so a program served
    from a compile cache adds nothing."""
    out = {k: sorted(v) for k, v in sorted(_RESOLVED.items())}
    if reset:
        _RESOLVED.clear()
    return out


def _resolve(primitive: str, backend: str | None, *,
             pallas_ok: bool = True) -> str:
    b = resolve_backend(backend, pallas_ok=pallas_ok)
    _RESOLVED.setdefault(primitive, set()).add(b)
    return b


def pairwise_sqdists(x: jax.Array, *, backend: str | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """[n, d] -> [n, n] exact squared L2 distances."""
    if _resolve("pairwise_sqdist", backend) == "pallas":
        from ..kernels.pairwise_sqdist import ops
        return ops.pairwise_sqdists(x, interpret=interpret)
    return rules.pairwise_sqdists(x)


def subset_diameters(d2: jax.Array, masks: jax.Array, *,
                     backend: str | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """[n,n] distances + [S,n] subset masks -> [S] subset diameters."""
    if _resolve("mda_diameter", backend) == "pallas":
        from ..kernels.mda_diameter import ops
        return ops.subset_diameters(d2, masks.astype(bool),
                                    interpret=interpret)
    return rules.subset_diameters(d2, masks.astype(bool))


def cwise_median(x: jax.Array, *, backend: str | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """[n, ...] -> [...] coordinate-wise median (kernel path needs a 2D
    stack; multi-dim leaves — e.g. pytree weight matrices — fall back)."""
    ok = x.ndim == 2 and x.shape[0] <= _MEDIAN_KERNEL_MAX_N
    if _resolve("cwise_median", backend, pallas_ok=ok) == "pallas":
        from ..kernels.cwise_median import ops
        return ops.cwise_median(x, interpret=interpret)
    return rules.coordinate_median(x)


# ---------------------------------------------------------------------------
# dispatch-level rule entry points (referenced by the registry specs)
# ---------------------------------------------------------------------------


def median(x: jax.Array, *, backend: str | None = None,
           interpret: bool | None = None) -> jax.Array:
    """Coordinate-wise median through the backend dispatch."""
    return cwise_median(x, backend=backend, interpret=interpret).astype(x.dtype)


def _cwise_rule(x: jax.Array, f: int, kernel_name: str, ref_fn,
                backend: str | None, interpret: bool | None) -> jax.Array:
    """Shared dispatch for the f-taking coordinate-wise order-statistic
    rules: the Pallas path shares cwise_median's sorting network; multi-dim
    leaves and stacks beyond the kernel's n limit fall back to the jnp
    reference."""
    ok = x.ndim == 2 and x.shape[0] <= _MEDIAN_KERNEL_MAX_N
    if _resolve(kernel_name, backend, pallas_ok=ok) == "pallas":
        from ..kernels.cwise_median import ops
        out = getattr(ops, kernel_name)(x, f, interpret=interpret)
        return out.astype(x.dtype)
    return ref_fn(x, f)


def trimmed_mean(x: jax.Array, f: int, *, backend: str | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """Coordinate-wise trimmed mean through the backend dispatch."""
    return _cwise_rule(x, f, "cwise_trimmed_mean", rules.trimmed_mean,
                       backend, interpret)


def meamed(x: jax.Array, f: int, *, backend: str | None = None,
           interpret: bool | None = None) -> jax.Array:
    """Mean-around-Median through the backend dispatch.

    Backend equivalence is exact except when two values are *exactly*
    equidistant from the median on opposite sides (probability zero on
    continuous data): both backends then select sets with identical distance
    profiles (same max, same sum — see the kernel's tie contract) but may
    average a different member of the tied pair."""
    return _cwise_rule(x, f, "cwise_meamed", rules.meamed, backend, interpret)


def mda(x: jax.Array, f: int, *, exact_limit: int = 200_000,
        backend: str | None = None,
        interpret: bool | None = None) -> jax.Array:
    """Minimum-Diameter Averaging through the backend dispatch: the Gram /
    distance step and (when exact) the subset-diameter scan both route to
    their kernels; selection logic stays in :mod:`repro.agg.rules`."""
    n = x.shape[0]
    if n < 2 * f + 1:
        raise ValueError(f"MDA needs n >= 2f+1 (n={n}, f={f})")
    if f == 0:
        return jnp.mean(x, axis=0)
    d2 = pairwise_sqdists(x, backend=backend, interpret=interpret)
    diam_fn = partial(subset_diameters, backend=backend, interpret=interpret)
    sel = rules.mda_selection(d2, f, exact_limit=exact_limit,
                              diameters_fn=diam_fn)
    w = sel.astype(jnp.float32) / (n - f)
    return (w @ x.astype(jnp.float32)).astype(x.dtype)


def krum(x: jax.Array, f: int, *, backend: str | None = None,
         interpret: bool | None = None) -> jax.Array:
    """Krum with the distance step routed through the backend dispatch."""
    d2 = pairwise_sqdists(x, backend=backend, interpret=interpret)
    w = rules.krum_weights_from_d2(d2, f)
    return (w @ x.astype(jnp.float32)).astype(x.dtype)


def multi_krum(x: jax.Array, f: int, *, m: int | None = None,
               backend: str | None = None,
               interpret: bool | None = None) -> jax.Array:
    """Multi-Krum with the distance step routed through the backend dispatch."""
    d2 = pairwise_sqdists(x, backend=backend, interpret=interpret)
    w = rules.multi_krum_weights_from_d2(d2, f, m=m)
    return (w @ x.astype(jnp.float32)).astype(x.dtype)
