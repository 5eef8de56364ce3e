"""The :class:`Aggregator` spec and the rule registry.

Every aggregation rule is described by one :class:`Aggregator`: its reference
callable with its *declared arity* (rules that ignore ``f`` simply don't take
it — no uniform-signature stubs), its breakdown point ``n >= k*f + c``, its
variance-to-norm safety threshold, and capability flags that replace every
call-site special case in the codebase:

  * ``needs_pairwise_d2`` / ``selection_based`` — the rule factors into a
    pairwise-distance computation plus a weights-on-inputs selection
    (``weights_from_d2``), which is what the sharded protocol and the pytree
    path exploit (leaf-partial Grams instead of flattening).
  * ``supports_masked_delivery`` — a traced-compatible masked implementation
    exists, so delivery masks built *inside jit* (quorum sampling, netsim
    traces) compose with the rule. Concrete (non-traced) masks work for every
    rule via subset gathering.
  * ``tree_mode`` — how the rule extends to pytrees: ``"leafwise"`` for
    coordinate-wise rules, ``"selection"`` for weights-based rules, ``None``
    for rules without a sound pytree decomposition (Bulyan).
  * ``masked_views`` — a dispatch-level entry that computes every
    receiver's masked aggregate of a whole leaf in one call, in the
    receivers' dtype (``(x, masks, out_dtype, *, fallback=None, backend=,
    interpret=)``), which the protocol's pull and gather take where the
    backend resolves to its kernel.

Lookup is by name (:func:`get`); ``f`` bounds are validated uniformly at call
time from the spec's mechanical requirement with a uniform error message.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import dispatch, rules


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


@dataclass(frozen=True)
class Aggregator:
    """Spec + entry point for one aggregation rule.

    Calling the spec aggregates a flat stack: ``spec(x, f, mask=..., ...)``.
    """
    name: str
    fn: Callable                     # reference callable, natural arity
    takes_f: bool                    # whether ``fn`` takes the declared f
    breakdown: str                   # human-readable resilience bound
    requires: tuple[int, int]        # enforced bound: n >= k*f + c (the
                                     # breakdown point for resilient rules)
    doc: str = ""
    variance_threshold: Callable[[int, int], float] | None = None
    needs_pairwise_d2: bool = False
    selection_based: bool = False
    tree_mode: str | None = "leafwise"      # 'leafwise' | 'selection' | None
    backends: tuple[str, ...] = ("jnp",)
    masked_fn: Callable | None = None       # traced-ok: (x, [f,] mask) -> [d]
    masked_views: Callable | None = None    # (x, masks [r, n], out_dtype)
                                            # -> [r, ...], one call
    weights_from_d2: Callable | None = None  # (d2, f, *, mask=None, **kw)->[n]
    tunables: frozenset[str] = frozenset()  # extra kwargs the rule accepts

    @property
    def supports_masked_delivery(self) -> bool:
        return self.masked_fn is not None or (
            self.selection_based and self.weights_from_d2 is not None)

    @property
    def is_sanitizer(self) -> bool:
        """Whether the rule launders Byzantine influence: a nonzero
        breakdown point (``n >= k*f + c`` with ``k >= 2``). ``mean`` is
        not one. ``repro.analyze``'s REPRO-TAINT-BYZ derives its
        sanitizer set from exactly this predicate (over the AST)."""
        return self.requires[0] >= 2

    def validate(self, n: int, f: int) -> None:
        """Uniform f-bounds check from the spec's mechanical requirement."""
        k, c = self.requires
        if f < 0:
            raise ValueError(f"aggregator {self.name!r}: f must be >= 0, got {f}")
        if f >= n:
            raise ValueError(
                f"aggregator {self.name!r}: need f < n, got n={n}, f={f}")
        if n < k * f + c:
            need = (f"{k}f+{c}" if k else f"{c}").replace("1f", "f")
            raise ValueError(
                f"aggregator {self.name!r} requires n >= {need} "
                f"(breakdown point {self.breakdown}): got n={n}, f={f}")

    def filter_kwargs(self, **kw) -> dict[str, Any]:
        """Keep only the kwargs this rule accepts (lets generic call sites pass
        rule-specific knobs like ``exact_limit`` without special-casing)."""
        return {k: v for k, v in kw.items() if k in self.tunables}

    def _call_unmasked(self, x, f, backend, interpret, **kw):
        kw = self.filter_kwargs(**kw)
        if "pallas" in self.backends:   # fn is a dispatch-level callable
            kw.update(backend=backend, interpret=interpret)
        return self.fn(x, f, **kw) if self.takes_f else self.fn(x, **kw)

    def __call__(self, x: jax.Array, f: int = 0, *,
                 mask: jax.Array | None = None, backend: str | None = None,
                 interpret: bool | None = None, **kw) -> jax.Array:
        n = x.shape[0]
        self.validate(n, f)
        if mask is None:
            return self._call_unmasked(x, f, backend, interpret, **kw)
        if not (_is_traced(mask) or _is_traced(x)):
            # concrete mask: exact subset semantics for EVERY rule
            m = np.asarray(mask, bool)
            if m.shape != (n,):
                raise ValueError(f"mask must be [n={n}] bool, got {m.shape}")
            self.validate(int(m.sum()), f)
            return self._call_unmasked(x[m], f, backend, interpret, **kw)
        if not self.supports_masked_delivery:
            raise ValueError(
                f"aggregator {self.name!r} has no traced-mask implementation; "
                f"use a concrete mask or one of "
                f"{sorted(k for k, s in _REGISTRY.items() if s.supports_masked_delivery)}")
        if self.masked_fn is not None:
            return (self.masked_fn(x, f, mask) if self.takes_f
                    else self.masked_fn(x, mask))
        # selection-based: d2 -> masked weights -> convex combination
        d2 = dispatch.pairwise_sqdists(x, backend=backend, interpret=interpret)
        w = self.weights_from_d2(d2, f, mask=mask, **self.filter_kwargs(**kw))
        return (w @ x.astype(jnp.float32)).astype(x.dtype)


_REGISTRY: dict[str, Aggregator] = {}


def register(spec: Aggregator) -> Aggregator:
    if spec.name in _REGISTRY:
        raise ValueError(f"aggregator {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> Aggregator:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown aggregator {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def specs() -> tuple[Aggregator, ...]:
    return tuple(_REGISTRY[n] for n in names())


# ---------------------------------------------------------------------------
# built-in rules
# ---------------------------------------------------------------------------

register(Aggregator(
    name="mda", fn=dispatch.mda, takes_f=True,
    breakdown="n >= 2f+1", requires=(2, 1),
    doc="Minimum-Diameter Averaging (the paper's worker-gradient GAR)",
    variance_threshold=rules.mda_variance_threshold,
    needs_pairwise_d2=True, selection_based=True, tree_mode="selection",
    backends=("jnp", "pallas"),
    weights_from_d2=rules.mda_weights_from_d2,
    tunables=frozenset({"exact_limit"})))

register(Aggregator(
    name="median", fn=dispatch.median, takes_f=False,
    breakdown="n >= 2f+1", requires=(2, 1),
    doc="coordinate-wise median (server-model DMC rule)",
    backends=("jnp", "pallas"),
    masked_fn=rules.masked_coordinate_median,
    masked_views=dispatch.masked_median_views))

register(Aggregator(
    name="meamed", fn=dispatch.meamed, takes_f=True,
    breakdown="n >= 2f+1", requires=(2, 1),
    doc="mean-around-median (sync worker gather rule)",
    backends=("jnp", "pallas"),
    masked_fn=rules.masked_meamed))

register(Aggregator(
    name="trimmed_mean", fn=dispatch.trimmed_mean, takes_f=True,
    breakdown="n >= 2f+1", requires=(2, 1),
    doc="coordinate-wise trimmed mean (baseline)",
    backends=("jnp", "pallas"),
    masked_fn=rules.masked_trimmed_mean))

register(Aggregator(
    name="krum", fn=dispatch.krum, takes_f=True,
    breakdown="n >= 2f+3", requires=(2, 3),
    doc="Krum (Blanchard et al. 2017) — single best-scored vector",
    variance_threshold=rules.krum_variance_threshold,
    needs_pairwise_d2=True, selection_based=True, tree_mode="selection",
    backends=("jnp", "pallas"),
    weights_from_d2=rules.krum_weights_from_d2))

register(Aggregator(
    name="multi_krum", fn=dispatch.multi_krum, takes_f=True,
    breakdown="n >= 2f+3", requires=(2, 3),
    doc="Multi-Krum — average of the m best-scored vectors",
    variance_threshold=rules.krum_variance_threshold,
    needs_pairwise_d2=True, selection_based=True, tree_mode="selection",
    backends=("jnp", "pallas"),
    weights_from_d2=rules.multi_krum_weights_from_d2,
    tunables=frozenset({"m"})))

register(Aggregator(
    name="bulyan", fn=rules.bulyan, takes_f=True,
    breakdown="n >= 4f+3", requires=(4, 3),
    doc="Bulyan — recursive Krum + trimmed aggregation (baseline)",
    needs_pairwise_d2=True, tree_mode=None))

register(Aggregator(
    name="vote", fn=rules.vote, takes_f=False,
    breakdown="n >= 2f+1", requires=(2, 1),
    doc="coordinate-wise plurality vote (serve-quorum read rule for "
        "discrete outputs, e.g. argmax token ids)",
    masked_fn=rules.masked_vote))

register(Aggregator(
    name="mean", fn=rules.mean, takes_f=False,
    breakdown="none (f = 0 only)", requires=(0, 1),
    doc="plain averaging (the paper's non-resilient strawman)",
    masked_fn=rules.masked_mean))


# ---------------------------------------------------------------------------
# registry-derived documentation (README "Aggregators" table)
# ---------------------------------------------------------------------------


def markdown_table(n: int = 18, f: int = 2) -> str:
    """The README aggregator table, derived from the registry
    (``python -m repro.agg`` regenerates it)."""
    head = ("| rule | breakdown point | variance threshold (n=%d, f=%d) | "
            "backends | masked delivery | pytree |" % (n, f))
    sep = "|---|---|---|---|---|---|"
    out = [head, sep]
    for s in specs():
        if s.variance_threshold is None:
            vt = "—"
        else:
            v = s.variance_threshold(n, f)
            vt = "inf" if v == float("inf") else f"{v:.3f}"
        out.append(
            f"| `{s.name}` | {s.breakdown} | {vt} | {', '.join(s.backends)} | "
            f"{'yes' if s.supports_masked_delivery else 'concrete-only'} | "
            f"{s.tree_mode or '—'} |")
    return "\n".join(out)
