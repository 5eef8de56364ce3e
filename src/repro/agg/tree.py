"""Pytree aggregation: apply any registered rule to a stacked pytree.

Leaves carry a leading stack axis ``[n, ...]`` (one entry per sender). The
rule's ``tree_mode`` capability decides the decomposition — no call site ever
branches on rule identity:

  * ``"leafwise"``  — coordinate-wise rules apply independently per leaf
    (exactly equal to the flat rule on the flattened stack);
  * ``"selection"`` — distance-based rules need *global* distances: the [n,n]
    distance matrix is assembled from per-leaf partial Grams (no full
    flatten/copy of the stack), the rule's ``weights_from_d2`` selects once,
    and leaves are combined with the selection weights.

An optional boolean delivery ``mask`` [n] restricts aggregation to delivered
senders; it composes with both modes (masked leafwise rules / masked
selection), so netsim ``TraceDelivery`` quorums work with any mask-capable
rule.

:func:`tree_gram` is the ONE streaming Gram path, shared with the distributed
protocol (``repro.core.protocol`` imports it): each leaf contributes a [n, n]
partial via a multi-dim ``dot_general`` — never a ``reshape(n, -1)`` flatten,
which would force the SPMD partitioner to replicate sharded leaves — and
large leaves stream chunk-by-chunk so no ``[n, P]`` stack (or all-gathered
full gradient) ever materializes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import registry, rules

# streaming thresholds (shared with the protocol's exchange streaming)
STREAM_MAX_DIM1 = 512   # layer-stack dims stream one layer at a time
STREAM_N_CHUNKS = 16    # wide dims (vocab tables) stream in 16 chunks
DEFAULT_CHUNK_BYTES = 256 * 2**20


def _gram_spec(shape, mesh) -> P:
    """Layout for the Gram contraction: the [n, n] output cannot be 'rep'-
    sharded on both dims, so we first all-to-all the leaf — replica axis
    replicated, 'model'/'rep'/'fsdp' spread over *body* dims — making the
    n x n dot fully local with a tiny psum over the sharded contraction dims.
    Without this, the SPMD partitioner all-gathers the entire replica stack
    per device (observed: 18 GiB temps on internlm2)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    order_axes = (("model", sizes["model"]), ("rep", sizes["rep"]),
                  ("fsdp", sizes["fsdp"]))
    body = list(shape[1:])
    spec: list = [None] * len(body)
    order = sorted(range(len(body)), key=lambda i: -body[i])
    taken: set = set()
    for ax, size in order_axes:
        if size <= 1:
            continue
        at = next((i for i in order
                   if i not in taken and body[i] % size == 0 and body[i] >= size),
                  None)
        if at is not None:
            spec[at] = ax
            taken.add(at)
    return P(None, *spec)


def _chunk_gram(chunk):
    lf = chunk.astype(jnp.float32)
    axes = tuple(range(1, lf.ndim))
    # dot_general with multi-dim contraction — NO flattening reshape
    # (tensordot reshapes to 2D, which forces XLA to replicate sharded
    # leaves; dot_general contracts sharded dims directly).
    return jax.lax.dot_general(lf, lf, ((axes, axes), ((), ())))   # [n, n]


def _reduce_stream(fn, leaf, chunk_bytes: int):
    """Accumulate fn(chunk) over slices of a large leaf: dim-1 for layer
    stacks, last dim for wide tables (mirrors the protocol's exchange
    streaming — bounds per-chunk transients without a full-leaf copy)."""
    big = leaf.size * leaf.dtype.itemsize > chunk_bytes
    n = leaf.shape[0]
    if leaf.ndim < 3 or not big:
        return fn(leaf)
    if leaf.shape[1] <= STREAM_MAX_DIM1:
        ax, n_steps, csize = 1, leaf.shape[1], 1
    elif leaf.shape[-1] % STREAM_N_CHUNKS == 0:
        ax = leaf.ndim - 1
        n_steps = STREAM_N_CHUNKS
        csize = leaf.shape[-1] // STREAM_N_CHUNKS
    else:
        return fn(leaf)

    def chunk_at(i):
        sl = jax.lax.dynamic_slice_in_dim(leaf, i * csize, csize, axis=ax)
        return jnp.squeeze(sl, 1) if (ax == 1 and csize == 1) else sl

    def body(i, acc):
        return acc + fn(chunk_at(i))

    return jax.lax.fori_loop(0, n_steps, body, jnp.zeros((n, n), jnp.float32))


def tree_gram(stacked_tree, mesh=None,
              chunk_bytes: int = DEFAULT_CHUNK_BYTES) -> jax.Array:
    """[n, n] Gram matrix over the full flattened stack, from per-leaf
    streaming partials.

    With a ('rep','fsdp','model') ``mesh``: whole-leaf all-to-all (gram_spec:
    'rep' moved onto a body dim) + local multi-dim dot + tiny psum.
    Empirically (EXPERIMENTS.md §Perf iteration log) this is the ONLY variant
    the SPMD partitioner handles without involuntary replication; per-chunk
    constraints and plain rep-sharded dots both blow up. Leaves whose bodies
    cannot host the 'rep' axis fall back to the streamed rep-gather."""
    total = None
    for l in jax.tree.leaves(stacked_tree):
        if mesh is not None and l.ndim >= 2:
            spec = _gram_spec(l.shape, mesh)
            if "rep" in jax.tree.leaves(tuple(spec)):
                lf = jax.lax.with_sharding_constraint(
                    l.astype(jnp.float32), NamedSharding(mesh, spec))
                g = _chunk_gram(lf)
            else:
                g = _reduce_stream(_chunk_gram, l, chunk_bytes)
        else:
            g = _reduce_stream(_chunk_gram, l, chunk_bytes)
        total = g if total is None else total + g
    return total


def tree_agg(rule, stacked_tree, f: int = 0, *, mask=None, mesh=None,
             chunk_bytes: int = DEFAULT_CHUNK_BYTES, **kw):
    """Aggregate a stacked pytree with a registered rule.

    ``rule`` is a registry name or an :class:`~repro.agg.registry.Aggregator`.
    Extra kwargs are filtered against the rule's declared tunables (e.g.
    ``exact_limit`` for MDA), so generic call sites can pass a superset.
    ``mesh``/``chunk_bytes`` tune the selection path's streaming Gram for
    sharded stacks (see :func:`tree_gram`).
    """
    spec = rule if isinstance(rule, registry.Aggregator) else registry.get(rule)
    leaves = jax.tree.leaves(stacked_tree)
    n = leaves[0].shape[0]
    spec.validate(n, f)
    if spec.tree_mode == "leafwise":
        # Per-leaf application beats flatten-then-apply here: coordinate-wise
        # rules commute with flattening, but the [n, D] concat/split copies
        # cost more than the repeated (elementwise, fusion-friendly) op graph,
        # especially under the simulator's receiver vmap.
        if mask is None:
            return jax.tree.map(
                lambda l: spec._call_unmasked(l, f, None, None, **kw),
                stacked_tree)
        return jax.tree.map(lambda l: spec(l, f, mask=mask, **kw),
                            stacked_tree)
    if spec.tree_mode != "selection":
        raise ValueError(
            f"aggregator {spec.name!r} does not support pytree aggregation "
            f"(tree_mode={spec.tree_mode!r})")
    d2 = rules.sqdists_from_gram(tree_gram(stacked_tree, mesh=mesh,
                                           chunk_bytes=chunk_bytes))
    w = spec.weights_from_d2(d2, f, mask=mask, **spec.filter_kwargs(**kw))
    return jax.tree.map(
        lambda l: jnp.tensordot(w, l.astype(jnp.float32), axes=1).astype(l.dtype),
        stacked_tree)


def selection_weights(rule, d2: jax.Array, f: int = 0, *, mask=None,
                      **kw) -> jax.Array:
    """[n,n] distances -> [n] aggregation weights for a selection-based rule.

    The entry point for call sites that already own the distance matrix (the
    sharded protocol builds it from leaf-partial Grams with a tiny [G,G] psum
    and averages locally with the returned weights).
    """
    spec = rule if isinstance(rule, registry.Aggregator) else registry.get(rule)
    if not spec.selection_based or spec.weights_from_d2 is None:
        raise ValueError(f"aggregator {spec.name!r} is not selection-based; "
                         "selection_weights needs one of "
                         f"{[s.name for s in registry.specs() if s.selection_based]}")
    spec.validate(d2.shape[0], f)
    return spec.weights_from_d2(d2, f, mask=mask, **spec.filter_kwargs(**kw))
