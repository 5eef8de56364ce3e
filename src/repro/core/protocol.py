"""Distributed ByzSGD on a TPU mesh (pjit formulation).

Maps the paper's server/worker protocol onto the ('rep', 'fsdp', 'model') view
of the production mesh (launch/mesh.py):

  * 'rep' indexes G = n_groups co-located worker+server groups (the failure
    domains). Group g holds server replica theta^(g) (ZeRO-sharded over its
    'fsdp' x 'model' chips) and computes worker gradient g^(g) on its 1/G of
    the global batch.
  * scatter step  = pull (per-worker masked Median over delivered replicas)
                  -> per-group gradient (vmap over 'rep')
                  -> MDA per server group over its delivered gradient quorum
                  -> local SGD update.
  * gather step   = DMC: masked Median across server replicas (every T steps).

Asynchrony = per-step delivery quorums: every step builder takes a pluggable
``DeliveryModel`` (core/quorum.py) — ``UniformDelivery`` (Assumption 7, the
default, with the *same* PRNG chain as the single-host simulator so the
1-device protocol is oracle-checked against it) or a netsim ``TraceDelivery``
replaying realized quorums. Byzantine behaviour is injected for
tests/benchmarks and *excluded from the benchmark cells' programs* (a real
adversary costs nothing extra on the honest path).

Engines:
  * 'naive'   — baseline, paper-faithful collective volume: gradients/replicas
    are all-gathered across 'rep' (volume (G-1)/G * G * P per step, like the
    paper's broadcast-to-all message pattern), streamed layer-by-layer to bound
    transients.
  * 'sharded' — beyond-paper: aggregations stay as reductions over 'rep'
    (XLA lowers to reduce-scatter/all-reduce, ~2P per step) and the MDA subset
    selection is driven by the leaf-partial Gram matrix (exact distances, tiny
    [G,G] psum). See DESIGN.md §2 and EXPERIMENTS.md §Perf.

:class:`ProtocolEngine` gives the protocol the fused-epoch treatment of
``repro.core.engine`` (shared scaffolding in ``repro.core.epochs``): donated
``lax.scan`` epochs with the DMC gather at the T-boundary via ``lax.cond`` on
the carried counter, per-group metrics reduced on device, and the bounded
semantic compile cache. ``repro.exp.run(spec.replace(runner="protocol"))``
drives it; the single-host ``EpochEngine`` is its correctness oracle
(tests/test_protocol_engine.py).
"""
from __future__ import annotations

import contextvars
import re
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import agg
from ..agg import dispatch as _agg_dispatch
from ..agg import rules as _agg_rules
from .attacks import ByzantineSpec, inject_gradients, inject_models
from .epochs import EpochRunner, delivery_cache_key, fn_cache_key
from .quorum import UniformDelivery

# The stages of one ByzSGD step. Each is built under a ``jax.named_scope`` of
# its name, so every HLO instruction's op_name metadata, and so a profiler
# trace's ``tf_op``, says which stage made it. The ops between the stages (key
# split, learning rate, the gather's predicate, step metrics) are unscoped.
STAGES = ("pull", "worker_grad", "distances", "aggregate", "update", "gather")
# a transform's wrapper around a scope in an op_name path: vmap(jvp(pull))
_WRAPPER = re.compile(r"[\w.<>]*\((.*)\)")


def stage_of(op_name: str) -> str | None:
    """The stage an op was built in, from its op_name metadata (or a trace's
    ``tf_op``): the outermost scope of the path that, stripped of transform
    wrappers, is in :data:`STAGES`. The path's last component is the op
    itself, not a scope; after a ``;`` come the names of ops XLA fused into
    it. None for an op outside every stage."""
    for scope in op_name.split(";", 1)[0].split("/")[:-1]:
        while (m := _WRAPPER.fullmatch(scope)):
            scope = m.group(1)
        if scope in STAGES:
            return scope
    return None

# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ProtocolConfig:
    n_groups: int                 # G = n_workers = n_servers (failure domains)
    f_workers: int
    f_servers: int
    q_workers: int
    q_servers: int
    T: int = 50                   # gather every T steps
    grad_microbatches: int = 1    # sequential accumulation per worker step
    engine: str = "sharded"       # 'naive' (paper volume) | 'sharded'
    pull: str = "median"          # 'median' (async variant) | 'roundrobin'
                                  # (sync variant §5: one model/step via
                                  # collective-permute + distance filter)
    gar: str = "mda"              # worker-gradient rule (selection-based:
                                  # aggregation = weights over 'rep')
    pull_gar: str = "median"      # model rule for the masked worker pull
    gather_gar: str = "median"    # model rule for the DMC gather
    optimizer: str = "sgd"        # repro.optim registry ref for the local
                                  # update (per-replica state in ByzState.opt)
    exchange_dtype: str = "float32"
    mda_exact_limit: int = 200_000
    chunk_bytes: int = 256 * 2**20   # stream leaves bigger than this over dim 1
    byz: ByzantineSpec = field(default_factory=ByzantineSpec)

    def __post_init__(self):
        # The sharded engine reduces gradients as weighted sums over 'rep',
        # so the gradient rule must be selection-based (convex weights); the
        # pull/DMC rule must take traced delivery masks.
        spec = agg.get(self.gar)
        if not spec.selection_based:
            raise ValueError(
                f"protocol gar={self.gar!r} must be selection-based; have "
                f"{[s.name for s in agg.specs() if s.selection_based]}")
        spec.validate(self.q_workers, self.f_workers)
        # masked_pull applies the rule per leaf chunk, so it must be a
        # coordinate-wise (leafwise) rule with a traced-mask implementation;
        # selection rules would pick a different sender subset per leaf.
        for role in ("pull_gar", "gather_gar"):
            name = getattr(self, role)
            pspec = agg.get(name)
            if pspec.tree_mode != "leafwise" or pspec.masked_fn is None:
                ok = [s.name for s in agg.specs()
                      if s.tree_mode == "leafwise" and s.masked_fn is not None]
                raise ValueError(f"{role}={name!r} must be a "
                                 f"coordinate-wise rule with traced-mask "
                                 f"support; have {ok}")
            pspec.validate(self.q_servers, self.f_servers)
        from .. import optim as _optim
        if self.optimizer not in _optim.OPTIMIZERS:
            raise ValueError(f"unknown optimizer {self.optimizer!r}; "
                             f"have {sorted(_optim.OPTIMIZERS)}")

    @staticmethod
    def derive(R: int, divisor: int = 1, *, T: int = 50, engine: str = "sharded",
               exchange_dtype: str = "float32", grad_microbatches: int = 1,
               pull: str = "median", byz: ByzantineSpec | None = None,
               f_workers: int | None = None, f_servers: int | None = None,
               q_workers: int | None = None, q_servers: int | None = None,
               gar: str = "mda", pull_gar: str = "median",
               gather_gar: str = "median", optimizer: str = "sgd",
               mda_exact_limit: int = 200_000) -> "ProtocolConfig":
        """Resilience parameters for G = R // divisor groups.

        Defaults: f_w = (G-1)//3, f_ps = (G-2)//3 (the paper's
        asymptotically-optimal 1/3 bounds) and full-minus-f quorums. Explicit
        ``f_*``/``q_*``/GAR overrides let ``Experiment.to_protocol_config``
        lower a declared cluster shape exactly (so the 1-device protocol and
        the single-host simulator draw identical quorums)."""
        G = R // divisor
        f_w = max((G - 1) // 3, 0) if f_workers is None else f_workers
        f_ps = max((G - 2) // 3, 0) if f_servers is None else f_servers
        q_w = (G - f_w) if q_workers is None else q_workers
        q_ps = (max(G - f_ps, min(2 * f_ps + 2, G)) if q_servers is None
                else q_servers)
        return ProtocolConfig(n_groups=G, f_workers=f_w, f_servers=f_ps,
                              q_workers=q_w, q_servers=q_ps, T=T, engine=engine,
                              exchange_dtype=exchange_dtype,
                              grad_microbatches=grad_microbatches, pull=pull,
                              gar=gar, pull_gar=pull_gar,
                              gather_gar=gather_gar, optimizer=optimizer,
                              mda_exact_limit=mda_exact_limit,
                              byz=byz or ByzantineSpec())


class ByzState(NamedTuple):
    params: Any          # pytree, leaves [G, ...]
    t: jax.Array         # scalar int32
    key: jax.Array       # protocol PRNG (quorums / attacks)
    opt: Any = ()        # per-replica optimizer state (empty for sgd), leaves
                         # [G, ...] stacked/sharded like params


# ---------------------------------------------------------------------------
# sharding rules for replica-stacked leaves
# ---------------------------------------------------------------------------


# Explicit per-leaf layout table (Megatron conventions), matched by the leaf's
# final path component. COLUMN-parallel ([.., D_in, D_out]): 'model' on the
# OUTPUT dim (matches head-sharded attention activations and F-sharded MLP
# intermediates). ROW-parallel ([.., D_out_contraction, D]): 'model' on the
# contraction dim (output psum/reduce-scatter). Tables: 'model' on vocab.
# 'fsdp' (ZeRO intra-group axis, K>1 archs) takes the complementary dim.
# Heuristic placement caused layout churn ("involuntary full remat") — see
# EXPERIMENTS.md §Perf iteration log.
_COL_LEAVES = {"w_gate", "w_up", "cWk"}
# ROW for: contraction-sharded outputs (wo/w_down/...), projections whose
# outputs reshape across non-divisible head boundaries (rwkv mixers), and
# mamba's in_proj (its output is segment-sliced, so output sharding would cut
# across segment boundaries -> SPMD relayout churn / SIGFPE).
_ROW_LEAVES = {"wo", "w_down", "out_proj", "Wo", "cWv", "wB", "in_proj",
               "Wr", "Wk", "Wv", "Wg", "cWr", "wA"}
_TABLE_LEAVES = {"table", "pos_dec"}
# wq/wk/wv are COL iff the (kv-)head count divides |model| (else the head
# reshape fights the flat output sharding); decided per-arch via `overrides`.


def _place(body, picks, M, K):
    """picks: ((axis_name, dim_index), ...) — applied iff divisible."""
    spec = [None] * len(body)
    for name, idx in picks:
        size = M if name == "model" else K
        if size <= 1:
            continue
        i = idx % len(body)
        if spec[i] is None and body[i] % size == 0 and body[i] >= size:
            spec[i] = name
    return spec


def leaf_spec(shape: tuple[int, ...], mesh, *, leading_rep: bool = True,
              name: str = "", overrides: dict | None = None) -> P:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    M, K = sizes["model"], sizes["fsdp"]
    body = list(shape[1:]) if leading_rep else list(shape)
    mode = (overrides or {}).get(name)
    if mode == "col" and len(body) >= 2:
        spec = _place(body, (("model", -1), ("fsdp", -2)), M, K)
    elif mode == "row" and len(body) >= 2:
        spec = _place(body, (("model", -2), ("fsdp", -1)), M, K)
    elif name in _COL_LEAVES and len(body) >= 2:
        spec = _place(body, (("model", -1), ("fsdp", -2)), M, K)
    elif name in _ROW_LEAVES and len(body) >= 2:
        spec = _place(body, (("model", -2), ("fsdp", -1)), M, K)
    elif name in _TABLE_LEAVES and len(body) >= 2:
        spec = _place(body, (("model", -2), ("fsdp", -1)), M, K)
    else:
        # fallback: largest divisible dims (covers odd leaves). A size-1
        # axis never claims a dim — it would shard nothing while blocking
        # the other axis from the leaf's best dim. 'fsdp' DOES take
        # divisible 1D bodies (biases, norm scales): GSPMD propagates the
        # fsdp split onto them inside the epoch anyway, and an input left
        # replicated would mismatch that output layout and silently drop
        # the state donation (REPRO-HLO-DONATION, 2D lane).
        spec = [None] * len(body)
        order = sorted(range(len(body)), key=lambda i: -body[i])
        m_at = next((i for i in order if body[i] % M == 0 and body[i] >= M
                     and len(body) >= 2), None) if M > 1 else None
        if m_at is not None:
            spec[m_at] = "model"
        k_at = next((i for i in order
                     if i != m_at and body[i] % K == 0 and body[i] >= K), None)
        if k_at is not None and K > 1:
            spec[k_at] = "fsdp"
    if leading_rep:
        return P("rep", *spec)
    return P(*spec)


def attn_overrides(cfg, mesh) -> dict:
    """wq is COL-parallel when heads divide |model| (one x-gather feeds a
    local matmul with head-sharded output — ~3x cheaper than ROW's full-size
    output psum, §Perf iteration 11). wk/wv stay ROW-parallel always: COL +
    GQA kv reshapes trigger an XLA SPMD SIGFPE on this backend (iteration 9).
    """
    # COL wq re-triggers the SIGFPE even for divisible heads (iteration 11,
    # REFUTED) — all three stay ROW on this backend.
    del mesh
    return {"wq": "row", "wk": "row", "wv": "row"}


def _named_tree_shardings(shapes_tree, mesh, overrides: dict | None = None):
    """Per-leaf-name NamedShardings for a replica-stacked pytree. The leaf's
    final path component keys the layout table, so optimizer moment trees
    (which mirror the param tree's names) land on the same shards as their
    params."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes_tree)
    out = []
    for path, leaf in flat:
        if leaf.ndim == 0 or leaf.size <= 2:
            out.append(NamedSharding(mesh, P()))
            continue
        name = str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))
        out.append(NamedSharding(mesh, leaf_spec(leaf.shape, mesh, name=name,
                                                 overrides=overrides)))
    return jax.tree_util.tree_unflatten(treedef, out)


def state_shardings(state_shapes, mesh, overrides: dict | None = None):
    """NamedShardings for a ByzState shape-tree (per-leaf-name layout)."""
    params = _named_tree_shardings(state_shapes.params, mesh, overrides)
    opt = _named_tree_shardings(state_shapes.opt, mesh, overrides)
    scalar = NamedSharding(mesh, P())
    return ByzState(params=params, t=scalar, key=scalar, opt=opt)


def body_spec(body_shape: tuple[int, ...], mesh) -> tuple:
    """Sharding tuple for a replica-body (no leading axes): 'model' on the
    largest divisible dim, 'fsdp' on the next."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    M, K = sizes["model"], sizes["fsdp"]
    body = list(body_shape)
    spec: list = [None] * len(body)
    order = sorted(range(len(body)), key=lambda i: -body[i])
    m_at = next((i for i in order if body[i] % M == 0 and body[i] >= M),
                None) if M > 1 else None
    if m_at is not None:
        spec[m_at] = "model"
    k_at = next((i for i in order
                 if i != m_at and body[i] % K == 0 and body[i] >= K), None)
    if k_at is not None and K > 1:
        spec[k_at] = "fsdp"
    return tuple(spec)


def _replicaless_spec(shape, mesh) -> P:
    """Sharding for consolidated (serving) params: no 'rep' axis; combine
    ('rep','fsdp') on the fsdp-eligible dim for maximal spread."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    M, RK = sizes["model"], sizes["rep"] * sizes["fsdp"]
    body = list(shape)
    spec: list = [None] * len(body)
    order = sorted(range(len(body)), key=lambda i: -body[i])
    m_at = next((i for i in order if body[i] % M == 0 and body[i] >= M), None)
    if m_at is not None:
        spec[m_at] = "model"
    k_at = next((i for i in order
                 if i != m_at and body[i] % RK == 0 and body[i] >= RK), None)
    if k_at is not None:
        spec[k_at] = ("rep", "fsdp")
    return P(*spec)


# ---------------------------------------------------------------------------
# chunked leaf streaming (bounds all-gather transients on huge leaves)
# ---------------------------------------------------------------------------


def _map_dim1(fn, *leaves, mesh=None):
    """Apply fn across dim-1 slices of [G, L, ...] leaves.

    Implemented with fori_loop + dynamic_slice on the (unsharded) layer dim —
    NO transposes of sharded tensors (moveaxis of a ('rep', None, 'model')
    leaf triggers XLA SPMD "involuntary full rematerialization" = per-device
    replication of the whole stack). The loop-carried accumulator is
    explicitly constrained to the replica-stacked layout (otherwise XLA
    replicates it).
    """
    L = leaves[0].shape[1]

    def slice_at(i):
        return tuple(jnp.squeeze(jax.lax.dynamic_slice_in_dim(l, i, 1, axis=1), 1)
                     for l in leaves)

    out0 = jax.eval_shape(fn, *(jax.eval_shape(lambda l: jnp.squeeze(l[:, :1], 1), l)
                                for l in leaves))

    def body(i, acc):
        res = fn(*slice_at(i))
        return jax.lax.dynamic_update_slice_in_dim(acc, res[:, None], i, axis=1)

    init = jnp.zeros((out0.shape[0], L) + out0.shape[1:], out0.dtype)
    if mesh is not None:
        init = jax.lax.with_sharding_constraint(
            init, NamedSharding(mesh, P("rep", None,
                                        *body_spec(out0.shape[1:], mesh))))
    return jax.lax.fori_loop(0, L, body, init)


# streaming thresholds shared with the Gram path (repro.agg.tree)
_STREAM_MAX_DIM1 = agg.tree.STREAM_MAX_DIM1
_STREAM_N_CHUNKS = agg.tree.STREAM_N_CHUNKS


def _map_last_chunks(fn, *leaves, n_chunks: int, mesh=None):
    """Chunked streaming over the LAST (unsharded) dim — used for wide tables
    (embeddings: [G, V('model'), D]); slicing the sharded V dim would localise
    each chunk to a single device, so we slice D instead."""
    ax = leaves[0].ndim - 1
    D = leaves[0].shape[ax]
    csize = D // n_chunks

    def slice_at(i):
        return tuple(jax.lax.dynamic_slice_in_dim(l, i * csize, csize, axis=ax)
                     for l in leaves)

    out0 = jax.eval_shape(fn, *(jax.eval_shape(
        lambda l: jax.lax.slice_in_dim(l, 0, csize, axis=ax), l)
        for l in leaves))

    def body(i, acc):
        res = fn(*slice_at(i))
        return jax.lax.dynamic_update_slice_in_dim(acc, res, i * csize, axis=ax)

    full_shape = out0.shape[:ax] + (D,)
    init = jnp.zeros(full_shape, out0.dtype)
    if mesh is not None:
        init = jax.lax.with_sharding_constraint(
            init, NamedSharding(mesh, P("rep", *body_spec(full_shape[1:], mesh))))
    return jax.lax.fori_loop(0, n_chunks, body, init)


def _leaf_stream(fn, chunk_bytes: int, mesh=None):
    """Wrap a per-leaf op to stream over the layer-stack (or table-row) dim
    when large."""
    def apply(*leaves):
        l0 = leaves[0]
        big = l0.size * l0.dtype.itemsize > chunk_bytes
        if l0.ndim >= 3 and big and l0.shape[1] <= _STREAM_MAX_DIM1:
            return _map_dim1(fn, *leaves, mesh=mesh)
        if (l0.ndim >= 3 and big
                and l0.shape[-1] % _STREAM_N_CHUNKS == 0):
            return _map_last_chunks(fn, *leaves, n_chunks=_STREAM_N_CHUNKS,
                                    mesh=mesh)
        return fn(*leaves)
    return apply


# ---------------------------------------------------------------------------
# protocol ops
# ---------------------------------------------------------------------------


# the dtype masked_pull returns float32 leaves' views in (views_in); None
# keeps each leaf's dtype
_VIEW_DTYPE = contextvars.ContextVar("view_dtype", default=None)


@contextmanager
def views_in(dtype):
    """Within the block, :func:`masked_pull` returns the views of float32
    leaves in ``dtype``. The pull hands its compute dtype down this way, so
    that the Median kernel writes it directly, while masked_pull keeps the
    signature ``(params, masks, cfg, mesh, rule)`` that stand-ins of it
    share."""
    token = _VIEW_DTYPE.set(jnp.dtype(dtype))
    try:
        yield
    finally:
        _VIEW_DTYPE.reset(token)


def masked_pull(params, masks, cfg: ProtocolConfig, mesh=None, rule=None):
    """Per-receiver masked aggregation over the replica axis.

    params leaves [G, ...]; masks [G_recv, G_send] bool. Returns leaves
    [G_recv, ...] — worker/server g's aggregated view of the replicas, in
    the leaf's dtype or, for float32 leaves, an enclosing
    :func:`views_in`'s. The rule defaults to ``cfg.pull_gar`` (any
    registered rule with traced-mask support), the paper's Median; the DMC
    gather passes ``cfg.gather_gar``.

    Where the rule has ``masked_views`` and the replicas live whole on one
    device (no mesh, or a mesh of one), each leaf goes to it in one call:
    where the backend resolves to its kernel, that reads each replica once
    and writes each view once, in its dtype. Its jnp side, and every other
    case, stream the leaf through the rule (``_leaf_stream``) and cast.
    """
    spec = agg.get(rule or cfg.pull_gar)
    whole = mesh is None or mesh.size == 1

    def med_chunk(chunk):  # [G, ...]
        def one(mask):
            return spec(chunk.astype(jnp.float32), cfg.f_servers, mask=mask)
        out = jax.vmap(one)(masks).astype(chunk.dtype)
        if mesh is not None:
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, P("rep", *body_spec(out.shape[1:], mesh))))
        return out

    stream = _leaf_stream(med_chunk, cfg.chunk_bytes, mesh)

    view_dtype = _VIEW_DTYPE.get()

    def op(leaf):
        dt = (view_dtype if view_dtype is not None
              and leaf.dtype == jnp.float32 else leaf.dtype)

        def streamed(l):
            return stream(l).astype(dt)

        if spec.masked_views is not None and whole:
            return spec.masked_views(leaf, masks, dt, fallback=streamed)
        return streamed(leaf)

    return jax.tree.map(op, params)


# The [G, G] Gram over the full gradient is the shared streaming
# implementation in repro.agg.tree (leaf-partial dot_general + tiny psum,
# never a flattened [G, P] stack); re-exported here for the step builders.
tree_gram = agg.tree.tree_gram


def quorum_weights(d2: jax.Array, quorum_idx: jax.Array, f: int,
                   cfg: ProtocolConfig) -> jax.Array:
    """Per-server selection weights for the configured gradient rule.

    d2: [G, G] squared distances; quorum_idx: [G_recv, q] delivered worker
    indices per server. Restricts the distance matrix to each delivered
    quorum, asks the rule's ``weights_from_d2`` for averaging weights (rows
    sum to 1; one-hot for Krum), and scatters back to [G_recv, G_send]."""
    G = d2.shape[0]

    def one(idx):
        sub = d2[idx][:, idx]                       # [q, q]
        w = agg.selection_weights(cfg.gar, sub, f,
                                  exact_limit=cfg.mda_exact_limit)
        return jnp.zeros((G,), jnp.float32).at[idx].set(w)

    return jax.vmap(one)(quorum_idx)


def aggregate_gradients(grads, weights, cfg: ProtocolConfig, mesh=None):
    """G_hat[s] = sum_w weights[s, w] * grads[w]  (leaf-wise, streamed).

    naive engine: materialise the all-gathered gradient stack per chunk
    (replicate over 'rep' only, body sharding preserved); sharded engine:
    leave the contraction to XLA. Ring-model traffic is the same either way
    — (G-1)·P per device, HLO-audited by ``repro.analyze`` — the engines
    differ in whether the [G, ...] operand stack is materialised per device
    (temp memory) before the dot."""
    dt = jnp.dtype(cfg.exchange_dtype)

    def agg_chunk(chunk):  # [G, ...]
        c = chunk.astype(dt)
        if cfg.engine == "naive" and mesh is not None:
            c = jax.lax.with_sharding_constraint(
                c, NamedSharding(mesh, P(None, *body_spec(c.shape[1:], mesh))))
        out = jax.lax.dot_general(weights.astype(dt), c,
                                  (((1,), (0,)), ((), ())))  # [G_recv, ...]
        if mesh is not None:
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, P("rep", *body_spec(out.shape[1:], mesh))))
        return out

    op = _leaf_stream(agg_chunk, cfg.chunk_bytes, mesh)
    return jax.tree.map(op, grads)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def make_init_fn(bundle, pcfg: ProtocolConfig):
    """Returns init(key) -> ByzState with replica-stacked params (and, for
    stateful optimizers, replica-stacked moment state)."""
    from .. import optim as _optim
    pdt = jnp.dtype(bundle.cfg.param_dtype)
    opt = _optim.get(pcfg.optimizer)

    def init(key):
        k_model, k_run = jax.random.split(key)
        p0 = bundle.init(k_model)
        p0 = jax.tree.map(lambda l: l.astype(pdt), p0)
        params = jax.tree.map(
            lambda l: jnp.broadcast_to(l, (pcfg.n_groups,) + l.shape), p0)
        return ByzState(params=params, t=jnp.zeros((), jnp.int32), key=k_run,
                        opt=opt.init(params))

    return init


def make_scatter_step(bundle, pcfg: ProtocolConfig, lr_schedule,
                      with_attack: bool = False, mesh=None, delivery=None):
    """One ByzSGD scatter step. batch leaves: [G, per_group, ...].

    ``delivery`` is a :class:`~repro.core.quorum.DeliveryModel`; the default
    ``UniformDelivery`` over G-of-G nodes draws the same quorums (same key
    chain and split order) as the single-host simulator's scatter step, which
    is what makes the simulator the protocol's oracle. A netsim
    ``TraceDelivery`` replays realized quorums instead.
    """
    from .. import optim as _optim
    G = pcfg.n_groups
    delivery = delivery or UniformDelivery(G, G, pcfg.q_workers,
                                           pcfg.q_servers)
    optimizer = _optim.get(pcfg.optimizer)

    overrides = attn_overrides(bundle.cfg, mesh) if mesh is not None else {}

    def _constrain_like_params(tree):
        if mesh is None:
            return tree
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        out = []
        for path, l in flat:
            if l.ndim >= 1 and l.size > 2:
                nm = str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))
                l = jax.lax.with_sharding_constraint(
                    l, NamedSharding(mesh, leaf_spec(l.shape, mesh, name=nm,
                                                     overrides=overrides)))
            out.append(l)
        return jax.tree_util.tree_unflatten(treedef, out)

    def scatter_step(state: ByzState, batch):
        # split order matches ByzSGDSimulator.scatter_step exactly, so with
        # UniformDelivery and identical init the two paths draw the same
        # quorums step for step (the oracle equivalence)
        key, k_pull, k_matk, k_push, k_gatk = jax.random.split(state.key, 5)
        eta = lr_schedule(state.t).astype(jnp.float32)

        # 1. worker pull ------------------------------------------------------
        with jax.named_scope("pull"):
            models = state.params
            if with_attack and pcfg.byz.server_attack:
                models = inject_models(models, pcfg.byz, k_matk)
            if pcfg.pull == "roundrobin":
                # synchronous variant (paper §5): each worker pulls ONE model
                # via a ring permutation over 'rep' (lowers to
                # collective-permute, O(P) vs the Median pull's O((q-1)P)),
                # validated by a distance filter against the worker's own
                # replica (the Outliers filter of Eq. 14 anchored locally; on
                # rejection the worker falls back to its own replica — a
                # conservative, honest model by definition. The Lipschitz
                # filter needs the previous gradient: carried only in the
                # faithful simulator, where memory is free).
                idx = (jnp.arange(G) + state.t + 1) % G
                pulled = jax.tree.map(lambda l: jnp.take(l, idx, axis=0),
                                      models)
                own = state.params
                d2g = None
                n2g = None
                for pl, ow in zip(jax.tree.leaves(pulled),
                                  jax.tree.leaves(own)):
                    ax = tuple(range(1, pl.ndim))
                    d = jnp.sum((pl.astype(jnp.float32)
                                 - ow.astype(jnp.float32)) ** 2, axis=ax)
                    n = jnp.sum(ow.astype(jnp.float32) ** 2, axis=ax)
                    d2g = d if d2g is None else d2g + d
                    n2g = n if n2g is None else n2g + n
                growth = ((3.0 * pcfg.T + 2.0) * (G - pcfg.f_workers)
                          / (4.0 * max(pcfg.f_workers, 1)))
                bound2 = (eta * growth) ** 2 * n2g + 1e-6
                ok = d2g <= bound2                      # [G] per-worker verdict
                pulled = jax.tree.map(
                    lambda p, o: jnp.where(
                        ok.reshape((G,) + (1,) * (p.ndim - 1)), p, o),
                    pulled, own)
            else:
                # asynchronous variant: masked Median over the delivered quorum
                pull_idx = delivery.pull_indices(k_pull, state.t)
                pull_masks = jnp.zeros((G, G), bool).at[
                    jnp.arange(G)[:, None], pull_idx].set(True)
                with views_in(bundle.cfg.act_dtype):
                    pulled = masked_pull(models, pull_masks, pcfg, mesh)
            pulled = jax.tree.map(
                lambda l: l.astype(jnp.dtype(bundle.cfg.act_dtype))
                if l.dtype == jnp.float32 else l, pulled)

        # 2. per-group worker gradients (vmap over 'rep'), accumulated over
        # grad_microbatches sequential micro-steps (bounds activation memory;
        # the batch arrives with a leading micro axis when n_micro > 1) ------
        with jax.named_scope("worker_grad"):
            gfn = jax.vmap(jax.grad(bundle.loss),
                           spmd_axis_name="rep" if mesh is not None else None)
            if pcfg.grad_microbatches > 1:
                def micro_body(acc, mb):
                    g = gfn(pulled, mb)
                    return jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32)
                        / pcfg.grad_microbatches, acc, g), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
                zeros = _constrain_like_params(zeros)
                grads, _ = jax.lax.scan(micro_body, zeros, batch)
            else:
                grads = gfn(pulled, batch)
            grads = jax.tree.map(
                lambda g: g.astype(jnp.dtype(pcfg.exchange_dtype)), grads)
            grads = _constrain_like_params(grads)
            if with_attack and pcfg.byz.worker_attack:
                grads = inject_gradients(grads, pcfg.byz, k_gatk)

        # 3. gradient rule (MDA by default) per server group over its quorum ---
        with jax.named_scope("distances"):
            push_idx = delivery.push_indices(k_push, state.t)
            d2 = agg.rules.sqdists_from_gram(tree_gram(grads, mesh))
            weights = quorum_weights(d2, push_idx, pcfg.f_workers, pcfg)
        with jax.named_scope("aggregate"):
            g_hat = aggregate_gradients(grads, weights, pcfg, mesh)

        # 4. local update (paper Eq. 2 for sgd; per-replica moments ride in
        # state.opt for stateful optimizers) -----------------------------------
        with jax.named_scope("update"):
            new_params, new_opt = optimizer.update(g_hat, state.opt,
                                                   state.params, eta)
        return ByzState(params=new_params, t=state.t + 1, key=key,
                        opt=new_opt)

    return scatter_step


def make_gather_step(pcfg: ProtocolConfig, with_attack: bool = False,
                     mesh=None, delivery=None):
    """DMC: servers exchange replicas and apply the masked ``gather_gar``
    (Median by default) every T steps."""
    G = pcfg.n_groups
    delivery = delivery or UniformDelivery(G, G, pcfg.q_workers,
                                           pcfg.q_servers)

    def gather_step(state: ByzState):
        with jax.named_scope("gather"):
            key, k_q, k_atk = jax.random.split(state.key, 3)
            idx = delivery.gather_indices(k_q, state.t)
            masks = jnp.zeros((G, G), bool).at[
                jnp.arange(G)[:, None], idx].set(True)
            models = state.params
            if with_attack and pcfg.byz.server_attack:
                models = inject_models(models, pcfg.byz, k_atk)
            new_params = masked_pull(models, masks, pcfg, mesh,
                                     rule=pcfg.gather_gar)
            new_params = jax.tree.map(lambda n, p: n.astype(p.dtype),
                                      new_params, state.params)
            return ByzState(params=new_params, t=state.t, key=key,
                            opt=state.opt)

    return gather_step


def make_train_step(bundle, pcfg: ProtocolConfig, lr_schedule,
                    with_attack: bool = False, mesh=None, delivery=None):
    """Fused step: scatter, then DMC gather iff t % T == 0 (lax.cond)."""
    delivery = delivery or UniformDelivery(
        pcfg.n_groups, pcfg.n_groups, pcfg.q_workers, pcfg.q_servers)
    scatter = make_scatter_step(bundle, pcfg, lr_schedule, with_attack, mesh,
                                delivery)
    gather = make_gather_step(pcfg, with_attack, mesh, delivery)

    def train_step(state: ByzState, batch):
        state = scatter(state, batch)
        return jax.lax.cond(state.t % pcfg.T == 0, gather, lambda s: s, state)

    return train_step


# ---------------------------------------------------------------------------
# serving-side consolidation
# ---------------------------------------------------------------------------


def consolidate(params, pcfg: ProtocolConfig, chunk_bytes: int | None = None):
    """Median-of-replicas -> single serving model (DMC applied once, full
    delivery). The serving path is vanilla DP x TP decode (DESIGN.md §5)."""
    cb = chunk_bytes or pcfg.chunk_bytes

    def med(leaf):
        def chunk_fn(c):
            return jnp.median(c.astype(jnp.float32), axis=0).astype(c.dtype)
        if (leaf.ndim >= 3 and leaf.shape[1] <= _STREAM_MAX_DIM1
                and leaf.size * leaf.dtype.itemsize > cb):
            L = leaf.shape[1]
            def body(i, acc):
                sl = jnp.squeeze(jax.lax.dynamic_slice_in_dim(leaf, i, 1, 1), 1)
                return jax.lax.dynamic_update_slice_in_dim(
                    acc, chunk_fn(sl)[None], i, axis=0)
            out0 = jax.eval_shape(chunk_fn,
                                  jax.eval_shape(lambda l: jnp.squeeze(l[:, :1], 1), leaf))
            init = jnp.zeros((L,) + out0.shape, out0.dtype)
            return jax.lax.fori_loop(0, L, body, init)
        return chunk_fn(leaf)

    return jax.tree.map(med, params)


# ---------------------------------------------------------------------------
# fused epoch engine over the protocol (repro.core.epochs scaffolding)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class _ProblemCfg:
    """Dtype carrier for paper-scale problems driven through the protocol
    step builders (the LM path passes full model-bundle configs instead)."""
    param_dtype: str = "float32"
    act_dtype: str = "float32"


@dataclass(frozen=True)
class ProblemBundle:
    """Minimal bundle adapter: wraps an ``(init_fn, loss_fn)`` problem (the
    repro.configs.paper_models factories) into the ``bundle`` interface the
    protocol step builders expect (``init``/``loss``/``cfg`` dtypes)."""
    init: Callable
    loss: Callable
    cfg: _ProblemCfg = field(default_factory=_ProblemCfg)


class ProtocolEngine(EpochRunner):
    """Fused multi-device epochs over the distributed ByzSGD protocol.

    The same scan/donation treatment ``repro.core.engine.EpochEngine`` gives
    the single-host simulator, applied to the replica-stacked (and, with a
    mesh, 'rep'-sharded) :class:`ByzState` for BOTH collective engines
    ('naive' | 'sharded'): one donated ``lax.scan`` per epoch whose body runs
    the scatter step and applies the DMC gather when the carried counter hits
    a multiple of T (``lax.cond`` — chunk lengths and run tails stay correct),
    with per-group metrics (accuracy on group 0's replica, the Lemma-4.2/4.3
    diameters) reduced on device into the scan's output buffers — ONE host
    transfer per ``run``.

    Epoch executables share the bounded semantic compile cache of
    ``repro.core.epochs`` (keyed on ProtocolConfig + loss/lr cache keys +
    delivery + mesh + metric flags), so spec sweeps over the protocol runner
    reuse compiled epochs. With the default ``UniformDelivery`` and
    ``pull="median"`` (the asynchronous schedule) the engine draws the same
    quorums as ``ByzSGDSimulator`` — the single-host engine is its oracle
    (params allclose, metrics identical on a 1-device mesh). The
    ``pull="roundrobin"`` mode is the protocol's own §5 collective
    formulation (ring permutation + distance filter); it is NOT oracle-matched
    against the simulator's sync filter variant.
    """

    def __init__(self, bundle, pcfg: ProtocolConfig, lr_schedule, *,
                 mesh=None, delivery=None, with_attack: bool = False,
                 acc_fn: Callable | None = None, eval_set: tuple | None = None,
                 track_delta: bool = False, metrics_every: int = 1,
                 rules: dict | None = None):
        if (acc_fn is None) != (eval_set is None):
            raise ValueError("acc_fn and eval_set must be given together")
        if metrics_every < 1:
            raise ValueError("metrics_every must be >= 1")
        self.bundle = bundle
        self.cfg = pcfg
        self.lr = lr_schedule
        self.mesh = mesh
        self.rules = dict(rules) if rules else None
        self.with_attack = with_attack
        self.delivery = delivery or UniformDelivery(
            pcfg.n_groups, pcfg.n_groups, pcfg.q_workers, pcfg.q_servers)
        self.acc_fn = acc_fn
        self.eval_set = eval_set
        self.track_delta = track_delta
        self.metrics_every = metrics_every
        self._epoch = self._get_or_build()

    # -- state -------------------------------------------------------------
    def init_state(self, key: jax.Array) -> ByzState:
        """Replica-stacked initial state (same PRNG chain as
        ``ByzSGDSimulator.init_state``: one split into model/run keys). With a
        mesh, the state is placed onto the per-leaf-name layouts."""
        init = make_init_fn(self.bundle, self.cfg)
        state = jax.jit(init)(key)
        if self.mesh is not None:
            shardings = state_shardings(
                jax.eval_shape(init, key), self.mesh,
                overrides=attn_overrides(self.bundle.cfg, self.mesh))
            state = jax.tree.map(jax.device_put, state, shardings)
        return state

    # -- epochs ------------------------------------------------------------
    def _flags(self):
        return (fn_cache_key(self.acc_fn), self.track_delta,
                self.metrics_every, self.with_attack,
                _agg_rules.sort_network_enabled(),
                _agg_dispatch.default_backend())

    def _cache_key(self):
        mesh_key = None if self.mesh is None else id(self.mesh)
        rules_key = (None if self.rules is None
                     else tuple(sorted(self.rules.items())))
        return ("protocol-epoch", self.cfg, fn_cache_key(self.bundle.loss),
                fn_cache_key(self.bundle.init), fn_cache_key(self.lr),
                delivery_cache_key(self.delivery), mesh_key, rules_key,
                *self._flags())

    def _instance_key(self):
        return ("protocol-epoch-inst", id(self), *self._flags())

    def _extra_args(self):
        if self.eval_set is not None:
            return self.eval_set
        return (jnp.zeros(()), jnp.zeros(()))

    def _build(self):
        pcfg = self.cfg
        T = pcfg.T
        h = pcfg.n_groups - pcfg.byz.n_byz_servers
        track_delta, acc_fn = self.track_delta, self.acc_fn
        metrics_every = self.metrics_every
        scatter = make_scatter_step(self.bundle, pcfg, self.lr,
                                    self.with_attack, self.mesh,
                                    self.delivery)
        gather = make_gather_step(pcfg, self.with_attack, self.mesh,
                                  self.delivery)

        def step_metrics(state: ByzState, delta_pre, eval_x, eval_y):
            m = {}
            if acc_fn is not None:
                def ev(_):
                    return acc_fn(jax.tree.map(lambda l: l[0], state.params),
                                  eval_x, eval_y)

                if metrics_every == 1:
                    m["acc"] = ev(None)
                else:
                    m["acc"] = lax.cond((state.t - 1) % metrics_every == 0,
                                        ev, lambda _: jnp.float32(0.0), None)
            if track_delta:
                from .simulator import (coordinatewise_diameter_sum,
                                        l2_diameter)
                m["delta_pre"] = delta_pre
                m["delta"] = coordinatewise_diameter_sum(state.params, h)
                m["l2_diam"] = l2_diameter(state.params, h)
            return m

        def epoch(state: ByzState, batches, eval_x, eval_y):
            def body(state, batch):
                state = scatter(state, batch)
                if track_delta:
                    from .simulator import coordinatewise_diameter_sum
                    delta_pre = coordinatewise_diameter_sum(state.params, h)
                else:
                    delta_pre = None
                # post-step boundary, like the async simulator: the gather
                # closes the scatter phase when t (already advanced) hits T
                state = lax.cond(state.t % T == 0, gather, lambda s: s, state)
                return state, step_metrics(state, delta_pre, eval_x, eval_y)

            return lax.scan(body, state, batches)

        if self.rules:
            # install the model's logical activation-sharding rules for the
            # whole epoch trace (loss fwd/bwd AND the in-scan eval), exactly
            # like the launch driver wraps its train step
            from ..models import sharding as shrules
            rules, inner_epoch = self.rules, epoch

            def epoch(state, batches, eval_x, eval_y):
                with shrules.sharding_rules(rules):
                    return inner_epoch(state, batches, eval_x, eval_y)

        return jax.jit(epoch, donate_argnums=(0,))


def collective_volume_bytes(pcfg: ProtocolConfig, n_params: int,
                            *, fsdp: int = 1) -> int:
    """Modeled per-device cross-'rep' collective exchange (bytes) of one
    scatter step's model/gradient payloads, HLO-verified by the compiled-
    artifact auditor (``repro.analyze``, REPRO-HLO-COLLECTIVES):

    * **pull** — the masked Median pull is an order statistic over the full
      replica stack, so it all-gathers ``[G, P]``: ``(G-1)·P·itemsize`` per
      device, for BOTH engines;
    * **push** — the ``[G_recv, G_send] x [G_send, P]`` weighted aggregation
      moves ``(G-1)·P·itemsize`` per device whichever way XLA lowers it
      (all-gather the operand stack, or partial-dot + reduce-scatter of the
      equally-sized ``[G, P]`` result — ring-model bytes are identical).

    Earlier revisions modeled the sharded engine at ``~2·P`` (a reduce-
    scatter of ONE replica's payload); auditing the compiled HLO showed
    XLA lowers both engines to the same ``(G-1)·P`` exchanges at these
    shapes — the engines differ in *temp memory* (the naive engine
    materializes the replicated stack per device; see
    ``aggregate_gradients``), not in ring-model traffic. The model covers
    the exchange primitives (``masked_pull`` + ``aggregate_gradients``);
    distance/Gram traffic for the selection weights rides on top.

    With the 'fsdp' axis lit (``fsdp`` = K > 1) each device holds 1/K of
    every replica's payload, so both exchanges ring-shift 1/K of the bytes:
    the all-gather result is the fsdp-sharded ``[G, P/K]`` stack, not the
    full ``[G, P]``. The default K=1 is the 1D model. Leaves whose dims K
    does not divide stay replicated and move full-size — the HLO audit's
    10% tolerance absorbs that remainder at repo shapes."""
    itemsize = jnp.dtype(pcfg.exchange_dtype).itemsize
    G = pcfg.n_groups
    return 2 * (G - 1) * n_params * itemsize // fsdp
