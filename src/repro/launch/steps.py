"""Activation sharding rules: logical name -> NamedSharding for the
model-internal ``shard`` constraints (``models/sharding.py``).

``train_rules`` serves the ByzSGD train mesh ('rep', 'fsdp', 'model') — the
benchmark program, ``repro.exp``'s protocol runner and launch/train.py install
it; ``serve_rules`` the flat ('data', 'model') serving mesh of launch/serve.py.
"""
from __future__ import annotations

from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# activation sharding rules for the model-internal constraints
# ---------------------------------------------------------------------------

def train_rules(bmesh, cfg):
    """Logical-name -> NamedSharding for the ByzSGD train mesh. The leading
    vmap (worker) axis prepends a 'rep' dim to every activation."""
    M = dict(zip(bmesh.axis_names, bmesh.devices.shape))["model"]
    r = {}
    def ns(*spec):
        return NamedSharding(bmesh, P(*spec))
    # NOTE: these apply INSIDE the per-worker vmap (spmd_axis_name='rep'
    # prepends the replica axis automatically), so specs are rank-matched to
    # the unbatched activations.
    # The residual stream shards its FEATURE dim over 'model' (d_model is
    # divisible by 16 for all 10 archs): the per-layer remat-saved scan
    # carries shrink 16x, and the qkv/ffn input projections contract the
    # sharded dim (partial matmul + reduce) without layout churn.
    r["act_btd"] = ns("fsdp", None, "model")
    r["logits"] = ns("fsdp", None, "model")
    if cfg.n_heads % M == 0:
        r["act_heads"] = ns("fsdp", None, "model", None)
    if cfg.n_kv_heads % M == 0:
        r["act_kv_heads"] = ns("fsdp", None, "model", None)
    if cfg.n_experts and cfg.d_ff % M == 0:
        # TP-within-expert: F over 'model', matching the replica-state COL/ROW
        # layout; dispatch activations take D over 'fsdp' so the e,c,d x e,d,f
        # contraction is shard-aligned on BOTH sides (mismatch here made XLA
        # hoist full-stack expert-weight gathers: 150+ GiB on qwen3).
        r["expert_w_in"] = ns(None, "fsdp", "model")
        r["expert_w_out"] = ns(None, "model", "fsdp")
        r["expert_tokens"] = ns(None, None, "fsdp")
    r["kv_cache"] = ns("fsdp", None, "model", None, None)
    return r


def serve_rules(smesh, cfg):
    M = dict(zip(smesh.axis_names, smesh.devices.shape))["model"]
    def ns(*spec):
        return NamedSharding(smesh, P(*spec))
    r = {}
    r["act_btd"] = ns("data", None, None)
    r["logits"] = ns("data", None, "model")
    if cfg.n_heads % M == 0:
        r["act_heads"] = ns("data", None, "model", None)
    if cfg.n_kv_heads % M == 0:
        r["act_kv_heads"] = ns("data", None, "model", None)
    if cfg.n_experts and cfg.d_ff % M == 0:
        r["expert_w_in"] = ns(None, None, "model")
        r["expert_w_out"] = ns(None, "model", None)
        r["expert_tokens"] = ns(None, "data", None)  # capacity over 'data'
    r["kv_cache"] = ns("data", None, "model", None, None)
    return r
