"""Operations that one trained token of RWKV-6 "Finch" needs, from the
configuration's shapes alone (``configs/<name>.json`` with
``reference: rwkv6``).

Forward and backward: 6 x the parameters in matrix products (the five
time-mix projections, the ddlerp and decay LoRAs, the three channel-mix
matrices, the untied head; the embedding lookup, the mixes, norms, w0 and u
not), plus the WKV recurrence's own products, 12 L H K^2: per head and
token the state update k^T v and the read-out r S, K^2 multiply-adds each
(4 K^2 operations forward), x3 for forward and backward. Recomputation under
remat and the ByzSGD exchange are not model work and are not counted.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    D, F, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    A = c["attention_hidden_size"]
    R = c["assumed"]["time_mix_extra_dim"]
    Rd = c["assumed"]["time_decay_extra_dim"]
    time_mix = 4 * D * A + A * D + 2 * 5 * D * R + D * Rd + Rd * A
    channel_mix = 2 * D * F + D * D
    return c["num_hidden_layers"] * (time_mix + channel_mix) + V * D


def model_flops_per_token(c: dict) -> float:
    """Forward + backward operations per trained token."""
    K = c["head_size"]
    H = c["attention_hidden_size"] // K
    return (6.0 * matmul_params(c)
            + 12.0 * c["num_hidden_layers"] * H * K * K)
