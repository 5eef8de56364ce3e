"""Operations and bytes that the work needs, from shapes alone.

``model_flops_per_token``: the forward and backward passes of one token of
training, counted as 6 x (parameters in matrix products, the tied output
head included, the embedding lookup not) plus causal attention's own
products (6 L H hd S). Recomputation under
remat and the ByzSGD exchange are not model work and are not counted.

``flash_call``: the least work of one call of a flash-attention kernel: the
causal half of its products and one read or write of each operand at the
sizes the algorithm needs (k and v at the key/value head count, one f32 per
row of log-sum-exp and delta).
"""
from __future__ import annotations


def _dense_matmul_params(c: dict) -> int:
    D, F = c["hidden_size"], c["intermediate_size"]
    H, kvH = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or D // H
    per_layer = D * H * hd + 2 * D * kvH * hd + H * hd * D + 3 * D * F
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * D


def model_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward operations per trained token at sequence ``seq``."""
    L = c["num_hidden_layers"]
    if c["reference"] == "dense":
        H = c["num_attention_heads"]
        hd = c.get("head_dim") or c["hidden_size"] // H
        # causal attention: QK^T and PV over half the keys on average,
        # 2 ops per multiply-add, x3 for forward and backward
        return 6.0 * _dense_matmul_params(c) + 6.0 * L * H * hd * seq
    raise ValueError(f"no FLOP count for reference {c['reference']!r}")


FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call(kind: str, heads: int, seq: int, hd: int, kv_ratio: int,
               causal: bool = True) -> tuple[float, float]:
    """(operations, bytes) one call of a flash kernel needs.

    ``heads``: batch x query heads in the call; ``kv_ratio``: query heads
    per key/value head. Matrix products: forward S=QK^T and PV; dq
    recomputes S, then dP and dQ; dkv recomputes S, then dV, dP and dK.
    Each is 2 x seq x seq x hd per head, halved for the causal mask."""
    frac = 0.5 if causal else 1.0
    flops = FLASH_MATMULS[kind] * 2.0 * seq * seq * hd * heads * frac
    row = seq * hd * 2                      # one bf16 [seq, hd] operand
    kv = 2 * row / kv_ratio                 # k and v at kv-head count
    stats = seq * 4                         # one f32 per row
    per_head = {
        "fwd": row + kv + row + stats,                        # q,k,v -> o,lse
        "dq": row + kv + row + 2 * stats + row,               # q,k,v,do,lse,
                                                              # delta -> dq
        "dkv": row + row + 2 * stats + kv + kv,               # q,do,lse,delta,
                                                              # k,v -> dk,dv
    }[kind]
    return flops, per_head * heads
