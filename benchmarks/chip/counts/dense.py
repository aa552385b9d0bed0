"""Operations and bytes that the dense family's algorithm needs, from
shapes.  A multiply-add is two operations.  Work the program does beyond
what the algorithm needs (recomputation under remat, masked attention
scores past the position) is not counted.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def matmul_params(c: dict) -> int:
    """Weights that take part in a matrix product per token: the blocks'
    projections and the head (the embedding is a lookup)."""
    D, H, KV, hd, F = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                       c["head_dim"], c["d_ff"])
    per_layer = D * H * hd * 2 + D * KV * hd * 2 + 3 * D * F
    return c["n_layers"] * per_layer + D * c["vocab"]


def attn_flops(c: dict, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys, all
    layers."""
    return c["n_layers"] * 2 * 2 * c["n_heads"] * c["head_dim"] * keys


def train_flops(c: dict, batch: int, seq: int) -> int:
    """Forward and backward (three times the forward) of one step under a
    causal mask: query i attends to i + 1 keys."""
    fwd = 2 * matmul_params(c) * batch * seq \
        + batch * attn_flops(c, 1) * seq * (seq + 1) // 2
    return 3 * fwd


def decode_flops(c: dict, batch: int, pos: int) -> int:
    """One decode step of ``batch`` sequences at position ``pos`` (the
    query attends to pos + 1 keys)."""
    return batch * (2 * matmul_params(c) + attn_flops(c, pos + 1))


def decode_bytes(c: dict, batch: int, pos: int) -> int:
    """HBM bytes one decode step needs: every projection and head weight,
    the ``batch`` embedding rows, and the K/V cache up to ``pos`` read
    plus one position written."""
    w = BYTES[c["dtype"]]
    kv = c["n_layers"] * batch * 2 * c["n_kv_heads"] * c["head_dim"] * w
    return (matmul_params(c) + 2 * c["n_layers"] * c["d_model"]
            + c["d_model"]) * w + batch * c["d_model"] * w \
        + kv * (pos + 1) + kv
