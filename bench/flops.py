"""Operations and bytes the served model and the device scan need, counted
from their shapes.

Only the work the requests need is counted: a prefill's real (unpadded)
prompt tokens at their real causal context, and a decode token at its real
context.  Padding and idle slots that the engine computes anyway are waste,
which a utilisation against these counts shows.
"""
from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    """Weights a token multiplies by in one dense GQA + SwiGLU layer."""
    d, h, kv, dh, ff = (m["hidden_size"], m["num_attention_heads"],
                        m["num_key_value_heads"], m["head_dim"],
                        m["intermediate_size"])
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * ff


def prefill_flops(m: dict, n: int) -> float:
    """One prompt of ``n`` real tokens: every token through every layer,
    causal attention over its own prefix, logits for the last token."""
    L, h, dh = m["num_hidden_layers"], m["num_attention_heads"], m["head_dim"]
    dense = 2.0 * n * L * layer_matmul_params(m)
    attn = 4.0 * L * h * dh * n * (n + 1) / 2.0
    head = 2.0 * m["hidden_size"] * m["vocab_size"]
    return dense + attn + head


def decode_flops(m: dict, context: int) -> float:
    """One decoded token attending to ``context`` positions."""
    L, h, dh = m["num_hidden_layers"], m["num_attention_heads"], m["head_dim"]
    return (2.0 * L * layer_matmul_params(m) + 4.0 * L * h * dh * context
            + 2.0 * m["hidden_size"] * m["vocab_size"])


def scan_cost(G: int, k: int, rows: int, qb: int, dim: int, n_slots: int,
              itemsize: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one call of the device IVF scan over ``G``
    groups of ``qb`` query rows, whose clusters hold ``rows`` valid rows in
    all.  Bytes: the valid rows of each group's slab tile at the slab's
    item size, the query groups (float32), the distances and indices out
    (float32, int32), the group-to-slot table and the slab's valid counts.
    FLOPs: the distance matmul, 2 per query row, valid row and dimension."""
    fl = 2.0 * qb * rows * dim
    by = (rows * dim * itemsize + G * qb * dim * 4 + G * qb * k * 8
          + G * 4 + n_slots * 4)
    return fl, float(by)
