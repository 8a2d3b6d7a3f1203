"""Operation and byte counts from shapes: the yardstick's arithmetic.

All counts take a configuration's ``model`` block (the keys of
``chipbench/configs/<config>.json``).  A multiply-add is two operations.
"""
from __future__ import annotations


def _attn_params(m: dict) -> int:
    h, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return h * q + 2 * h * kv + q * h


def active_params(m: dict) -> int:
    """Parameters a token passes through: attention projections, router,
    its top-k experts (every tensor-parallel shard of each: the full expert
    width) and the LM head.  The embedding lookup does no arithmetic and is
    not counted."""
    h = m["hidden_size"]
    expert = 3 * h * m["moe_intermediate_size"]
    layer = (_attn_params(m) + h * m["num_experts"]
             + m["num_experts_per_tok"] * expert)
    return m["num_hidden_layers"] * layer + h * m["vocab_size"]


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """PaLM's convention: 6 * N_active + 12 * L * (heads * head_dim) * T.
    Recomputation is not counted."""
    attn = 12 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * m["head_dim"] * seq_len
    return 6.0 * active_params(m) + attn


def grouped_ffn_fwd(m: dict, rows: float, slot_weights: float, etp: int = 1,
                    itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the grouped SwiGLU expert FFN's forward over
    ``rows`` routed rows, each through one expert shard of width F / etp
    (three matmuls of H x F/etp), holding ``slot_weights`` expert weight
    elements (read once).  Bytes are the weights plus the rows in and out.
    Padding rows of the kernel's buffer are not useful work and are not
    counted."""
    h, f = m["hidden_size"], m["moe_intermediate_size"] // etp
    ops = 6.0 * rows * h * f
    byts = itemsize * (slot_weights + 2.0 * rows * h)
    return ops, byts


def grouped_ffn_bwd(m: dict, rows: float, slot_weights: float, etp: int = 1,
                    itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of its backward: the input and the weight
    gradients of the three matmuls, twice the forward's operations.  Bytes:
    the weights read and their gradients written, the rows and their output
    gradients read, the input gradients written."""
    h, f = m["hidden_size"], m["moe_intermediate_size"] // etp
    ops = 12.0 * rows * h * f
    byts = itemsize * (2.0 * slot_weights + 3.0 * rows * h)
    return ops, byts


def least_time(ops: float, byts: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_ops = ops / peak["bf16_flops"]
    t_mem = byts / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
