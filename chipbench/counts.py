"""Operations and bytes the model's work needs, from its shapes.

These count the work, whatever implements it: no recomputation, no
capacity padding, each token through its routed experts only, and the
causal half of the attention scores.  The input embedding is a gather and
does no matmul, so it adds no operations.  A training step is the forward
pass and a backward pass of twice its operations.
"""
from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2
F32 = 4


def _dims(m: Dict):
    return (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["d_ff_expert"], m["num_experts"], m["experts_per_token"],
            m["vocab_size"], m["num_layers"])


def attention_fwd_flops(m: Dict, batch: int, seq: int) -> float:
    """QK^T and PV of every layer, causal: each query sees ``seq / 2``
    keys on average."""
    d, H, KV, hd, f, E, K, V, L = _dims(m)
    return L * 4.0 * batch * seq * (seq / 2) * H * hd


def step_model_flops(m: Dict, batch: int, seq: int) -> float:
    """Model operations of one training step."""
    d, H, KV, hd, f, E, K, V, L = _dims(m)
    per_token = L * 2.0 * (2 * d * H * hd + 2 * d * KV * hd    # q, o; k, v
                           + d * E                              # router
                           + K * 2 * d * f)                     # experts
    per_token += 2.0 * d * V                                    # head
    return 3.0 * (batch * seq * per_token + attention_fwd_flops(m, batch, seq))


def flash_attention_work(m: Dict, batch: int, seq: int) -> Tuple[float, float]:
    """(operations, bytes) of the attention kernels of one step, forward
    and backward.  Bytes: the forward reads q, k, v and writes o and the
    row statistics; the backward reads q, k, v, o, dO and two row
    statistics and writes dq, dk, dv."""
    d, H, KV, hd, f, E, K, V, L = _dims(m)
    flops = 3.0 * attention_fwd_flops(m, batch, seq)
    row = batch * H * seq * hd * BF16
    stat = batch * H * seq * F32
    return flops, L * ((4 * row + stat) + (8 * row + 2 * stat))


def grouped_matmul_work(m: Dict, batch: int, seq: int,
                        chips: int) -> Tuple[float, float]:
    """(operations, bytes) per chip of the expert matmuls of one step:
    forward (up, out), input gradients and weight gradients, over the
    ``batch * seq * experts_per_token / chips`` rows a chip holds on
    average and its ``num_experts / chips`` experts' weights, bf16."""
    d, H, KV, hd, f, E, K, V, L = _dims(m)
    rows = batch * seq * K / chips
    e_local = E / chips
    flops = L * 6 * 2.0 * rows * d * f
    mm = BF16 * (rows * d + e_local * d * f + rows * f)   # one matmul
    return flops, L * 6 * mm
