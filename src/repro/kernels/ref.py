"""Pure-jnp oracles for every Pallas kernel (allclose-asserted in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ref_topk_gate(logits: jax.Array, k: int):
    """Oracle for kernels.topk_gate.fused_topk_gate."""
    logits = logits.astype(jnp.float32)
    rowmax = jnp.max(logits, axis=-1, keepdims=True)
    sumexp = jnp.sum(jnp.exp(logits - rowmax), axis=-1, keepdims=True)
    vals, idx = jax.lax.top_k(logits, k)
    return vals, idx.astype(jnp.int32), rowmax, sumexp


def ref_gather_rows(src: jax.Array, idx: jax.Array):
    """Oracle for kernels.layout_transform.gather_rows."""
    safe = jnp.maximum(idx, 0)
    out = src[safe]
    return jnp.where((idx >= 0)[:, None], out, 0)


def ref_flash_attention(q, k, v, q_pos, k_pos, scale, causal, window, cap):
    """Oracle for kernels.flash_attention.flash_attention: plain f32
    softmax attention over q (B,H,Sq,d), k/v (B,KV,Sk,d)."""
    G = q.shape[1] // k.shape[1]
    kk = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    vv = jnp.repeat(v, G, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    m = (k_pos >= 0)[None, :]
    if causal:
        m = m & (k_pos[None, :] <= q_pos[:, None])
    if window is not None:
        m = m & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv).astype(q.dtype)
