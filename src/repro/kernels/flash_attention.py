"""Flash attention (fwd + bwd) as Pallas TPU kernels — beyond-paper §Perf.

The dry-run roofline shows every train/prefill pair memory-bound, with
the (Q, S) attention score tensors' HBM round-trips the single largest
traffic source (dbrx train_4k: ~4 TB/dev/step).  HetuMoE doesn't touch
attention ("expert networks exist in common models"); we do — the
standard online-softmax tiling keeps scores VMEM-resident.

Kernel layout (head-major):
  q (B, H, Sq, d), k/v (B, KV, Sk, d); GQA handled by the k/v BlockSpec
  index map ``h → h // (H // KV)`` — no materialized head expansion.
  Grid (B, H, nq, nk), sequential in nk: online-softmax accumulators
  (o_acc f32, running max m, sum l) live in VMEM scratch across the nk
  steps; the output block is written at the last step.  Causal + window
  masks come from explicit q/k position vectors (prefetch-style inputs),
  so SEQUENCE-SHARDED q (context parallelism) works: each model-rank
  computes its q slice against the full k/v.

Backward: standard two-kernel flash bwd (dq over (nq, nk) grid; dk/dv
over (nk, G, nq) accumulating across the query heads of each kv head),
using the saved per-row logsumexp and the precomputed Δ = rowsum(dO∘O).
Supports the gemma2 attn-logit softcap (tanh recomputed blockwise, its
derivative applied in ds).

Validated in interpret mode against the pure-jnp oracle (ref.py) over
shape/dtype/mask sweeps; see tests/test_flash_attention.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG = -1e30


def _mask(q_pos, k_pos, causal, window):
    """q_pos (bq, 1), k_pos (1, bk) → (bq, bk) bool."""
    m = k_pos >= 0
    if causal:
        m = m & (k_pos <= q_pos)
    if window is not None:
        m = m & (k_pos > q_pos - window)
    return m


def _nt(a, b):
    """a (m, d) · b (n, d)ᵀ → (m, n) f32, no explicit transpose."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _tn(a, b):
    """a (m, n)ᵀ · b (m, d) → (n, d) f32, no explicit transpose."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _fwd_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                o_acc, m_acc, l_acc, *, scale, causal, window, cap, nk):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG)
        l_acc[...] = jnp.zeros_like(l_acc)

    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]       # (bq|bk, d)
    s = _nt(q, k) * scale                                 # (bq, bk) f32
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    s = jnp.where(_mask(qp_ref[...], kp_ref[...], causal, window), s, NEG)
    m_prev = m_acc[...]                                   # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_acc[...] = l_acc[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_acc[...] = o_acc[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_acc[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = l_acc[...]
        o_ref[0, 0] = (o_acc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_acc[...] + jnp.log(l)


def _probs_and_dscores(qp_ref, kp_ref, q, k, v, do, lse, delta, *, scale,
                       causal, window, cap):
    """Recompute the (bq, bk) probabilities of one block and dL/ds."""
    s = _nt(q, k) * scale
    if cap is not None:
        t = jnp.tanh(s / cap)
        s = cap * t
    msk = _mask(qp_ref[...], kp_ref[...], causal, window)
    p = jnp.exp(jnp.where(msk, s, NEG) - lse)             # lse (bq, 1)
    ds = p * (_nt(do, v) - delta)
    if cap is not None:
        ds = ds * (1.0 - t * t)
    return p, jnp.where(msk, ds, 0.0)


def _bwd_dq_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc, *, scale, causal, window,
                   cap, nk):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k = k_ref[0, 0]
    _, ds = _probs_and_dscores(
        qp_ref, kp_ref, q_ref[0, 0], k, v_ref[0, 0], do_ref[0, 0],
        lse_ref[0, 0], delta_ref[0, 0], scale=scale, causal=causal,
        window=window, cap=cap)
    dq_acc[...] += jnp.dot(ds.astype(k.dtype), k,
                           preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                    causal, window, cap, ng, nq):
    g = pl.program_id(3)
    iq = pl.program_id(4)

    @pl.when((g == 0) & (iq == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, do = q_ref[0, 0], do_ref[0, 0]
    p, ds = _probs_and_dscores(
        qp_ref, kp_ref, q, k_ref[0, 0], v_ref[0, 0], do, lse_ref[0, 0],
        delta_ref[0, 0], scale=scale, causal=causal, window=window, cap=cap)
    dv_acc[...] += _tn(p.astype(do.dtype), do)
    dk_acc[...] += _tn(ds.astype(q.dtype), q) * scale

    @pl.when((g == ng - 1) & (iq == nq - 1))
    def _done():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _blocks(S, want):
    b = min(want, S)
    while S % b:
        b -= 1
    return b


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def flash_attention(q, k, v, q_pos, k_pos, scale: float, causal: bool,
                    window: Optional[int], cap: Optional[float],
                    block_q: int = 512, interpret: bool = True):
    """q (B,H,Sq,d), k/v (B,KV,Sk,d), positions i32 (Sq,)/(Sk,) →
    o (B,H,Sq,d).  k_pos < 0 marks invalid slots."""
    o, _ = _flash_fwd(q, k, v, q_pos, k_pos, scale, causal, window, cap,
                      block_q, interpret)
    return o


def _flash_fwd(q, k, v, q_pos, k_pos, scale, causal, window, cap,
               block_q, interpret):
    B, H, Sq, d = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq = _blocks(Sq, block_q)
    bk = _blocks(Sk, block_q)
    nq, nk = Sq // bq, Sk // bk
    grid = (B, H, nq, nk)
    kv_map = lambda b, h, iq, ik: (b, h // G, ik, 0)
    row_map = lambda b, h, iq, ik: (b, h, iq, 0)
    o, lse = _scoped(pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          window=window, cap=cap, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, 1), lambda b, h, iq, ik: (iq, 0)),
            pl.BlockSpec((1, bk), lambda b, h, iq, ik: (0, ik)),
            pl.BlockSpec((1, 1, bq, d), row_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bq, d), row_map),
            pl.BlockSpec((1, 1, bq, 1), row_map),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu_scratch((bq, d)), pltpu_scratch((bq, 1)),
            pltpu_scratch((bq, 1)),
        ],
        interpret=interpret,
    ), *_pos2d(q_pos, k_pos), q, k, v)
    return o, lse


def _pos2d(q_pos, k_pos):
    """Positions as a (Sq, 1) column and a (1, Sk) row: Mosaic tiles the
    last two dims of every block, so 1-D position blocks do not lower."""
    return (q_pos.astype(jnp.int32).reshape(-1, 1),
            k_pos.astype(jnp.int32).reshape(1, -1))


def pltpu_scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _scoped(fn, *operands):
    """Trace a pallas_call under the "pallas_vmem" name scope: the HLO
    analyzer treats those ops as VMEM-resident (only block DMAs count as
    HBM traffic) — matching what the Mosaic kernel does on real TPU."""
    with jax.named_scope("pallas_vmem"):
        return fn(*operands)


def _fa_fwd(q, k, v, q_pos, k_pos, scale, causal, window, cap, block_q,
            interpret):
    o, lse = _flash_fwd(q, k, v, q_pos, k_pos, scale, causal, window, cap,
                        block_q, interpret)
    return o, (q, k, v, q_pos, k_pos, o, lse)


def _fa_bwd(scale, causal, window, cap, block_q, interpret, res, do):
    q, k, v, q_pos, k_pos, o, lse = res
    B, H, Sq, d = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq = _blocks(Sq, block_q)
    bk = _blocks(Sk, block_q)
    nq, nk = Sq // bq, Sk // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)               # (B,H,Sq,1)
    pos = _pos2d(q_pos, k_pos)
    kv_map4 = lambda b, h, iq, ik: (b, h // G, ik, 0)
    row_map4 = lambda b, h, iq, ik: (b, h, iq, 0)
    dq = _scoped(pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, cap=cap, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((bq, 1), lambda b, h, iq, ik: (iq, 0)),
            pl.BlockSpec((1, bk), lambda b, h, iq, ik: (0, ik)),
            pl.BlockSpec((1, 1, bq, d), row_map4),
            pl.BlockSpec((1, 1, bk, d), kv_map4),
            pl.BlockSpec((1, 1, bk, d), kv_map4),
            pl.BlockSpec((1, 1, bq, d), row_map4),
            pl.BlockSpec((1, 1, bq, 1), row_map4),
            pl.BlockSpec((1, 1, bq, 1), row_map4),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), row_map4),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu_scratch((bq, d))],
        interpret=interpret,
    ), *pos, q, k, v, do, lse, delta)

    # dk/dv: grid over kv heads and blocks; accumulate across the G query
    # heads of this kv head and all q blocks
    def hmap(b, kv, ik, g, iq):
        return (b, kv * G + g, iq, 0)

    kvmap5 = lambda b, kv, ik, g, iq: (b, kv, ik, 0)

    dk, dv = _scoped(pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, cap=cap, ng=G, nq=nq),
        grid=(B, KV, nk, G, nq),
        in_specs=[
            pl.BlockSpec((bq, 1), lambda b, kv, ik, g, iq: (iq, 0)),
            pl.BlockSpec((1, bk), lambda b, kv, ik, g, iq: (0, ik)),
            pl.BlockSpec((1, 1, bq, d), hmap),
            pl.BlockSpec((1, 1, bk, d), kvmap5),
            pl.BlockSpec((1, 1, bk, d), kvmap5),
            pl.BlockSpec((1, 1, bq, d), hmap),
            pl.BlockSpec((1, 1, bq, 1), hmap),
            pl.BlockSpec((1, 1, bq, 1), hmap),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bk, d), kvmap5),
            pl.BlockSpec((1, 1, bk, d), kvmap5),
        ),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        scratch_shapes=[pltpu_scratch((bk, d)), pltpu_scratch((bk, d))],
        interpret=interpret,
    ), *pos, q, k, v, do, lse, delta)
    return dq, dk, dv, None, None


flash_attention.defvjp(_fa_fwd, _fa_bwd)
