"""Grouped-query attention: RoPE, sliding windows, softcaps, KV caches.

Covers every attention variant in the assigned zoo:

* GQA with arbitrary H/KV ratio (yi 32/4, starcoder2 24/2, …)
* RoPE (configurable θ) or none (hubert uses learned conv pos — stubbed
  into the frontend embeddings)
* sliding-window masks (h2o-danube3 SWA, gemma2 local layers)
* gemma2 attention-logit softcap
* encoder (bidirectional) mode for hubert
* decode caches: linear (append-at-pos) and RING (bounded window memory —
  what makes SWA archs eligible for the 524k-token decode shape)

The full pass is q-chunked (flash-style accumulation-free streaming over
query blocks via ``lax.scan``) so the (Q, S) score matrix never exceeds
``q_chunk · S`` per head group — the memory knob for prefill_32k.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.config import AttentionConfig
from repro.models.layers import rms_norm, softcap as _softcap


def init_attention(rng: jax.Array, cfg: AttentionConfig, d_model: int
                   ) -> Dict[str, jax.Array]:
    hd = cfg.head_dim or d_model // cfg.num_heads
    ks = jax.random.split(rng, 4)
    s = d_model ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d_model, cfg.num_heads * hd), jnp.float32) * s,
        "wk": jax.random.normal(ks[1], (d_model, cfg.num_kv_heads * hd), jnp.float32) * s,
        "wv": jax.random.normal(ks[2], (d_model, cfg.num_kv_heads * hd), jnp.float32) * s,
        "wo": jax.random.normal(ks[3], (cfg.num_heads * hd, d_model), jnp.float32)
              * (cfg.num_heads * hd) ** -0.5,
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., S, n, hd), positions (..., S) → rotated x."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq      # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _qkv(params, x, cfg: AttentionConfig, positions):
    B, S, d = x.shape
    hd = cfg.head_dim or d // cfg.num_heads
    dt = x.dtype
    q = (x @ params["wq"].astype(dt)).reshape(B, S, cfg.num_heads, hd)
    k = (x @ params["wk"].astype(dt)).reshape(B, S, cfg.num_kv_heads, hd)
    v = (x @ params["wv"].astype(dt)).reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attend(q, k, v, q_pos, k_pos, *, causal: bool,
            window: Optional[int], cap: Optional[float], scale: float):
    """q (B,Q,H,hd), k/v (B,S,KV,hd), positions (Q,)/(S,); k_pos < 0 ⇒ slot
    invalid.  Returns (B,Q,H,hd)."""
    B, Q, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Q, KV, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if cap is not None:
        s = _softcap(s, cap)
    m = (k_pos >= 0)[None, :]
    if causal:
        m = m & (k_pos[None, :] <= q_pos[:, None])
    if window is not None:
        m = m & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(m[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return o.reshape(B, Q, H, hd)


def full_attention(params: Dict[str, jax.Array], x: jax.Array,
                   cfg: AttentionConfig, *, positions: jax.Array,
                   causal: bool = True, window: Optional[int] = None,
                   q_chunk: int = 512, mesh=None
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Train/prefill pass.  Returns (y, kv) — kv reused to build caches.

    §Perf note (dbrx hillclimb H1, two REFUTED variants recorded in
    EXPERIMENTS.md): pinning k/v (or the pre-qkv input) S-replicated to
    hoist the sequence-parallel gather out of the q-chunk scan made XLA
    insert per-chunk reshards (+128 GB/dev) or replicate the global
    batch (+1 TB/dev).  The baseline per-chunk staging stands; the
    winning lever is the FLASH path (H3) below."""
    B, S, d = x.shape
    q, k, v = _qkv(params, x, cfg, positions[None, :])
    hd = q.shape[-1]
    scale = hd ** -0.5
    cap = cfg.attn_softcap
    win = window if window is not None else cfg.window
    if use_flash() and S > q_chunk:
        o = _flash_path(q, k, v, positions, mesh, causal=causal, window=win,
                        cap=cap, scale=scale, q_chunk=q_chunk)
        y = o.reshape(B, S, -1).astype(x.dtype) @ params["wo"].astype(x.dtype)
        return y, {"k": k, "v": v}
    if S <= q_chunk:
        o = _attend(q, k, v, positions, positions,
                    causal=causal, window=win, cap=cap, scale=scale)
    else:
        assert S % q_chunk == 0, (S, q_chunk)
        nc = S // q_chunk
        qs = q.reshape(B, nc, q_chunk, *q.shape[2:]).transpose(1, 0, 2, 3, 4)
        ps = positions.reshape(nc, q_chunk)

        # checkpoint: otherwise scan's VJP stacks every chunk's softmax
        # residuals — the full (S, S) score tensor in f32 (flash-attention
        # recomputes scores in backward for the same reason)
        @jax.checkpoint
        def body(_, qp):
            qi, pi = qp
            return None, _attend(qi, k, v, pi, positions,
                                 causal=causal, window=win, cap=cap, scale=scale)

        _, os = lax.scan(body, None, (qs, ps))
        o = os.transpose(1, 0, 2, 3, 4).reshape(B, S, q.shape[2], hd)
    y = o.reshape(B, S, -1).astype(x.dtype) @ params["wo"].astype(x.dtype)
    return y, {"k": k, "v": v}


def use_flash() -> bool:
    """Flash-attention Pallas path toggle (§Perf H3).  On by default for
    long sequences; REPRO_FLASH=0 reverts to the chunked-jnp baseline."""
    import os
    return os.environ.get("REPRO_FLASH", "1") == "1"


def _flash_path(q, k, v, positions, mesh, *, causal, window, cap, scale,
                q_chunk):
    """Run the flash kernel, context-parallel when a mesh is present:
    q's sequence shards over `model` (each rank computes its query slice
    against the full k/v — GQA-agnostic, divides for every arch), batch
    over the data axes; k/v replicate over `model` (gathered ONCE at the
    shard_map boundary — the fix per-chunk staging couldn't achieve)."""
    from repro.kernels import ops as kops
    from repro.kernels.flash_attention import flash_attention
    B, S, H, hd = q.shape
    qh = q.transpose(0, 2, 1, 3)                   # (B, H, S, hd)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    k_pos = positions
    interpret = kops.INTERPRET
    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    if mesh is None or mesh.devices.size == 1 or S % msize or msize <= 1:
        o = flash_attention(qh, kh, vh, positions, k_pos, scale, causal,
                            window, cap, min(q_chunk, 512), interpret)
        return o.transpose(0, 2, 1, 3)
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))

    def local(qh, kh, vh, qp, kp):
        return flash_attention(qh, kh, vh, qp, kp, scale, causal, window,
                               cap, min(q_chunk, 512), interpret)

    o = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, "model", None), P(dp, None, None, None),
                  P(dp, None, None, None), P("model"), P(None)),
        out_specs=P(dp, None, "model", None), check_vma=False,
    )(qh, kh, vh, positions, k_pos)
    return o.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------

def init_cache(cfg: AttentionConfig, batch: int, cache_len: int, d_model: int,
               dtype) -> Dict[str, jax.Array]:
    hd = cfg.head_dim or d_model // cfg.num_heads
    shape = (batch, cache_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def fill_cache(cache: Dict[str, jax.Array], kv: Dict[str, jax.Array],
               *, ring: bool) -> Dict[str, jax.Array]:
    """Write a prefill's (B, S, KV, hd) keys/values into the cache.

    Ring caches store position p at slot p % W, so decode's slot
    arithmetic continues seamlessly after an over-long prefill."""
    S = kv["k"].shape[1]
    W = cache["k"].shape[1]
    if ring and S >= W:
        # keep the last W positions, permuted so position p sits at p % W
        order = (jnp.arange(W) - S) % W        # slot s ← prompt row (s-S)%W
        kv = {n: kv[n][:, S - W:][:, order] for n in ("k", "v")}
        k = kv["k"].astype(cache["k"].dtype)
        v = kv["v"].astype(cache["v"].dtype)
        return {"k": k, "v": v, "pos": jnp.asarray(S, jnp.int32)}
    k = lax.dynamic_update_slice(cache["k"], kv["k"].astype(cache["k"].dtype),
                                 (0, 0, 0, 0))
    v = lax.dynamic_update_slice(cache["v"], kv["v"].astype(cache["v"].dtype),
                                 (0, 0, 0, 0))
    return {"k": k, "v": v, "pos": jnp.asarray(S, jnp.int32)}


def decode_attention(params: Dict[str, jax.Array], x: jax.Array,
                     cache: Dict[str, jax.Array], cfg: AttentionConfig, *,
                     ring: bool = False, window: Optional[int] = None,
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode.  x (B, 1, d).  ``ring=True`` uses the bounded
    ring buffer (cache_len == window) — O(W) memory at any sequence length."""
    B, one, d = x.shape
    assert one == 1
    pos = cache["pos"]
    q, k_new, v_new = _qkv(params, x, cfg, pos[None, None])
    W = cache["k"].shape[1]
    slot = (pos % W) if ring else pos
    k = lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                 (0, slot, 0, 0))
    v = lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                 (0, slot, 0, 0))
    idx = jnp.arange(W, dtype=jnp.int32)
    if ring:
        # slot s holds position pos - ((pos - s) mod W); negatives invalid
        k_pos = pos - ((pos - idx) % W)
        k_pos = jnp.where(k_pos >= 0, k_pos, -1)
    else:
        k_pos = jnp.where(idx <= pos, idx, -1)
    win = window if window is not None else cfg.window
    o = _attend(q, k, v, pos[None], k_pos, causal=True, window=win,
                cap=cfg.attn_softcap, scale=q.shape[-1] ** -0.5)
    y = o.reshape(B, 1, -1).astype(x.dtype) @ params["wo"].astype(x.dtype)
    return y, {"k": k, "v": v, "pos": pos + 1}
