"""Model assembly: ModelConfig → init / full-pass / prefill / decode.

One code path serves all 10 assigned architectures.  The layer stack is a
``lax.scan`` over SUPER-BLOCKS (one period of ``cfg.block_pattern``), so
the HLO is O(period), not O(num_layers) — essential for compile time on
the 512-device dry-run and the standard production pattern for
homogeneous stacks.

Block kinds (see ModelConfig.block_pattern):
  attn / local / global   GQA attention (+ window / softcap variants) + MLP
  dense                   same as attn (name used in MoE interleaves)
  moe                     attention + HetuMoE FFN (core/moe) [+ shared MLP]
  mamba                   Mamba-2 block
  mamba_sa                Mamba-2 block + zamba2-style SHARED attention
                          block (one param set for all occurrences,
                          per-occurrence LoRA on its input)
  rwkv                    RWKV-6 time-mix + channel-mix

Sharding: the model runs under jit/SPMD; activations get
``with_sharding_constraint`` hints at block boundaries (batch →
data axes, ffn/heads → model).  The MoE block is the explicit-collective
island (shard_map) per the paper.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import moe as moe_lib
from repro.core.config import ModelConfig
from repro.models import attention as attn_lib
from repro.models import layers, mamba2, rwkv6

LORA_R = 16   # zamba2 shared-block per-occurrence adapter rank


# ---------------------------------------------------------------------------
# sharding hints
# ---------------------------------------------------------------------------

def _dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def use_expert_tp() -> bool:
    """Expert-TP decode toggle (§Perf, llama4/dbrx decode hillclimb).
    REPRO_EXPERT_TP=0 reverts to ZeRO-3 gathered expert weights."""
    import os
    return os.environ.get("REPRO_EXPERT_TP", "1") == "1"


def decode_expert_tp_axis(mesh) -> Optional[str]:
    """The expert-TP axis the decode path shards the expert f dim over,
    or None.  One decision point for the MoE decode block AND the
    serving step-builder (``serving/engine.py``), so both agree on the
    decode-time collective layout — composes with ``dispatch="grouped"``
    (the ragged-aware TP gather, PR 4), which is the supported serving
    configuration for the tiny ragged decode batches."""
    if not use_expert_tp() or mesh is None:
        return None
    if "data" in mesh.axis_names:
        return "data"
    import warnings
    warnings.warn(
        f"expert TP requested (REPRO_EXPERT_TP) but mesh "
        f"{mesh.axis_names} has no 'data' axis — decoding "
        f"without expert tensor parallelism")
    return None


def shard_act(x: jax.Array, mesh, kind: str = "blk") -> jax.Array:
    """Activation sharding hint.  kind: blk (B,S,d) | logits (B,S,V).

    Block-boundary activations are SEQUENCE-PARALLEL (S over model) when
    the sequence divides the axis — Megatron-SP — which divides saved-
    for-backward activation memory by the model-axis size; XLA inserts
    the all-gather before attention where the full sequence is needed.
    """
    if mesh is None or mesh.devices.size == 1:
        return x
    dp = _dp_axes(mesh)
    msize = mesh.shape.get("model", 1)
    if kind == "logits":
        vdim = "model" if x.shape[-1] % msize == 0 else None
        spec = P(dp, None, vdim)
    else:
        sdim = "model" if (x.ndim == 3 and x.shape[1] % msize == 0
                           and x.shape[1] > 1) else None
        spec = P(dp, sdim, None)
    return lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(rng: jax.Array, kind: str, cfg: ModelConfig) -> Dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 6)
    if kind in ("attn", "local", "global", "dense"):
        return {"ln1": layers.init_norm(d),
                "attn": attn_lib.init_attention(ks[0], cfg.attention, d),
                "ln2": layers.init_norm(d),
                "mlp": layers.init_mlp(ks[1], d, f, cfg.act)}
    if kind == "moe":
        p = {"ln1": layers.init_norm(d),
             "attn": attn_lib.init_attention(ks[0], cfg.attention, d),
             "ln2": layers.init_norm(d),
             "moe": moe_lib.init_moe_params(
                 ks[1], cfg.moe, d, cfg.moe.d_ff_expert or f,
                 cfg.moe.num_experts, act=cfg.act, dtype=jnp.float32)}
        if cfg.moe.num_shared_experts:
            p["shared_mlp"] = layers.init_mlp(
                ks[2], d, (cfg.moe.d_ff_expert or f) * cfg.moe.num_shared_experts,
                cfg.act)
        return p
    if kind == "mamba":
        return {"ln1": layers.init_norm(d),
                "mamba": mamba2.init_mamba_block(ks[0], cfg.ssm, d)}
    if kind == "mamba_sa":
        return {"ln1": layers.init_norm(d),
                "mamba": mamba2.init_mamba_block(ks[0], cfg.ssm, d),
                "sa_ln": layers.init_norm(d),
                "sa_lora_a": jax.random.normal(ks[1], (d, LORA_R), jnp.float32) * d ** -0.5,
                "sa_lora_b": jnp.zeros((LORA_R, d), jnp.float32)}
    if kind == "rwkv":
        return {"ln1": layers.init_norm(d),
                "rwkv": rwkv6.init_rwkv_block(ks[0], cfg.rwkv, d),
                "ln2": layers.init_norm(d),
                "mlp": layers.init_mlp(ks[1], d, f, cfg.act)}
    raise ValueError(kind)


def init_model(rng: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    nsb = cfg.num_super_blocks
    k_embed, k_blocks, k_head, k_shared = jax.random.split(rng, 4)
    # stacked per-kind block params: init one per super-block, stack leaves
    block_keys = jax.random.split(k_blocks, nsb)

    def one_super(k):
        kk = jax.random.split(k, len(cfg.block_pattern))
        return tuple(_init_block(kk[j], kind, cfg)
                     for j, kind in enumerate(cfg.block_pattern))

    blocks = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[one_super(k) for k in block_keys])
    params: Dict[str, Any] = {"blocks": blocks,
                              "final_norm": layers.init_norm(cfg.d_model)}
    if cfg.frontend is None:
        params["embed"] = layers.init_embedding(k_embed, cfg.vocab_size, cfg.d_model)
    if not cfg.tie_embeddings or cfg.frontend is not None:
        params["lm_head"] = (jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab_size), jnp.float32)
            * cfg.d_model ** -0.5)
    if "mamba_sa" in cfg.block_pattern:
        params["shared_attn"] = {
            "ln": layers.init_norm(cfg.d_model),
            "attn": attn_lib.init_attention(k_shared, cfg.attention, cfg.d_model)}
    return params


# ---------------------------------------------------------------------------
# per-block application (mode: "full" with optional cache collect | "decode")
# ---------------------------------------------------------------------------

def _block_window(kind: str, cfg: ModelConfig, long_context: bool) -> Optional[int]:
    if kind == "local":
        return cfg.local_window
    if kind == "global" and long_context:
        # documented long_500k variant: global layers capped to local_window
        return cfg.local_window
    return cfg.attention.window if cfg.attention else None


def _apply_attn_mlp(bp, shared, x, kind, cfg: ModelConfig, mesh, mode, cache,
                    positions, long_context, rng):
    """Attention + MLP (or MoE) block.  Returns ``(x, cache, aux,
    router)``: ``router`` is the MoE layer's metrics dict
    (``balance.METRIC_KEYS``), None for a dense block."""
    win = _block_window(kind, cfg, long_context)
    causal = not cfg.encoder_only
    with jax.named_scope("attention"):
        h = layers.rms_norm(x, bp["ln1"], cfg.norm_eps)
        if mode == "decode":
            ring = win is not None and cache["k"].shape[1] == win
            a, cache = attn_lib.decode_attention(bp["attn"], h, cache,
                                                 cfg.attention, ring=ring,
                                                 window=win)
        else:
            a, kv = attn_lib.full_attention(bp["attn"], h, cfg.attention,
                                            positions=positions,
                                            causal=causal, window=win,
                                            mesh=mesh)
            if cache is not None:
                ring = win is not None and cache["k"].shape[1] == win
                cache = attn_lib.fill_cache(cache, kv, ring=ring)
    x = x + a
    aux = jnp.zeros((), jnp.float32)
    router = None
    if kind == "moe":
        with jax.named_scope("moe_gate"):
            h = layers.rms_norm(x, bp["ln2"], cfg.norm_eps)
        # expert TP needs a data axis to shard f over; sharded_moe_apply
        # rejects axes missing from the mesh rather than silently no-op'ing
        tp = decode_expert_tp_axis(mesh) if mode == "decode" else None
        y, aux, router = moe_lib.sharded_moe_apply(
            mesh, cfg.moe, bp["moe"], h, num_experts=cfg.moe.num_experts,
            act=cfg.act, rng=rng, expert_tp_axis=tp)
        if "shared_mlp" in bp:
            y = y + layers.apply_mlp(bp["shared_mlp"], h, cfg.act)
    else:
        h = layers.rms_norm(x, bp["ln2"], cfg.norm_eps)
        y = layers.apply_mlp(bp["mlp"], h, cfg.act)
    return x + y, cache, aux, router


def _apply_block(j, kind, bp, shared, x, cfg, mesh, mode, cache, positions,
                 long_context, rng):
    """One block: ``(x, cache, aux, router)``, as :func:`_apply_attn_mlp`
    (``router`` is None but for a MoE block)."""
    zero = jnp.zeros((), jnp.float32)
    if kind in ("attn", "local", "global", "dense", "moe"):
        return _apply_attn_mlp(bp, shared, x, kind, cfg, mesh, mode, cache,
                               positions, long_context, rng)
    if kind in ("mamba", "mamba_sa"):
        h = layers.rms_norm(x, bp["ln1"], cfg.norm_eps)
        if mode == "decode":
            y, mstate = mamba2.mamba_decode_step(bp["mamba"], h,
                                                 cache["mamba"], cfg.ssm,
                                                 cfg.d_model)
        else:
            y, mstate = mamba2.mamba_forward(bp["mamba"], h, cfg.ssm,
                                             cfg.d_model, mesh=mesh)
            mstate = mstate if cache is not None else None
        x = x + y
        if kind == "mamba_sa":
            # zamba2: the SHARED attention block, LoRA-adapted per occurrence
            h = layers.rms_norm(x, bp["sa_ln"], cfg.norm_eps)
            h = h + (h @ bp["sa_lora_a"].astype(h.dtype)) @ bp["sa_lora_b"].astype(h.dtype)
            h = layers.rms_norm(h, shared["ln"], cfg.norm_eps)
            win = cfg.local_window if long_context else cfg.attention.window
            if mode == "decode":
                a, sa_cache = attn_lib.decode_attention(
                    shared["attn"], h, cache["sa"], cfg.attention,
                    ring=cache["sa"]["k"].shape[1] == win, window=win)
            else:
                a, kv = attn_lib.full_attention(shared["attn"], h, cfg.attention,
                                                positions=positions, window=win,
                                                mesh=mesh)
                sa_cache = attn_lib.fill_cache(
                    cache["sa"], kv, ring=cache["sa"]["k"].shape[1] == win) \
                    if cache is not None else None
            x = x + a
            new_cache = {"mamba": mstate, "sa": sa_cache} \
                if (cache is not None or mode == "decode") else None
        else:
            new_cache = {"mamba": mstate} if (cache is not None or mode == "decode") else None
        return x, new_cache, zero, None
    if kind == "rwkv":
        h = layers.rms_norm(x, bp["ln1"], cfg.norm_eps)
        if mode == "decode":
            y, rstate = rwkv6.rwkv_decode_step(bp["rwkv"], h, cache["rwkv"],
                                               cfg.rwkv)
        else:
            y, s = rwkv6.rwkv_time_mix(bp["rwkv"], h, cfg.rwkv)
            rstate = {"s": s, "x_last": h[:, -1].astype(jnp.float32)} \
                if cache is not None else None
        x = x + y
        h = layers.rms_norm(x, bp["ln2"], cfg.norm_eps)
        x = x + layers.apply_mlp(bp["mlp"], h, cfg.act)   # channel mix
        new_cache = {"rwkv": rstate} if (cache is not None or mode == "decode") else None
        return x, new_cache, zero, None
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, cache_len: int, *,
                long_context: bool = False, dtype=jnp.bfloat16):
    """Per-super-block stacked caches for decode/prefill."""

    def one(kind):
        if kind in ("attn", "local", "global", "dense", "moe"):
            win = _block_window(kind, cfg, long_context)
            L = min(cache_len, win) if win is not None else cache_len
            return attn_lib.init_cache(cfg.attention, batch, L, cfg.d_model, dtype)
        if kind in ("mamba", "mamba_sa"):
            c = {"mamba": mamba2.init_mamba_state(cfg.ssm, batch, cfg.d_model)}
            if kind == "mamba_sa":
                win = cfg.local_window if long_context else cfg.attention.window
                L = min(cache_len, win) if win is not None else cache_len
                c["sa"] = attn_lib.init_cache(cfg.attention, batch, L,
                                              cfg.d_model, dtype)
            return c
        if kind == "rwkv":
            return {"rwkv": init_rwkv(cfg, batch)}
        raise ValueError(kind)

    def init_rwkv(cfg, batch):
        return rwkv6.init_rwkv_state(cfg.rwkv, batch, cfg.d_model)

    single = tuple(one(k) for k in cfg.block_pattern)
    nsb = cfg.num_super_blocks
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (nsb, *a.shape)).copy(), single)


# ---------------------------------------------------------------------------
# full / prefill / decode passes
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ModelConfig, inputs: jax.Array, dtype, mesh=None):
    if cfg.frontend is not None:
        return inputs.astype(dtype)     # precomputed frame/patch embeddings
    table = params["embed"]
    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    dp_size = 1
    if mesh is not None:
        for a in _dp_axes(mesh):
            dp_size *= mesh.shape[a]
    if msize > 1 and table.shape[0] % msize == 0 \
            and inputs.shape[0] % dp_size == 0:
        # vocab-parallel embedding (Megatron): local masked gather + psum.
        # A plain sharded gather makes XLA materialize the full unsharded
        # (V, d) gradient scatter in backward — 2.3 GiB/dev at dbrx scale.
        dp = _dp_axes(mesh)

        def local(tbl, ids):
            m = lax.axis_index("model")
            vloc = tbl.shape[0]
            rel = ids - m * vloc
            ok = (rel >= 0) & (rel < vloc)
            rows = tbl.astype(dtype)[jnp.clip(rel, 0, vloc - 1)]
            return lax.psum(jnp.where(ok[..., None], rows, 0), "model")

        x = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P("model", None), P(dp)),
            out_specs=P(dp, None, None), check_vma=False,
        )(table, inputs)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
        return x
    return layers.embed(table, inputs, dtype, cfg.scale_embeddings)


def _router_stats(routers, num_experts: int):
    """One block stack's MoE metrics → ``(expert_load_ratio, dropped
    share summed over the layers, grouped visit share summed over the
    layers)``: the busiest expert's assignments over the mean, largest
    over the layers."""
    ratio = jnp.max(jnp.stack([r["expert_load_max"] for r in routers]))
    dropped = sum(r["dropped_share"] for r in routers)
    visits = sum(r["grouped_visit_share"] for r in routers)
    return ratio * num_experts, dropped, visits


def forward(params: Dict[str, Any], inputs: jax.Array, cfg: ModelConfig, *,
            mesh=None, rng: Optional[jax.Array] = None,
            caches=None, collect_caches: bool = False,
            long_context: bool = False, remat: str = "none",
            positions: Optional[jax.Array] = None,
            router_metrics: bool = False):
    """Full-sequence pass (train / prefill).

    inputs: (B, S) int tokens, or (B, S, d) embeddings for frontend archs.
    Returns (hidden (B,S,d), aux_loss, caches|None); with
    ``router_metrics`` (a model with MoE blocks) a fourth item, the
    routing counters: ``expert_load_ratio`` (the busiest expert's
    assignments over the mean, largest over the MoE layers: 1 balanced,
    E collapsed), ``dropped_share`` (assignments dropped by capacity
    or the grouped-EP bound over all routed ones, over the MoE layers) and
    ``grouped_visit_share`` (the grouped Pallas kernels' share of grid
    steps that do work, over the MoE layers; 0 on other paths).
    """
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = _embed_inputs(params, cfg, inputs, dtype, mesh)
    B, S = x.shape[:2]
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    shared = params.get("shared_attn")
    x = shard_act(x, mesh)

    def super_body(carry, xs):
        x, aux, rng = carry
        bparams, cache_in = xs
        rng, *rks = jax.random.split(rng, len(cfg.block_pattern) + 1)
        new_caches, routers = [], []
        for j, kind in enumerate(cfg.block_pattern):
            c_in = cache_in[j] if cache_in is not None else None
            x, c_out, a, router = _apply_block(
                j, kind, bparams[j], shared, x, cfg, mesh, "full", c_in,
                positions, long_context, rks[j])
            x = shard_act(x, mesh)
            aux = aux + a
            new_caches.append(c_out)
            if router is not None:
                routers.append(router)
        out_caches = tuple(new_caches) if cache_in is not None else None
        stats = (_router_stats(routers, cfg.moe.num_experts)
                 if router_metrics else None)
        return (x, aux, rng), (out_caches, stats)

    body = super_body
    if remat == "block":
        body = jax.checkpoint(super_body)
    elif remat == "full":
        body = jax.checkpoint(super_body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    if collect_caches and caches is None:
        caches = init_caches(cfg, B, S, long_context=long_context, dtype=dtype)
    xs = (params["blocks"], caches)
    (x, aux, _), (out_caches, stats) = lax.scan(
        body, (x, jnp.zeros((), jnp.float32), rng), xs)
    with jax.named_scope("head_loss"):
        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if not router_metrics:
        return x, aux, out_caches
    ratio, dropped, visits = stats
    n_moe = cfg.num_super_blocks * cfg.block_pattern.count("moe")
    return x, aux, out_caches, {
        "expert_load_ratio": jnp.max(ratio),
        "dropped_share": jnp.sum(dropped) / n_moe,
        "grouped_visit_share": jnp.sum(visits) / n_moe}


def head_weight(params, cfg: ModelConfig) -> jax.Array:
    """The unembedding (d, V): the embedding's transpose when tied."""
    return (params["embed"].T if cfg.tie_embeddings and cfg.frontend is None
            else params["lm_head"])


def logits_from_hidden(params, cfg: ModelConfig, h: jax.Array, mesh=None):
    return unembed(head_weight(params, cfg), cfg, h, mesh)


def unembed(w: jax.Array, cfg: ModelConfig, h: jax.Array, mesh=None):
    """Logits (..., V) of hidden states h (..., d) under the head w (d, V)."""
    logits = h @ w.astype(h.dtype)
    if cfg.final_softcap:
        logits = layers.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return shard_act(logits, mesh, "logits")


def decode_step(params: Dict[str, Any], token: jax.Array, caches, cfg: ModelConfig,
                *, mesh=None, rng: Optional[jax.Array] = None,
                long_context: bool = False):
    """One-token serve step.  token (B,1) ids or (B,1,d) embeddings;
    caches as returned by init_caches/prefill.  Returns (logits (B,1,V), caches)."""
    dtype = jnp.dtype(cfg.dtype)
    x = _embed_inputs(params, cfg, token, dtype, mesh)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    shared = params.get("shared_attn")

    def super_body(carry, xs):
        x, aux, rng = carry
        bparams, cache_in = xs
        rng, *rks = jax.random.split(rng, len(cfg.block_pattern) + 1)
        new_caches = []
        for j, kind in enumerate(cfg.block_pattern):
            x, c_out, a, _ = _apply_block(j, kind, bparams[j], shared, x,
                                          cfg, mesh, "decode", cache_in[j],
                                          None, long_context, rks[j])
            aux = aux + a
            new_caches.append(c_out)
        return (x, aux, rng), tuple(new_caches)

    (x, _, _), new_caches = lax.scan(
        super_body, (x, jnp.zeros((), jnp.float32), rng),
        (params["blocks"], caches))
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return logits_from_hidden(params, cfg, x, mesh), new_caches
