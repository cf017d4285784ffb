"""Router auxiliary losses + load metrics (Switch/GShard style)."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.config import MoEConfig
from repro.core.gating import GateOutput

# THE canonical router-metric key list.  ``aux_losses`` returns exactly
# these keys (zipped strictly against the values it computes), and
# ``moe.sharded_moe_apply`` builds its shard_map metric out_specs from
# this tuple — add a metric here (and its value in ``aux_losses``) and
# every consumer stays in sync; duplicating the names at the shard_map
# boundary produced an opaque pytree-mismatch error instead.
METRIC_KEYS = ("load_balance_loss", "router_z_loss",
               "expert_load_max", "dropped_share", "grouped_visit_share")


def _masked_mean(x: jax.Array, valid: Optional[jax.Array],
                 axes: Tuple[str, ...] = ()) -> jax.Array:
    """Mean of ``x`` over its leading (token) axis, restricted to the
    ``valid`` rows.  Padded decode tokens (rerouted to the virtual
    expert, combine weight zeroed) must not bias the router statistics.

    ``axes``: mesh axis names to aggregate over (inside shard_map).  The
    (sum, count) pair is psum'd BEFORE dividing, so every valid token
    weighs the same globally — a pmean of per-shard means would
    over-weight tokens on padding-heavy shards (and count an all-padding
    shard as a zero), breaking padded ≡ unpadded.
    """
    if valid is None:
        s = jnp.sum(x, axis=0)
        n = jnp.asarray(x.shape[0], s.dtype)
    else:
        w = valid.astype(x.dtype)
        s = jnp.sum(x * (w[:, None] if x.ndim > 1 else w), axis=0)
        n = jnp.sum(w)
    if axes:
        s = lax.psum(s, axes)
        n = lax.psum(n, axes)
    return s / jnp.maximum(n, 1.0)


def load_balance_loss(gate: GateOutput,
                      valid: Optional[jax.Array] = None,
                      axes: Tuple[str, ...] = ()) -> jax.Array:
    """Switch Transformer aux loss: E · Σ_e f_e · P_e.

    f_e — fraction of tokens whose FIRST choice is e (hard counts);
    P_e — mean router probability of e (soft, differentiable).
    Minimized (=1) by a uniform assignment.  ``valid`` (S,) bool masks
    padded rows out of BOTH means (their expert_index points at the
    virtual expert, so they would deflate f_e and skew P_e otherwise);
    ``axes`` makes the means global over the mesh (see _masked_mean).
    """
    E = gate.router_probs.shape[-1]
    first = gate.expert_index[:, 0]
    f = _masked_mean(
        jax.nn.one_hot(first, E, dtype=gate.router_probs.dtype), valid, axes)
    p = _masked_mean(gate.router_probs, valid, axes)
    return E * jnp.sum(f * p)


def router_z_loss(gate: GateOutput,
                  valid: Optional[jax.Array] = None,
                  axes: Tuple[str, ...] = ()) -> jax.Array:
    """ST-MoE z-loss: mean (logsumexp logits)² — keeps router logits small.
    ``valid`` masks padded rows (their all-zero logits contribute a
    spurious log(E)² each)."""
    return _masked_mean(jax.nn.logsumexp(gate.logits, axis=-1) ** 2,
                        valid, axes)


def aux_losses(cfg: MoEConfig, gate: GateOutput,
               expert_counts: jax.Array | None = None,
               valid: Optional[jax.Array] = None,
               axes: Tuple[str, ...] = (),
               dropped: Optional[jax.Array] = None,
               visit_share: Optional[jax.Array] = None,
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Weighted aux-loss scalar + router metrics dict.

    ``expert_counts`` (E,) — per-expert assignment counts already derived
    by the dispatch plan's single sort; passing them skips the O(S·K·E)
    one-hot re-count here (sort-once: the plan is the source of truth for
    load state).  ``valid`` (S,) — mask of real (non-padded) tokens;
    ``axes`` — mesh axes to reduce over, making lb/z-loss exact GLOBAL
    masked means and the load metrics global counts (the caller's later
    pmean is then an identity on them).  ``dropped`` — how many of this
    shard's valid assignments the dispatch dropped (capacity or the
    grouped-EP bound); ``None`` for a dropless dispatch.
    ``visit_share`` — the grouped Pallas kernels' share of row-axis grid
    steps that do work (``grouped_ffn.visit_share``); ``None`` on a path
    that runs no grouped Pallas kernel.

    Metrics: ``expert_load_max`` is the busiest expert's share of all
    routed assignments (1/E when balanced, 1 when collapsed);
    ``dropped_share`` is the dropped assignments over all routed ones;
    ``grouped_visit_share`` is ``visit_share``, a constant 0 without it.
    """
    E = gate.router_probs.shape[-1]
    lb = load_balance_loss(gate, valid, axes)
    zl = router_z_loss(gate, valid, axes)
    loss = cfg.aux_loss_weight * lb + cfg.router_z_loss_weight * zl
    if expert_counts is not None:
        counts = expert_counts.astype(jnp.float32)
    else:
        counts = jnp.sum(
            jax.nn.one_hot(gate.expert_index, E, dtype=jnp.float32), axis=(0, 1))
    dropped = (jnp.zeros((), jnp.float32) if dropped is None
               else dropped.astype(jnp.float32))
    visit_share = (jnp.zeros((), jnp.float32) if visit_share is None
                   else visit_share.astype(jnp.float32))
    if axes:
        counts, dropped = lax.psum((counts, dropped), axes)
    total = jnp.maximum(jnp.sum(counts), 1.0)
    # zip(strict=True) raises even under ``python -O`` if a metric is
    # added to one side but not the other
    metrics = dict(zip(METRIC_KEYS,
                       (lb, zl, jnp.max(counts) / total, dropped / total,
                        visit_share),
                       strict=True))
    return loss, metrics
