"""Train step: chunked CE loss, gradient accumulation, clipping, AdamW,
and the non-finite skip-step guard.

Chunked cross-entropy: the unembed + softmax-CE is scanned over sequence
chunks so the full (B, S, V) logits tensor is NEVER materialized — at
gemma2's V=256k that tensor is ~2 GB/device f32 on train_4k; chunking
caps it at (B, S/nc, V).  Under differentiation it is a fused linear
cross-entropy: each chunk takes its VJP inside the forward scan, which
carries the head weight's f32 gradient and stacks dh, so the backward
neither keeps nor recomputes any chunk's logits (three logits-sized
matmuls a step, not four).  Both are beyond-paper optimizations.

Gradient accumulation: ``lax.scan`` over microbatches (the standard
jax idiom — one compiled step regardless of accumulation factor).

Skip-step guard (fault tolerance): one NaN/Inf gradient must not corrupt
the optimizer state — the step's update is suppressed with ``jnp.where``
(params, moments, AND the Adam bias-correction count stay bitwise
unchanged) and ``TrainState`` carries ``skipped`` / ``nonfinite_streak``
counters so the driver can fail fast after ``tcfg.max_skipped_steps``
consecutive bad steps.  ``tcfg.loss_scale`` adds (static or dynamic)
loss scaling for bf16: the loss is scaled before the backward, grads are
unscaled before clipping, and in "dynamic" mode the scale halves on a
bad step and doubles after ``loss_scale_growth_interval`` good ones.
Injection seams for the fault harness (``core/faults.py``):
``train.activations``, ``train.loss``, ``train.grads``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import faults as faults_mod
from repro.core.config import ModelConfig, TrainConfig
from repro.models import transformer as T
from repro.optim import adamw_update, clip_by_global_norm, init_opt_state, make_schedule

# dynamic loss scaling bounds (standard mixed-precision choices)
_DYNAMIC_SCALE_INIT = 2.0 ** 15
_SCALE_MIN = 1.0
_SCALE_MAX = 2.0 ** 24


class TrainState(NamedTuple):
    params: Any
    opt: Dict
    step: jax.Array
    # fault-tolerance counters (None only in legacy 3-field construction;
    # init_train_state always fills real scalars)
    skipped: Any = None            # i32: total skipped (non-finite) steps
    nonfinite_streak: Any = None   # i32: CONSECUTIVE skipped steps
    good_streak: Any = None        # i32: consecutive finite steps (scale growth)
    loss_scale: Any = None         # f32: current loss scale


def init_loss_scale(tcfg: TrainConfig) -> float:
    return (_DYNAMIC_SCALE_INIT if tcfg.loss_scale == "dynamic"
            else float(tcfg.loss_scale))


def init_train_state(rng: jax.Array, cfg: ModelConfig,
                     tcfg: TrainConfig) -> TrainState:
    params = T.init_model(rng, cfg)
    # distinct zero buffers: donated state must not alias across leaves
    zero = lambda: jnp.zeros((), jnp.int32)
    return TrainState(params, init_opt_state(params, tcfg), zero(),
                      skipped=zero(), nonfinite_streak=zero(),
                      good_streak=zero(),
                      loss_scale=jnp.float32(init_loss_scale(tcfg)))


def _auto_chunks(S: int, V: int) -> int:
    """Pick the CE chunk count so one chunk's logits stay ~2^25 elements
    per batch row (≈ 128 MB/device at B_local≈16, f32) — the memory knob
    that keeps gemma2 (V=256k) and internvl2 (V=92k) under HBM."""
    target_tokens = max(16, 2 ** 25 // max(V, 1))
    nc = 1
    while S % (nc * 2) == 0 and S // nc > target_tokens and nc < 64:
        nc *= 2
    return nc


def chunked_ce_loss(params, cfg: ModelConfig, h: jax.Array, targets: jax.Array,
                    mask: jax.Array, mesh=None, num_chunks: Optional[int] = None):
    """Scan the unembed+CE over sequence chunks.  h (B,S,d) → scalar.

    Differentiable in ``params`` (through the head weight) and ``h``;
    the gradient is taken in the forward scan (``_fused_ce``)."""
    S = h.shape[1]
    nc = num_chunks or _auto_chunks(S, cfg.vocab_size)
    while S % nc:
        nc -= 1
    return _fused_ce(cfg, mesh, nc, h, T.head_weight(params, cfg),
                     targets, mask)


def _ce_scan(cfg: ModelConfig, mesh, nc: int, h, w, targets, mask,
             inv=None):
    """Masked NLL summed over ``nc`` sequence chunks.  Given ``inv``, the
    cotangent of that sum, each chunk also takes its VJP while its logits
    are live, and this returns (sum, (dh (B,S,d), dW (d,V) in f32))."""
    B, S, d = h.shape
    chunk = lambda x: x.reshape(B, nc, S // nc, *x.shape[2:]).swapaxes(0, 1)

    def nll(hi, w, ti, mi):
        logits = T.unembed(w, cfg, hi, mesh).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * mi)

    def body(acc, xs):
        hi, ti, mi = xs
        if inv is None:
            return acc + nll(hi, w, ti, mi), None
        tot, dw = acc
        s, pull = jax.vjp(lambda a, b: nll(a, b, ti, mi), hi, w)
        dhi, dwi = pull(inv)
        return (tot + s, dw + dwi), dhi

    zero = jnp.zeros((), jnp.float32)
    xs = (chunk(h), chunk(targets), chunk(mask))
    if inv is None:
        return lax.scan(body, zero, xs)[0]
    (tot, dw), dh = lax.scan(body, (zero, jnp.zeros(w.shape, jnp.float32)),
                             xs)
    return tot, (dh.swapaxes(0, 1).reshape(B, S, d), dw)


def _count(mask):
    """The loss's token count (at least 1), in f32."""
    return jnp.maximum(jnp.sum(mask, dtype=jnp.float32), 1.0)


# Fused linear cross-entropy.  The loss is a scalar and its token count
# is known before the scan, so each chunk's VJP can be taken in the
# forward (cotangent 1/count); the backward only scales (dh, dW) by the
# incoming cotangent.  No chunk's (S/nc, V) logits outlive its scan step,
# so the (S, V) tensor the chunking avoids is never stacked as residuals.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_ce(cfg, mesh, nc, h, w, targets, mask):
    return _ce_scan(cfg, mesh, nc, h, w, targets, mask) / _count(mask)


def _fused_ce_fwd(cfg, mesh, nc, h, w, targets, mask):
    cnt = _count(mask)
    tot, (dh, dw) = _ce_scan(cfg, mesh, nc, h, w, targets, mask,
                             inv=1.0 / cnt)
    return tot / cnt, (dh, dw.astype(w.dtype))


def _fused_ce_bwd(cfg, mesh, nc, res, g):
    dh, dw = res
    return dh * g.astype(dh.dtype), dw * g.astype(dw.dtype), None, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def donation_alias_pairs(tree) -> list:
    """Leaf paths in ``tree`` (a donated pytree, e.g. a ``TrainState``)
    that share one buffer.

    The driver donates the whole train state to the compiled step; two
    leaves backed by the SAME array make XLA's donation reject the alias
    (or silently un-donate, doubling the state's HBM residency).  This is
    why ``init_train_state`` builds DISTINCT zero scalars for the
    counters — the contract the ``donation-alias`` lint rule
    (``repro.analysis``) enforces.  Returns ``[(path_a, path_b), ...]``
    for every aliased pair (empty = safe to donate).
    """
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]

    def key(leaf):
        try:  # committed single-device arrays: compare the real buffer
            return ("ptr", leaf.unsafe_buffer_pointer())
        except Exception:  # tracers / sharded arrays: object identity
            return ("id", id(leaf))

    seen: Dict[Any, str] = {}
    pairs = []
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path)
        k = key(leaf)
        if k in seen:
            pairs.append((seen[k], name))
        else:
            seen[k] = name
    return pairs


def _tree_where(ok, new, old):
    """Per-leaf select: ``new`` on a finite step, ``old`` (bitwise) on a
    skipped one.  ``jnp.where(False, nan, x)`` returns ``x`` unchanged."""
    return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh=None,
                    faults: Optional[faults_mod.FaultPlan] = None):
    """Returns train_step(state, batch, rng) → (state, metrics).

    ``batch`` holds the GLOBAL batch; with ``tcfg.microbatches > 1`` it is
    split on the batch axis and accumulated via scan.

    A model with MoE blocks adds the routing counters to the metrics:
    ``expert_load_ratio`` (largest over the microbatches) and
    ``dropped_share`` (their mean), see ``transformer.forward``.  The
    cross-entropy runs under the ``head_loss`` named scope and the
    guard, clipping and AdamW under ``optimizer``, so their device ops
    say so in the compiled step's op metadata.

    ``faults`` (a ``core.faults.FaultPlan``) arms the traced injection
    seams at trace time; None (production) inserts no extra ops.
    """
    sched = make_schedule(tcfg)
    dynamic = tcfg.loss_scale == "dynamic"
    static_scale = not dynamic and float(tcfg.loss_scale) == 1.0
    routed = "moe" in cfg.block_pattern

    def train_step(state: TrainState, batch, rng) -> Tuple[TrainState, Dict]:
        mbs = tcfg.microbatches
        scale = (jnp.float32(1.0) if static_scale
                 else state.loss_scale.astype(jnp.float32))

        def loss_fn(params, mb, r):
            out = T.forward(params, mb["inputs"], cfg, mesh=mesh, rng=r,
                            remat=tcfg.remat, router_metrics=routed)
            h, aux = out[0], out[1]
            router = out[3] if routed else {}
            h = faults_mod.apply_traced(faults, "train.activations",
                                        state.step, h)
            with jax.named_scope("head_loss"):
                ce = chunked_ce_loss(params, cfg, h, mb["targets"],
                                     mb["loss_mask"], mesh)
            loss = ce + aux
            loss = faults_mod.apply_traced(faults, "train.loss",
                                           state.step, loss)
            scaled = loss if static_scale else loss * scale
            return scaled, (loss, ce, aux, router)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        if mbs == 1:
            (_, (loss, ce, aux, router)), grads = grad_fn(state.params,
                                                          batch, rng)
        else:
            def split(x):
                return x.reshape(mbs, x.shape[0] // mbs, *x.shape[1:])
            mb_batch = jax.tree.map(split, batch)
            rngs = jax.random.split(rng, mbs)

            def body(acc, xs):
                mb, r = xs
                (_, (l, c, a, router)), g = grad_fn(state.params, mb, r)
                gacc, lacc, cacc, aacc = acc
                gacc = jax.tree.map(jnp.add, gacc, g)
                return (gacc, lacc + l, cacc + c, aacc + a), router

            zeros = jax.tree.map(jnp.zeros_like, state.params)
            (grads, loss, ce, aux), router = lax.scan(
                body, (zeros, jnp.zeros(()), jnp.zeros(()), jnp.zeros(())),
                (mb_batch, rngs))
            grads = jax.tree.map(lambda g: g / mbs, grads)
            loss, ce, aux = loss / mbs, ce / mbs, aux / mbs
            if routed:
                # over the microbatches: the busiest load, the mean drops
                router = {
                    "expert_load_ratio": jnp.max(router["expert_load_ratio"]),
                    "dropped_share": jnp.mean(router["dropped_share"])}

        grads = faults_mod.apply_traced(faults, "train.grads", state.step,
                                        grads)
        with jax.named_scope("optimizer"):
            # -- non-finite guard -----------------------------------------
            # Under single-controller jit these arrays are global, so
            # reducing them IS the cross-device all-reduce of the isfinite
            # check (XLA inserts the collective for sharded leaves).
            ok = jnp.isfinite(loss)
            for g in jax.tree.leaves(grads):
                ok = ok & jnp.all(jnp.isfinite(g))

            if not static_scale:
                # unscale AFTER the finite check (an overflowed Inf grad
                # must be seen as non-finite, not Inf/scale); skipped steps
                # never consume the unscaled values.
                inv = (jnp.float32(1.0) / scale)
                grads = jax.tree.map(lambda g: (g * inv.astype(g.dtype)),
                                     grads)

            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
            lr = sched(state.step)
            new_params, new_opt = adamw_update(grads, state.opt,
                                               state.params, tcfg, lr)
            # bad step: params, moments AND the bias-correction count keep
            # their old bits — the update never happened.
            new_params = _tree_where(ok, new_params, state.params)
            new_opt = _tree_where(ok, new_opt, state.opt)

            oki = ok.astype(jnp.int32)
            skipped = state.skipped + (1 - oki)
            streak = jnp.where(ok, 0, state.nonfinite_streak + 1)
            good = jnp.where(ok, state.good_streak + 1, 0)
            if dynamic:
                grow = ok & (good >= tcfg.loss_scale_growth_interval)
                new_scale = jnp.where(
                    ok,
                    jnp.where(grow, jnp.minimum(scale * 2.0, _SCALE_MAX),
                              scale),
                    jnp.maximum(scale * 0.5, _SCALE_MIN))
                good = jnp.where(grow, 0, good)
            else:
                new_scale = state.loss_scale

        metrics = {"loss": loss, "ce": ce, "aux": aux,
                   "grad_norm": gnorm, "lr": lr,
                   "skipped": skipped, "nonfinite_streak": streak,
                   "loss_scale": new_scale, **router}
        return TrainState(new_params, new_opt, state.step + 1,
                          skipped=skipped, nonfinite_streak=streak,
                          good_streak=good, loss_scale=new_scale), metrics

    return train_step
