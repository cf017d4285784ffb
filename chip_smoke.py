#!/usr/bin/env python3
"""Chip smoke run of the HetuMoE trainer at the paper's published widths.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # expert parallelism over four chips

One chip runs three phases of ``hetumoe-paper-16e`` (d=2048, 16 experts of
FFN width 2048, 16 heads of 128, vocab 50304) at batch 8 x seq 1024:

  train    ``repro.launch.train.run`` for a few steps on mesh (1, 1): the
           first loss is finite and near ln(vocab), no step is skipped and
           the loss falls;
  kernels  one step of the same model with the HetuMoE kernel path
           (``use_pallas_gate=True, dispatch="grouped"``: fused top-k gate,
           layout transform, grouped matmul forward and backward) against
           the same dispatch on the jnp / ``ragged_dot`` reference: loss,
           gradient norm and each expert's weight gradients;
  flash    the flash-attention kernels, forward and gradient, against the
           plain f32 oracle ``kernels.ref.ref_flash_attention``.

``--chips 4`` runs only the expert-parallel phase: one step on mesh (1, 4)
with the experts sharded over ``model`` and the grouped AllToAll crossing
the chips, against a one-chip run of the same batch and seed (loss and
gradient norm), and each expert's weight gradients of the same pair of
steps in f32 at full matmul precision, batch 2.

Everything runs in this one process.  The run stops at once off a TPU or
with the kernels in interpret mode.  The times it prints come from a smoke
run, not a benchmark.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "hetumoe-paper-16e"
BATCH, SEQ = 8, 1024
STEPS, SEED = 10, 0

# Tolerances, all normwise relative |a - b| / |b|; the readings beside
# them are from one v5e run of this script (TPU v5 lite).
#
# kernels: both sides run the same grouped dispatch in bf16 with f32
#   accumulation; they differ only in where partial sums are rounded (the
#   Pallas backward rounds the cotangent to bf16, 2^-9 relative, before
#   its matmuls; the reference's backward runs in f32).  The forward
#   rounds at the same points on both sides: loss read 0, grad norm
#   4.46e-6.  The grad norm is dominated by the 50304 x 2048 embedding,
#   so the expert weights get their own check: the worst (layer, expert)
#   slice of each expert weight's gradient.  Elementwise bf16 rounding of
#   the cotangent gives a few 2^-9 there (read 4.31e-3), while one
#   misplaced or missing (512, 512) tile of a (2048, 2048) expert gives
#   about 0.25 or more.
KERNEL_LOSS_RTOL, KERNEL_GNORM_RTOL = 1e-5, 1e-4
KERNEL_EXPERT_RTOL = 2e-2
# flash: bf16 inputs (unit roundoff 2^-9 ~ 2e-3); the kernel rounds the
#   probabilities and dS to bf16 before each MXU matmul and its outputs to
#   bf16, so a few 2^-9 relative is expected.  A wrong mask, block or
#   statistic gives O(1) errors.
FLASH_FWD_RTOL, FLASH_GRAD_RTOL = 1e-2, 2e-2
# ep4: the same math on a (1, 4) mesh; dropless grouped dispatch routes
#   every token by its own router output, so only summation order and the
#   rounding points of the sharded matmuls and all-reduces differ.
#   Readings: loss 2.37e-6, grad norm 4.35e-5.  In bf16 that is not
#   enough to compare single experts: attention and the projections are
#   sharded over ``model``, their bf16 outputs move by an ulp here and
#   there, near-tied tokens then pick another expert, and one expert's
#   gradient moves by tens of percent (read on the chip: worst slice
#   0.153; on the CPU at d = 256: worst 0.34, median 2e-2).  An f32 step
#   at the TPU's default matmul precision still rounds every matmul input
#   to bf16 (read on the chip: worst slice 2.53e-2).  So the expert
#   gradients are compared on a step in f32 at full matmul precision,
#   batch 2, full widths, where the rounding moves no token (read on the
#   chip: worst slice 3.34e-4; CPU at d = 256: 1.5e-6).
EP_LOSS_RTOL, EP_GNORM_RTOL = 1e-4, 1e-3
EP_EXPERT_BATCH, EP_EXPERT_RTOL = 2, 1e-3

# the expert weights under each block's "moe" params (leading dims layer,
# expert), whose gradients the grouped matmul's drhs kernel produces
EXPERT_WEIGHTS = ("w_up", "w_gate", "w_out")


# the HetuMoE kernels of the grouped dispatch path: fused top-k gate,
# layout transform (and its scatter-add VJP), grouped matmul fwd + bwd
MOE_KERNELS = ("fused_topk_gate", "_gather_rows_impl", "scatter_add_rows",
               "_grouped_matmul_impl", "_grouped_drhs_impl")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, non-finite or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def check_device():
    """The TPU devices of this process; raises off a TPU or when the
    kernels would run in interpret mode."""
    import jax
    from repro.kernels import ops as kops

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"needs a TPU, JAX found platform {devs[0].platform!r}")
    check(not kops.INTERPRET, "Pallas kernels are in interpret mode")
    return devs


def paper_config(**moe_kw):
    from repro import configs
    cfg = configs.get_config(ARCH)
    if moe_kw:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                               **moe_kw))
    return cfg


def peak_gib(dev) -> float:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 2 ** 30


def expert_grads(opt_state) -> dict:
    """Each expert weight's gradient after one step, as host arrays.

    AdamW's first update leaves its first moment at (1 - b1) * g, g being
    the gradient clipped by the global norm; the clip factor differs
    between two compared runs only by their grad norms' own relative
    difference, far below the expert tolerances."""
    import jax
    return {jax.tree_util.keystr(path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                opt_state["m"])
            if len(path) > 1 and getattr(path[-2], "key", None) == "moe"
            and getattr(path[-1], "key", None) in EXPERT_WEIGHTS}


def worst_expert(a: dict, b: dict):
    """The largest relative difference over the (layer, expert) slices of
    the expert gradients ``a`` against ``b``, with the slice's name."""
    check(a and a.keys() == b.keys(), "no matching expert gradients")
    errs = {}
    for name, x in a.items():
        y = b[name]
        check(x.shape == y.shape and x.ndim >= 3,
              f"expert gradient {name}: shapes {x.shape} vs {y.shape}")
        for idx in np.ndindex(*x.shape[:-2]):
            errs[f"{name}{list(idx)}"] = rel(x[idx], y[idx])
    worst = max(errs, key=errs.get)
    return errs[worst], worst


def one_step(cfg, mesh, batch: int = BATCH):
    """Compile and run one train step of ``cfg`` on ``mesh``; returns the
    step's metrics, its expert gradients, its compiled HLO text and the
    compile seconds."""
    import jax
    from repro.core.config import TrainConfig
    from repro.launch import train

    tr = train.build(cfg, TrainConfig(seed=SEED), mesh, batch=batch,
                     seq=SEQ, seed=SEED)
    bt, key = tr.batch(0), jax.random.fold_in(tr.rng, 0)
    t0 = time.perf_counter()
    compiled = tr.step.lower(tr.state, bt, key).compile()
    t_compile = time.perf_counter() - t0
    state, m = compiled(tr.state, bt, key)
    m = {k: float(v) for k, v in m.items()}
    grads = expert_grads(state.opt)
    del tr, bt, state
    gc.collect()
    for k in ("loss", "ce", "grad_norm"):
        check(math.isfinite(m[k]), f"{cfg.name}: non-finite {k} {m[k]}")
    check(all(np.isfinite(g).all() for g in grads.values()),
          f"{cfg.name}: non-finite expert gradient")
    return m, grads, compiled.as_text(), t_compile


def compare_step(tag: str, got: dict, want: dict, rtol_loss: float,
                 rtol_gnorm: float) -> None:
    """Print and check one step's loss and grad norm against a reference
    step's."""
    e_loss = rel(got["loss"], want["loss"])
    e_gn = rel(got["grad_norm"], want["grad_norm"])
    print(f"[{tag}] loss {got['loss']:.6f} vs {want['loss']:.6f} (rel "
          f"{e_loss:.2e}, tol {rtol_loss:g}); grad norm "
          f"{got['grad_norm']:.6f} vs {want['grad_norm']:.6f} (rel "
          f"{e_gn:.2e}, tol {rtol_gnorm:g})")
    check(e_loss <= rtol_loss, f"{tag}: loss differs from the reference")
    check(e_gn <= rtol_gnorm, f"{tag}: grad norm differs from the reference")


def compare_experts(tag: str, got: dict, want: dict, rtol: float) -> None:
    """Print and check the worst expert gradient slice against a reference
    step's."""
    err, worst = worst_expert(got, want)
    print(f"[{tag}] worst expert gradient {worst} (rel {err:.2e}, tol "
          f"{rtol:g})")
    check(err <= rtol,
          f"{tag}: expert gradient {worst} differs from the reference")


def phase_train() -> None:
    import jax
    from repro.launch import train

    cfg = paper_config()
    _, hist = train.run(ARCH, steps=STEPS, batch=BATCH, seq=SEQ, smoke=False,
                        mesh_shape=(1, 1), log_every=1, seed=SEED)
    gc.collect()
    first, last = hist[0], hist[-1]
    ln_v = math.log(cfg.vocab_size)
    check(len(hist) == STEPS, f"train: {len(hist)} of {STEPS} steps ran")
    check(math.isfinite(first["loss"]), f"train: first loss {first['loss']}")
    check(abs(first["ce"] - ln_v) < 1.0,
          f"train: first-step ce {first['ce']:.4f} is not near ln(V) = "
          f"{ln_v:.4f}")
    check(last["skipped"] == 0 and all(h["nonfinite_streak"] == 0
                                       for h in hist),
          f"train: {last['skipped']:.0f} steps were skipped")
    check(last["loss"] < first["loss"],
          f"train: loss did not fall ({first['loss']:.4f} -> "
          f"{last['loss']:.4f})")
    steady = sorted(h["step_s"] for h in hist[1:])
    med = steady[len(steady) // 2]
    print(f"[train] ok: loss {first['loss']:.4f} -> {last['loss']:.4f} "
          f"(ln V = {ln_v:.4f}) over {STEPS} steps, 0 skipped")
    print(f"[train] smoke timing, not a benchmark: first step incl. compile "
          f"{hist[0]['step_s']:.2f} s, median later step {med:.4f} s, "
          f"{BATCH * SEQ / med:,.0f} tokens/s, device peak "
          f"{peak_gib(jax.devices()[0]):.2f} GiB")


def phase_kernels() -> None:
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_smoke_mesh((1, 1))
    runs = {}
    for pallas in (True, False):
        cfg = paper_config(use_pallas_gate=pallas, dispatch="grouped")
        runs[pallas] = one_step(cfg, mesh)
    (mk, gk, hk, tk), (mr, gr, hr, tr) = runs[True], runs[False]
    # each Pallas kernel leaves its jit name in the op metadata of the
    # Mosaic custom call it lowered to
    missing = [k for k in MOE_KERNELS if f"jit({k})/pallas_call" not in hk]
    check("tpu_custom_call" in hk and not missing,
          f"kernels: {missing} did not lower to Mosaic in the kernel path")
    check(not any(f"jit({k})" in hr for k in MOE_KERNELS),
          "kernels: the reference step runs the MoE kernels")
    print(f"[kernels] Mosaic kernels {', '.join(MOE_KERNELS)}; compile "
          f"{tk:.1f} s vs reference {tr:.1f} s")
    compare_step("kernels", mk, mr, KERNEL_LOSS_RTOL, KERNEL_GNORM_RTOL)
    compare_experts("kernels", gk, gr, KERNEL_EXPERT_RTOL)


def phase_flash() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import ref_flash_attention

    cfg = paper_config()
    H = cfg.attention.num_heads
    hd = cfg.d_model // H
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, w = (jax.random.normal(kk, (BATCH, H, SEQ, hd), jnp.bfloat16)
                  for kk in ks)
    pos = jnp.arange(SEQ, dtype=jnp.int32)
    scale = hd ** -0.5

    def kernel(q, k, v):
        return flash_attention(q, k, v, pos, pos, scale, True, None, None,
                               512, kops.INTERPRET)

    def oracle(q, k, v):
        with jax.default_matmul_precision("highest"):
            return ref_flash_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), pos, pos, scale, True, None, None)

    def fwd_and_grads(f):
        loss = lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w)
        return jax.jit(lambda q, k, v: (f(q, k, v),) + jax.grad(
            loss, (0, 1, 2))(q, k, v))(q, k, v)

    got, want = fwd_and_grads(kernel), fwd_and_grads(oracle)
    errs = {n: rel(g.astype(jnp.float32), r)
            for n, g, r in zip(("o", "dq", "dk", "dv"), got, want)}
    print("[flash] rel err vs f32 oracle: " + ", ".join(
        f"{n} {e:.2e}" for n, e in errs.items())
        + f" (tol fwd {FLASH_FWD_RTOL:g}, grads {FLASH_GRAD_RTOL:g})")
    check(errs["o"] <= FLASH_FWD_RTOL, "flash: forward differs from oracle")
    check(max(errs["dq"], errs["dk"], errs["dv"]) <= FLASH_GRAD_RTOL,
          "flash: gradient differs from oracle")


def phase_ep4() -> None:
    import jax
    from repro.launch import mesh as mesh_lib

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, JAX found {len(devs)}")
    cfg = paper_config(use_pallas_gate=True, dispatch="grouped")
    # the four-chip run goes first, so each device's peak is its own
    mesh4, mesh1 = (mesh_lib.make_smoke_mesh(s) for s in ((1, 4), (1, 1)))
    m4, _, h4, t4 = one_step(cfg, mesh4)
    peaks = [peak_gib(d) for d in devs[:4]]
    check("all-to-all" in h4, "ep4: no all-to-all in the four-chip step")
    missing = [k for k in MOE_KERNELS if f"jit({k})/pallas_call" not in h4]
    check(not missing, f"ep4: {missing} did not lower to Mosaic")
    m1, _, _, t1 = one_step(cfg, mesh1)
    print(f"[ep4] mesh (1, 4) vs one chip; compile {t4:.1f} s vs {t1:.1f} s")
    print("[ep4] peak_bytes_in_use during the (1, 4) step, GiB per device: "
          + ", ".join(f"{d.id}: {p:.2f}" for d, p in zip(devs, peaks)))
    compare_step("ep4", m4, m1, EP_LOSS_RTOL, EP_GNORM_RTOL)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        _, g4, _, _ = one_step(cfg32, mesh4, EP_EXPERT_BATCH)
        _, g1, _, _ = one_step(cfg32, mesh1, EP_EXPERT_BATCH)
    compare_experts(f"ep4 f32 batch {EP_EXPERT_BATCH}", g4, g1,
                    EP_EXPERT_RTOL)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the expert-parallel phase")
    args = ap.parse_args()
    try:
        from repro.launch.cache import enable_compile_cache
        print(f"compile cache: {enable_compile_cache()}")
        devs = check_device()
        if args.chips == 4:
            phase_ep4()
        else:
            phase_train()
            phase_kernels()
            phase_flash()
    except Exception as e:                          # noqa: BLE001
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
