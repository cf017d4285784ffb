"""End-to-end training driver with crash-safe resume.

CPU-scale usage (the examples use this):
  PYTHONPATH=src python -m repro.launch.train --arch hetumoe-paper-16e \\
      --steps 200 --batch 8 --seq 128 --smoke

On a real pod the same driver runs with ``--mesh 16x16`` under the
production mesh; data parallel input feeding is per-host via the
deterministic synthetic pipeline (every host generates its shard).

Fault tolerance: ``--ckpt-every N`` saves atomically every N steps
(keep-last ``--ckpt-keep``); ``--resume`` restores the newest *intact*
checkpoint and continues — because the synthetic pipeline and rng are
keyed by the global step, a killed-and-resumed run reproduces the
uninterrupted loss trajectory bitwise.  Non-finite steps are skipped by
the train step (see ``training/train_step.py``); the driver fails fast
once ``TrainConfig.max_skipped_steps`` CONSECUTIVE steps were skipped.
``--history-out`` dumps the per-step metric history as JSON so resume
tests and bench tooling diff trajectories without parsing stdout, and
``--inject site:mode@steps`` arms the deterministic fault harness
(``core/faults.py``) from the CLI.

Tracing: each step of the loop runs under
``jax.profiler.StepTraceAnnotation("train", step_num=s)``, its parts
under ``TraceAnnotation`` spans ``train.batch`` (host batch and
``device_put``), ``train.step`` (the jitted call), ``train.fetch`` (the
metrics to the host, which waits for the device) and
``train.checkpoint``.  ``--profile DIR`` writes a profiler trace of
``PROFILE_STEPS`` steps after the first ``PROFILE_SKIP``, where those
spans sit on one clock with the device's ops.  An MoE model's log line
and history carry the routing counters ``expert_load_ratio`` (busiest
expert over the mean), ``dropped_share`` (assignments dropped) and
``grouped_visit_share`` (the grouped Pallas kernels' grid steps that do
work; 0 on other paths).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time
from typing import Any, NamedTuple

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core import faults as faults_mod
from repro.core import tuning
from repro.core.config import ModelConfig, TrainConfig
from repro.data import SyntheticLM
from repro.launch import mesh as mesh_lib
from repro.launch.cache import enable_compile_cache
from repro.training import make_train_step
from repro.training.train_step import TrainState, init_train_state
from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint

PROFILE_SKIP = 3      # steps before --profile's trace starts (compiles)
PROFILE_STEPS = 10    # steps --profile traces


class Trainer(NamedTuple):
    """The pieces :func:`run` drives, placed on one mesh."""
    state: TrainState                 # sharded by mesh_lib.state_shardings
    state_sharding: Any
    step: Any                         # jitted (state, batch, rng) → (state, m)
    data: SyntheticLM
    batch_sharding: Any
    rng: jax.Array
    max_skipped_steps: int

    def batch(self, step: int):
        """The global batch of ``step``, placed by the mesh's batch rules."""
        return jax.device_put(self.data.next_batch(step), self.batch_sharding)

    def dispatch(self, state: TrainState, s: int):
        """Send global step ``s``: its batch and the key ``fold_in(rng,
        s)``.  Returns ``(state, metrics)``, both still on the device."""
        with jax.profiler.TraceAnnotation("train.batch"):
            batch = self.batch(s)
        with jax.profiler.TraceAnnotation("train.step"):
            return self.step(state, batch, jax.random.fold_in(self.rng, s))

    def fetch(self, m) -> dict:
        """A step's metrics on the host; this waits for the step."""
        with jax.profiler.TraceAnnotation("train.fetch"):
            return {k: float(v) for k, v in m.items()}

    def check(self, s: int, m: dict) -> None:
        """Fail fast once ``max_skipped_steps`` consecutive steps were
        skipped as non-finite."""
        if m["nonfinite_streak"] >= self.max_skipped_steps:
            raise RuntimeError(
                f"aborting at step {s}: {int(m['nonfinite_streak'])} "
                f"consecutive non-finite steps were skipped (>= "
                f"max_skipped_steps={self.max_skipped_steps}) — the run "
                f"is diverging; restore an earlier checkpoint, lower the "
                f"lr, or enable loss_scale='dynamic'")


def build(cfg: ModelConfig, tcfg: TrainConfig, mesh, *, batch: int, seq: int,
          seed: int = 0, faults: faults_mod.FaultPlan = None) -> Trainer:
    """Initial state, data and the jitted train step on ``mesh``.

    State and batch are placed by the mesh rules from the start (experts
    over ``model``, batch over the data axes), so nothing piles up on
    device 0; the step keeps the state in the same shardings."""
    rng = jax.random.PRNGKey(seed)
    init = lambda r: init_train_state(r, cfg, tcfg)
    state_sh = mesh_lib.state_shardings(mesh, jax.eval_shape(init, rng))
    data = SyntheticLM(cfg, batch=batch, seq_len=seq, seed=seed)
    step = jax.jit(make_train_step(cfg, tcfg, mesh, faults=faults),
                   out_shardings=(state_sh, NamedSharding(mesh, P())),
                   donate_argnums=(0,))
    return Trainer(jax.jit(init, out_shardings=state_sh)(rng), state_sh, step,
                   data, mesh_lib.batch_shardings(mesh, data.next_batch(0)),
                   rng, tcfg.max_skipped_steps)


def run(arch: str, *, steps: int, batch: int, seq: int, smoke: bool,
        lr: float = 3e-3, microbatches: int = 1, remat: str = "none",
        mesh_shape=(1, 1), log_every: int = 10, ckpt_dir: str = None,
        ckpt_every: int = None, ckpt_keep: int = 3, resume: bool = False,
        seed: int = 0, loss_scale="none", history_out: str = None,
        faults: faults_mod.FaultPlan = None, tune: str = "auto",
        fabric=None, profile_dir: str = None):
    if (ckpt_every or resume) and not ckpt_dir:
        raise ValueError("--ckpt-every/--resume require --ckpt-dir")
    cfg = configs.smoke_config(arch) if smoke else configs.get_config(arch)
    ls = 1.0 if loss_scale in (None, "none") else (
        "dynamic" if loss_scale == "dynamic" else float(loss_scale))
    tcfg = TrainConfig(learning_rate=lr, warmup_steps=max(steps // 10, 1),
                       total_steps=steps, microbatches=microbatches,
                       remat=remat, seed=seed, loss_scale=ls)
    mesh = mesh_lib.make_smoke_mesh(tuple(mesh_shape))
    tmode, tfab = tuning.configure(tune, fabric, mesh=mesh)
    if cfg.moe is not None:
        print(f"tune={tmode} fabric={tfab}")
    tr = build(cfg, tcfg, mesh, batch=batch, seq=seq, seed=seed,
               faults=faults)
    state = tr.state
    start = 0
    if resume:
        if latest_step(ckpt_dir) is not None:
            state, start = restore_checkpoint(ckpt_dir, state)
            state = jax.device_put(state, tr.state_sharding)
            print(f"resumed from step {start} ({ckpt_dir})")
        else:
            print(f"--resume: no checkpoint under {ckpt_dir}, starting fresh")
    n_params = sum(np.prod(p.shape) for p in jax.tree.leaves(state.params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M mesh={dict(mesh.shape)}")
    history = []
    t0 = time.time()
    trace_from = start + PROFILE_SKIP
    with faults_mod.active(faults), contextlib.ExitStack() as profiler:
        for s in range(start, steps):
            faults_mod.crash_point("train.loop", index=s)
            if profile_dir and s == trace_from:
                profiler.enter_context(jax.profiler.trace(profile_dir))
            with jax.profiler.StepTraceAnnotation("train", step_num=s):
                t_step = time.perf_counter()
                state, m = tr.dispatch(state, s)
                m = tr.fetch(m)
                # host seconds to the step's metrics (fetch waits for them)
                history.append({"step": s, **m,
                                "step_s": time.perf_counter() - t_step})
                if s % log_every == 0 or s == steps - 1:
                    dt = time.time() - t0
                    tput = batch * seq * (s + 1 - start) / max(dt, 1e-9)
                    print(_log_line(s, m, tput))
                tr.check(s, m)
                if ckpt_every and (s + 1) % ckpt_every == 0 and s + 1 < steps:
                    with jax.profiler.TraceAnnotation("train.checkpoint"):
                        save_checkpoint(ckpt_dir, state, s + 1,
                                        keep=ckpt_keep)
            if s + 1 == trace_from + PROFILE_STEPS:
                profiler.close()
    if profile_dir:
        print("profile written to", profile_dir)
    if ckpt_dir:
        save_checkpoint(ckpt_dir, state, steps, keep=ckpt_keep)
        print("checkpoint saved to", ckpt_dir)
    if history_out:
        with open(history_out, "w") as f:
            json.dump({"arch": cfg.name, "steps": steps, "start": start,
                       "resumed": bool(resume and start), "seed": seed,
                       "history": history}, f, indent=1)
        print("history written to", history_out)
    return state, history


def _log_line(s: int, m: dict, tput: float) -> str:
    line = (f"step {s:5d} loss {m['loss']:.4f} ce {m['ce']:.4f} "
            f"aux {m['aux']:.4f} gnorm {m['grad_norm']:.2f} "
            f"skip {m['skipped']:.0f} streak "
            f"{m['nonfinite_streak']:.0f}")
    if "expert_load_ratio" in m:
        line += (f" load {m['expert_load_ratio']:.3f} "
                 f"drop {m['dropped_share']:.4f} "
                 f"visit {m['grouped_visit_share']:.3f}")
    return line + f" tok/s {tput:,.0f}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU scale)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "block", "full"])
    ap.add_argument("--mesh", default="1x1", type=mesh_lib.mesh_cli_arg,
                    help="DxM data×model mesh, e.g. 1x1 (CPU) or 16x16")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="save an atomic checkpoint every N steps")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="retain only the newest K checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest intact checkpoint in --ckpt-dir")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--loss-scale", default="none",
                    help="'none', 'dynamic', or a static float (bf16 stability)")
    ap.add_argument("--history-out", default=None,
                    help="dump the per-step metric history as JSON")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help=f"write a profiler trace of {PROFILE_STEPS} steps "
                         f"after the first {PROFILE_SKIP} to DIR")
    ap.add_argument("--inject", action="append", default=[],
                    help="fault spec 'site:mode@steps' (repeatable), e.g. "
                         "'train.grads:nan@3' or 'ckpt.data_tmp_written:kill@20'")
    ap.add_argument("--tune", default="auto",
                    choices=list(tuning.TUNE_MODES),
                    help="'auto' resolves MoEConfig 'auto' knobs from the "
                         "α–β cost model, 'off' pins them to the static "
                         "defaults, 'calibrate' measures a few AllToAll "
                         "shapes once and fits α–β (persisted to "
                         "TUNE_moe.json)")
    ap.add_argument("--fabric", default="ici_dcn",
                    type=mesh_lib.fabric_cli_arg,
                    help="named fast/slow LinkSpec pair the tuner scores "
                         "against (ici_dcn | pcie_eth100)")
    args = ap.parse_args()
    enable_compile_cache()
    faults = faults_mod.plan_from_specs(args.inject) if args.inject else None
    run(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        smoke=args.smoke, lr=args.lr, microbatches=args.microbatches,
        remat=args.remat, mesh_shape=args.mesh, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, ckpt_keep=args.ckpt_keep,
        resume=args.resume, log_every=args.log_every, seed=args.seed,
        loss_scale=args.loss_scale, history_out=args.history_out,
        faults=faults, tune=args.tune, fabric=args.fabric,
        profile_dir=args.profile)


if __name__ == "__main__":
    main()
