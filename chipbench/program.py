"""The system under test, as one cell drives it.

The entry is ``repro.launch.train.build``: the jitted
``training.train_step.make_train_step`` step with the Trainer's state and
batch shardings, weights initialised on the device from the seed, and the
state donated.  :class:`Program` compiles that step once, and every step
it takes, in set-up and in the window, goes through the parts of the
loop body of ``repro.launch.train.run``: :meth:`Program.dispatch` places
the batch with the Trainer's ``batch_sharding`` and calls the step;
:meth:`Program.fetch` brings its metrics to the host and checks the
non-finite streak.  :meth:`Program.step` is the two back to back, as
``launch/train.run`` takes them; the window keeps steps in flight
between the two.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from chipbench import traffic as traffic_lib
from chipbench.spec import Cell, SpecError

# the config file's "model" numbers, and where the program keeps each
_MODEL_KEYS = {
    "num_layers": lambda c: c.num_layers,
    "d_model": lambda c: c.d_model,
    "vocab_size": lambda c: c.vocab_size,
    "num_heads": lambda c: c.attention.num_heads,
    "num_kv_heads": lambda c: c.attention.num_kv_heads,
    "head_dim": lambda c: c.head_dim,
    "rope_theta": lambda c: c.attention.rope_theta,
    "norm_eps": lambda c: c.norm_eps,
    "act": lambda c: c.act,
    "num_experts": lambda c: c.moe.num_experts,
    "gate": lambda c: c.moe.gate,
    "capacity_factor": lambda c: c.moe.capacity_factor,
    "d_ff_expert": lambda c: c.moe.d_ff_expert or c.d_ff,
    "aux_loss_weight": lambda c: c.moe.aux_loss_weight,
    "router_z_loss_weight": lambda c: c.moe.router_z_loss_weight,
    "dtype": lambda c: c.dtype,
    "router_dtype": lambda c: c.moe.router_dtype,
    "tie_embeddings": lambda c: c.tie_embeddings,
}


def _replace(obj, overrides: Dict[str, Any]):
    kw = {}
    for k, v in overrides.items():
        cur = getattr(obj, k)
        kw[k] = _replace(cur, v) if isinstance(v, dict) else v
    return dataclasses.replace(obj, **kw)


def model_config(cell: Cell):
    """The program's ``ModelConfig`` for the cell: the registered arch with
    the config's overrides and the cell's MoE path.  Raises where it
    differs from the config file's ``model`` numbers."""
    from repro import configs
    from repro.core import gating

    cfg = configs.get_config(cell.config["arch"])
    cfg = _replace(cfg, cell.config.get("overrides", {}))
    cfg = _replace(cfg, {"moe": cell.workload.get("moe", {})})
    want = cell.model
    got = {k: f(cfg) for k, f in _MODEL_KEYS.items()}
    got["experts_per_token"] = gating.gate_k(cfg.moe)
    bad = {k: (got[k], want.get(k)) for k in got if got[k] != want.get(k)}
    if bad or cfg.block_pattern != ("moe",) or cfg.moe.num_shared_experts:
        raise SpecError(f"{cell.name}: the program's config differs from "
                        f"{cell.config['name']}.json (program, file): {bad}")
    return cfg


def train_config(cell: Cell):
    from repro.core.config import TrainConfig
    return TrainConfig(**cell.workload["train"])


def canonical_leaves(params) -> Dict[str, Any]:
    """The program's parameter tree as ``{canonical name: array}``, one
    entry per layer of each stacked block leaf (``layers.<l>.attn.wq``),
    the names the reference uses."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        if keys[0] == "blocks":
            rest = ".".join(str(k) for k in keys[2:])
            for layer in range(leaf.shape[0]):
                out[f"layers.{layer}.{rest}"] = leaf[layer]
        else:
            out[".".join(str(k) for k in keys)] = leaf
    return out


@jax.jit
def _leaf_norms(tree):
    """Norm of each leaf; of each layer's slice for the stacked block
    leaves (leading layer axis)."""
    def norm(path, x):
        x = jnp.square(x.astype(jnp.float32))
        stacked = getattr(path[0], "key", None) == "blocks"
        return jnp.sqrt(jnp.sum(x, axis=tuple(range(1 if stacked else 0,
                                                      x.ndim))))
    return jax.tree_util.tree_map_with_path(norm, tree)


class NonFiniteRun(RuntimeError):
    """The trainer's non-finite streak reached its limit."""


class Program:
    """One cell's trainer on its mesh, with its compiled step."""

    def __init__(self, cell: Cell, seed: int, *, devices=None):
        from repro.launch import train

        self.cell = cell
        self.cfg = model_config(cell)
        self.tcfg = train_config(cell)
        n = int(np.prod(cell.mesh))
        devs = list(devices if devices is not None else jax.devices())[:n]
        self.mesh = jax.sharding.Mesh(np.asarray(devs).reshape(cell.mesh),
                                      ("data", "model"))
        self.ring = traffic_lib.make_ring(cell.traffic, self.cfg.vocab_size,
                                          seed)
        self.tr = train.build(self.cfg, self.tcfg, self.mesh,
                              batch=cell.batch, seq=cell.seq, seed=seed)
        self.state = self.tr.state
        b0 = self._place(0)
        self.compiled = self.tr.step.lower(
            self.state, b0, jax.random.fold_in(self.tr.rng, 0)).compile()
        self.devices = devs
        self.last: Dict[str, float] = {}

    def _place(self, s: int):
        return jax.device_put(self.ring[s % len(self.ring)],
                              self.tr.batch_sharding)

    @staticmethod
    def _mark(annotate: bool):
        if annotate:
            return lambda n: jax.profiler.TraceAnnotation("bench." + n)
        return lambda n: contextlib.nullcontext()

    def dispatch(self, s: int,
                 annotate: bool = False) -> Tuple[int, Dict[str, Any]]:
        """Send global step ``s``: the batch ``ring[s % len(ring)]`` and the
        key ``fold_in(rng, s)``, as ``launch/train.run`` keys its steps.
        Returns ``(s, metrics)``, the metrics still on the device.
        ``annotate`` marks each part in the profiler's trace as
        ``bench.<part>``."""
        mark = self._mark(annotate)
        with mark("place"):
            batch = self._place(s)
        with mark("dispatch"):
            self.state, m = self.compiled(self.state, batch,
                                          jax.random.fold_in(self.tr.rng, s))
        return s, m

    def fetch(self, sent: Tuple[int, Dict[str, Any]],
              annotate: bool = False) -> Dict[str, float]:
        """The metrics of a step that :meth:`dispatch` sent, on the host
        (this waits for the step), and the non-finite streak checked."""
        s, m = sent
        with self._mark(annotate)("fetch"):
            m = {k: float(v) for k, v in m.items()}
        self.last = m
        if m["nonfinite_streak"] >= self.tcfg.max_skipped_steps:
            raise NonFiniteRun(f"step {s}: {m['nonfinite_streak']:.0f} "
                               f"consecutive non-finite steps")
        return m

    def step(self, s: int) -> Dict[str, float]:
        """Global step ``s`` sent and its metrics fetched."""
        return self.fetch(self.dispatch(s))

    # -- what the correctness check reads from the program's state -------
    def params_to_host(self) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v) for k, v in
                canonical_leaves(jax.device_get(self.state.params)).items()}

    def first_moment_norms(self) -> Dict[str, float]:
        """Per-leaf norms of AdamW's first moment, which after the first
        step is ``(1 - b1)`` times the clipped gradient."""
        norms = jax.device_get(_leaf_norms(self.state.opt["m"]))
        return {k: float(v) for k, v in canonical_leaves(norms).items()}

    def free(self) -> None:
        """Drop the program's device state, so the reference has the chip."""
        self.state = None
        self.tr = None
        self.compiled = None
