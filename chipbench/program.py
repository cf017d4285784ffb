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

# the config file's "model" numbers that the program derives; every other
# key is an attribute of the same name on the ModelConfig, its attention
# or its MoE config
_DERIVED = {
    "head_dim": lambda c: c.head_dim,
    "d_ff_expert": lambda c: c.moe.d_ff_expert or c.d_ff,
    "experts_per_token": lambda c: _gate_k(c.moe),
}
# keys held to the paper model's value where a config file leaves them out
_IMPLIED = {"block_pattern": ["moe"], "num_shared_experts": 0}


def _gate_k(moe):
    from repro.core import gating
    return gating.gate_k(moe)


def _lookup(cfg, key: str):
    """The program's value of a config file's ``model`` key; ``KeyError``
    where the program has no such number."""
    if key in _DERIVED:
        return _DERIVED[key](cfg)
    for obj in (cfg, cfg.attention, cfg.moe):
        if obj is not None and hasattr(obj, key):
            value = getattr(obj, key)
            return list(value) if isinstance(value, tuple) else value
    raise KeyError(key)


def _replace(obj, overrides: Dict[str, Any]):
    kw = {}
    for k, v in overrides.items():
        cur = getattr(obj, k)
        kw[k] = _replace(cur, v) if isinstance(v, dict) else v
    return dataclasses.replace(obj, **kw)


def model_config(cell: Cell):
    """The program's ``ModelConfig`` for the cell: the registered arch with
    the config's overrides and the cell's MoE path.  Raises where a key of
    the config file's ``model`` is missing from it or differs."""
    from repro import configs

    cfg = configs.get_config(cell.config["arch"])
    cfg = _replace(cfg, cell.config.get("overrides", {}))
    cfg = _replace(cfg, {"moe": cell.workload.get("moe", {})})
    want = {**_IMPLIED, **cell.model}
    got = {}
    for k in want:
        try:
            got[k] = _lookup(cfg, k)
        except KeyError:
            raise SpecError(f"{cell.name}: {cell.config['name']}.json states "
                            f"{k!r}, which the program's config has not"
                            ) from None
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise SpecError(f"{cell.name}: the program's config differs from "
                        f"{cell.config['name']}.json (program, file): {bad}")
    return cfg


def train_config(cell: Cell):
    from repro.core.config import TrainConfig
    return TrainConfig(**cell.workload["train"])


def canonical_leaves(params) -> Dict[str, Any]:
    """The program's parameter tree as ``{canonical name: array}``, one
    entry per layer of each stacked block leaf (``layers.<l>.attn.wq``),
    the names the reference uses.  ``blocks`` holds one tree per position
    of the block pattern, each leaf stacked over the super-blocks, so the
    leaf of position ``j`` in super-block ``b`` is layer
    ``b * period + j``."""
    out = {}
    period = len(params.get("blocks", ()))
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        if keys[0] == "blocks":
            rest = ".".join(str(k) for k in keys[2:])
            for b in range(leaf.shape[0]):
                out[f"layers.{b * period + keys[1]}.{rest}"] = leaf[b]
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


@jax.jit
def _take_rows(table, ids):
    return table[ids]


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

    def first_moment_rows(self, ids: np.ndarray) -> np.ndarray:
        """Rows ``ids`` of AdamW's first moment of the embedding table, on
        the host.  They are gathered on the device, with ``ids`` padded to
        one step's tokens so that every seed gathers at the same shape."""
        n = self.cell.batch * self.cell.seq
        padded = np.pad(ids, (0, n - len(ids)), mode="edge")
        rows = _take_rows(self.state.opt["m"]["embed"], padded)
        return np.asarray(jax.device_get(rows))[:len(ids)]

    def free(self) -> None:
        """Drop the program's device state, so the reference has the chip."""
        self.state = None
        self.tr = None
        self.compiled = None
