"""Auto-tuned dispatch plans: resolve the grouped-path ``"auto"`` knobs
from the α–β cost model instead of hand-set config constants.

HetuMoE's headline wins come from *choosing* the communication strategy
per workload — hierarchical vs flat AllToAll, message aggregation,
batch-size-dependent crossovers (paper Figs. 5–8) — and the serving
stack compiles many distinct ``(cfg, mesh, shape)`` cells per process,
each of which deserves its own choice.  This module turns the
``MoEConfig`` sentinels (:data:`repro.core.config.AUTO` on ``a2a``,
``overlap_chunks``, ``grouped_block_m``, ``grouped_ep_bound_factor``,
``payload_dtype``)
into a frozen :class:`TunedPlan` per ``(cfg, mesh factoring, static
token count, dtype)`` cell, scored with the existing α–β cost functions
(``alltoall.cost_flat`` / ``cost_hierarchical`` / ``cost_pipelined``)
over a selectable fabric (a named ``LinkSpec`` pair from
``alltoall.FABRICS``, or a measure-once startup calibration persisted
to ``TUNE_moe.json``).

Contract (the reason resolution lives at choke points, not call sites):

* **Explicit values are honored verbatim.**  A config with no ``"auto"``
  knob passes through :func:`resolve_moe_config` as the SAME object —
  zero behaviour change, bitwise-identical graphs.
* **Resolution is deterministic** given (config, static shape, fabric):
  pure integer/float arithmetic, no RNG, no wall clock.  The same cell
  always resolves to the same plan, so ``"auto"`` never changes a traced
  graph shape mid-process and the serving step cache keys stay stable
  (``engine.trace_counts`` shows no new retraces).
* **The tuner never changes numerics.**  ``grouped_ep_bound_factor``
  resolves to ``None`` (truly dropless): a lossy bound drops tokens,
  which is a quality decision the user must make explicitly.

Choke points: ``moe.sharded_moe_apply`` / ``moe.validate_dispatch_config``
resolve at trace time (the per-shard token count is static there);
``serving/engine.py`` resolves at step-BUILD time so the resolved knobs
join the compiled-step cache key; ``launch/train.py`` / ``launch/serve.py``
select the mode and fabric via ``--tune auto|off|calibrate`` and
``--fabric`` (``launch/mesh.parse_fabric``).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, Optional, Tuple

from repro.core import alltoall, capacity
from repro.core.alltoall import LinkSpec
from repro.core.config import AUTO, MoEConfig

# knobs the resolver owns (a2a_inner rides along with a2a)
TUNED_KNOBS = ("a2a", "overlap_chunks", "grouped_block_m",
               "grouped_ep_bound_factor", "payload_dtype")
TUNE_MODES = ("auto", "off", "calibrate")

# payload_dtype="auto" quantizes the wire to int8 only when the α–β
# model predicts the exchange gets at least this much relatively
# cheaper.  Small (α-dominated) payloads never clear it — quantize/
# dequantize work plus the scales exchange would not pay for itself —
# and fp8 is never auto-picked: it is cheaper than int8 nowhere (same
# 1-byte wire) and strictly less accurate, so it stays an explicit
# opt-in for hardware with native fp8 convert paths.
QUANT_MIN_SAVING = 0.15

# overlap_chunks candidate ladder (filtered to divisors of the bound)
OVERLAP_LADDER = (1, 2, 4, 8)

# nominal compute throughput used to estimate the expert-FFN time the
# overlap pipeline can hide (v5e-class bf16 peak; only the RATIO of
# compute to exchange time matters, and both scale with the same d)
NOMINAL_FLOPS = 2.0e14

# measure-once calibration artifact (machine-local, not committed)
TUNE_SCHEMA = "tune_moe/v1"
TUNE_PATH = pathlib.Path(__file__).resolve().parents[3] / "TUNE_moe.json"


def has_auto_knobs(cfg: MoEConfig) -> bool:
    """True iff any tuner-owned knob carries the ``"auto"`` sentinel."""
    return any(getattr(cfg, k) == AUTO for k in TUNED_KNOBS)


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """One resolved cell: the concrete knob values plus the cost-model
    evidence they were chosen on (for benchmarks/lint reporting).  Costs
    are α–β seconds for ONE dispatch-exchange at the cell's payload;
    ``cost_serial`` / ``cost_overlapped`` are the full
    dispatch+compute+combine layer times at P=1 and the chosen P."""
    a2a: str
    a2a_inner: int
    overlap_chunks: int
    grouped_block_m: Optional[int]
    grouped_ep_bound_factor: Optional[float]
    payload_dtype: Optional[str]
    fabric: str
    payload_bytes: int
    cost_flat: float
    cost_chosen: float
    cost_serial: float
    cost_overlapped: float


# ---------------------------------------------------------------------------
# process-wide tuning state (set from the CLI; tests save/restore)
# ---------------------------------------------------------------------------

_MODE: str = "auto"
_FABRIC: Tuple[str, Tuple[LinkSpec, LinkSpec]] = (
    "ici_dcn", alltoall.FABRICS["ici_dcn"])

# (cfg, statics, mode, fabric) → TunedPlan / resolved MoEConfig.  Keys
# hash frozen dataclasses; the mode+fabric components make a CLI change
# a clean cache split, never a stale hit.
_PLAN_CACHE: Dict[tuple, TunedPlan] = {}
_CFG_CACHE: Dict[tuple, MoEConfig] = {}


def set_tuning(mode: Optional[str] = None, fabric=None):
    """Set the process tuning mode and/or default fabric.  Returns the
    previous ``(mode, fabric)`` pair so tests can restore it.

    ``fabric`` is ``(name, (fast, slow))`` — the :func:`parse_fabric`
    return shape — or a bare name from ``alltoall.FABRICS``."""
    global _MODE, _FABRIC
    prev = (_MODE, _FABRIC)
    if mode is not None:
        if mode not in ("auto", "off"):
            raise ValueError(
                f"tuning mode must be 'auto' or 'off' (calibrate is a CLI "
                f"action, not a steady state), got {mode!r}")
        _MODE = mode
    if fabric is not None:
        _FABRIC = _coerce_fabric(fabric)
    return prev


def get_tuning() -> Tuple[str, Tuple[str, Tuple[LinkSpec, LinkSpec]]]:
    return _MODE, _FABRIC


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _CFG_CACHE.clear()


def _coerce_fabric(fabric) -> Tuple[str, Tuple[LinkSpec, LinkSpec]]:
    if isinstance(fabric, str):
        if fabric not in alltoall.FABRICS:
            raise ValueError(
                f"unknown fabric {fabric!r}; valid fabrics: "
                f"{tuple(alltoall.FABRICS)}")
        return fabric, alltoall.FABRICS[fabric]
    name, pair = fabric
    fast, slow = pair
    return str(name), (fast, slow)


# ---------------------------------------------------------------------------
# the resolver
# ---------------------------------------------------------------------------

def _dtype_bytes(dtype) -> int:
    """Itemsize of the compute dtype the payload is exchanged at.

    ``None`` is an error, not a default: silently assuming bf16 (2
    bytes) mis-scored an f32 run's flat-vs-hierarchical payload by 2×.
    The choke points (``moe.sharded_moe_apply``, the serving step
    builders) always know the concrete activation dtype — they must
    pass it."""
    if dtype is None:
        raise ValueError(
            "_dtype_bytes(None): plan resolution needs the concrete "
            "activation dtype (payload bytes scale α–β costs); pass "
            "dtype=x.dtype at the choke point instead of relying on a "
            "bf16 guess")
    import numpy as np
    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:
        import jax.numpy as jnp
        return int(jnp.dtype(dtype).itemsize)


def _round_up(n: int, align: int = 8) -> int:
    return -(-n // align) * align


def _ffn_seconds(cfg: MoEConfig, rows: int, d_model: int) -> float:
    """Rough expert-FFN time for ``rows`` dispatched rows: 3 matmuls
    (gate/up/out) of d×f each, 2 FLOPs per MAC, at NOMINAL_FLOPS.  Only
    its magnitude relative to the α–β exchange time matters — both are
    coarse models of the same hardware generation."""
    f = cfg.d_ff_expert or 4 * d_model
    return rows * d_model * f * 3 * 2 / NOMINAL_FLOPS


def _factoring(model_size: int, inner: int) -> Tuple[int, int]:
    """(N, G) nodes × GPUs for the α–β functions: G = the fast inner
    group, N = the slow outer dimension."""
    if 1 < inner < model_size and model_size % inner == 0:
        return model_size // inner, inner
    return model_size, 1


def resolve_plan(cfg: MoEConfig, *, model_size: int, tokens_per_shard: int,
                 d_model: int, dtype=None, fabric=None) -> TunedPlan:
    """Resolve one ``(cfg, model_size, tokens_per_shard, d_model, dtype)``
    cell into a frozen :class:`TunedPlan`.  Deterministic and cached;
    given a concrete ``dtype`` it never raises for a valid config (the
    knobs it emits always pass ``moe.validate_dispatch_config``).

    Auto payload policy: ``payload_dtype="auto"`` resolves to
    ``"int8"`` iff the α–β model predicts the 1-byte wire makes the
    flat dispatch exchange at least :data:`QUANT_MIN_SAVING` relatively
    cheaper than at the compute dtype, else ``None`` (lossless).  fp8
    is explicit-only — see the QUANT_MIN_SAVING note."""
    mode, default_fab = get_tuning()
    fab_name, (fast, slow) = (_coerce_fabric(fabric) if fabric is not None
                              else default_fab)
    isz = _dtype_bytes(dtype)
    key = (cfg, model_size, tokens_per_shard, d_model, isz, mode,
           fab_name, fast, slow)
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        return plan

    # knob 1 — grouped_ep_bound_factor: AUTO → None (never lossy).
    factor = (None if cfg.grouped_ep_bound_factor == AUTO
              else cfg.grouped_ep_bound_factor)
    base = dataclasses.replace(
        cfg, grouped_ep_bound_factor=factor, a2a="flat", a2a_inner=1,
        overlap_chunks=1, grouped_block_m=None)

    T = int(tokens_per_shard)
    grouped = cfg.dispatch == "grouped"
    ep = grouped and model_size > 1
    if ep:
        B = capacity.grouped_segment_bound(base, T, model_size)
        buffer_rows = model_size * B
        payload = model_size * B * d_model * isz
    elif grouped:
        B = capacity.grouped_tp_gather_bound(base, T)
        buffer_rows = B
        payload = 0                  # TP gather, no EP exchange to tune
    else:
        E = cfg.num_experts
        C = capacity.expert_capacity(base, T, E)
        B = 0
        buffer_rows = E * C
        payload = (E * C * d_model * isz) if model_size > 1 else 0

    # knob 0 — payload_dtype: only the grouped-EP exchange quantizes;
    # everywhere else (TP gather, dense dispatch, model_size == 1) AUTO
    # resolves to None.  In auto mode, quantize iff the predicted
    # relative saving of the 1-byte wire clears QUANT_MIN_SAVING —
    # α-dominated (small) payloads stay lossless.
    qdt = None if cfg.payload_dtype == AUTO else cfg.payload_dtype
    if cfg.payload_dtype == AUTO and mode != "off" and ep and payload:
        full_c = alltoall.cost_flat(payload, model_size, 1, fast, slow)
        quant_c = alltoall.cost_flat(payload // isz, model_size, 1,
                                     fast, slow)
        if full_c > 0 and (full_c - quant_c) / full_c >= QUANT_MIN_SAVING:
            qdt = "int8"
    if qdt is not None and ep:
        payload = payload // isz     # every wire dtype is 1 byte

    if mode == "off":
        # pre-refactor defaults, no cost model consulted
        plan = TunedPlan(a2a="flat", a2a_inner=1, overlap_chunks=1,
                         grouped_block_m=None, grouped_ep_bound_factor=factor,
                         payload_dtype=qdt,
                         fabric=fab_name, payload_bytes=payload,
                         cost_flat=0.0, cost_chosen=0.0,
                         cost_serial=0.0, cost_overlapped=0.0)
        _PLAN_CACHE[key] = plan
        return plan

    # knob 2 — a2a mode (+ inner): for every two-stage factoring of the
    # model axis, score flat AND hierarchical at the SAME (N, G) — the
    # paper's Fig. 7 comparison, where the fast inner fabric is a mesh
    # property both strategies see.  Hierarchical wins only when
    # strictly cheaper at its best factoring (ties go flat — fewer
    # collectives, same cost).
    flat_cost = (alltoall.cost_flat(payload, model_size, 1, fast, slow)
                 if payload else 0.0)
    a2a_mode, a2a_inner = "flat", 1
    chosen_cost = flat_cost
    if cfg.a2a == AUTO:
        if payload:
            best = None                  # (hier_cost, flat_at_same_NG, inner)
            for inner in range(2, model_size):
                if model_size % inner:
                    continue
                N, G = model_size // inner, inner
                hc = alltoall.cost_hierarchical(payload, N, G, fast, slow)
                if best is None or hc < best[0]:
                    best = (hc, alltoall.cost_flat(payload, N, G, fast,
                                                   slow), inner)
            if best is not None:
                flat_cost = best[1]
                if best[0] < flat_cost:
                    a2a_mode, a2a_inner = "hierarchical", best[2]
                    chosen_cost = best[0]
                else:
                    chosen_cost = flat_cost
    else:
        a2a_mode, a2a_inner = cfg.a2a, cfg.a2a_inner
        N, G = _factoring(model_size, a2a_inner if a2a_mode == "hierarchical"
                          else 1)
        if payload:
            flat_cost = alltoall.cost_flat(payload, N, G, fast, slow)
            chosen_cost = (alltoall.cost_hierarchical(payload, N, G, fast,
                                                      slow)
                           if G > 1 else flat_cost)
    N, G = _factoring(model_size, a2a_inner if a2a_mode == "hierarchical"
                      else 1)
    cost_fn = (alltoall.cost_hierarchical if G > 1 else alltoall.cost_flat)

    # knob 3 — overlap_chunks: divisor ladder, argmin of the pipelined
    # layer time (2× exchange + FFN, fill/drain exposed) — only the
    # grouped-EP path has an exchange to hide.
    ffn_s = _ffn_seconds(cfg, buffer_rows, d_model) if grouped else 0.0
    serial = 2 * chosen_cost + ffn_s

    def pipe_cost(P: int) -> float:
        if P <= 1:
            return serial
        return alltoall.cost_pipelined(payload, N, G, fast, slow,
                                       n_chunks=P, compute_s=ffn_s,
                                       cost_fn=cost_fn)

    overlap = 1
    if cfg.overlap_chunks == AUTO:
        if ep and payload:
            best = serial
            for P in OVERLAP_LADDER:
                if P > 1 and B % P == 0 and pipe_cost(P) < best:
                    overlap, best = P, pipe_cost(P)
    else:
        overlap = cfg.overlap_chunks
    overlapped = pipe_cost(overlap)

    # knob 4 — grouped_block_m: clamp the kernel row block to the
    # per-window buffer (sublane-aligned) so tiny decode windows stop
    # padding to a full default block.
    if cfg.grouped_block_m == AUTO:
        if grouped:
            from repro.kernels.grouped_ffn import DEFAULT_BLOCK_M
            window_rows = buffer_rows // max(overlap, 1)
            block_m = max(8, min(DEFAULT_BLOCK_M, _round_up(window_rows)))
        else:
            block_m = None
    else:
        block_m = cfg.grouped_block_m

    plan = TunedPlan(a2a=a2a_mode, a2a_inner=a2a_inner,
                     overlap_chunks=overlap, grouped_block_m=block_m,
                     grouped_ep_bound_factor=factor,
                     payload_dtype=qdt, fabric=fab_name,
                     payload_bytes=payload, cost_flat=flat_cost,
                     cost_chosen=chosen_cost, cost_serial=serial,
                     cost_overlapped=overlapped)
    _PLAN_CACHE[key] = plan
    return plan


def apply_plan(cfg: MoEConfig, plan: TunedPlan) -> MoEConfig:
    """The concrete config: plan values fill ONLY the ``"auto"`` fields
    (explicit values are honored verbatim)."""
    kw = {}
    if cfg.a2a == AUTO:
        kw["a2a"] = plan.a2a
        kw["a2a_inner"] = plan.a2a_inner
    if cfg.overlap_chunks == AUTO:
        kw["overlap_chunks"] = plan.overlap_chunks
    if cfg.grouped_block_m == AUTO:
        kw["grouped_block_m"] = plan.grouped_block_m
    if cfg.grouped_ep_bound_factor == AUTO:
        kw["grouped_ep_bound_factor"] = plan.grouped_ep_bound_factor
    if cfg.payload_dtype == AUTO:
        kw["payload_dtype"] = plan.payload_dtype
    return dataclasses.replace(cfg, **kw) if kw else cfg


def resolve_moe_config(cfg: MoEConfig, *, model_size: int,
                       tokens_per_shard: int, d_model: int,
                       dtype=None, fabric=None) -> MoEConfig:
    """``cfg`` with every ``"auto"`` knob resolved for this cell.  A
    config with no autos is returned as the SAME object (bitwise
    pass-through); resolved configs are memoized so repeated step builds
    hand the cache identical keys."""
    if not has_auto_knobs(cfg):
        return cfg
    mode, (fab_name, _) = get_tuning()
    key = (cfg, model_size, int(tokens_per_shard), int(d_model),
           _dtype_bytes(dtype), mode, fab_name, fabric)
    out = _CFG_CACHE.get(key)
    if out is None:
        plan = resolve_plan(cfg, model_size=model_size,
                            tokens_per_shard=tokens_per_shard,
                            d_model=d_model, dtype=dtype, fabric=fabric)
        out = apply_plan(cfg, plan)
        _CFG_CACHE[key] = out
    return out


def describe_resolution(auto_cfg: MoEConfig, resolved: MoEConfig) -> str:
    """Human-readable "what did 'auto' become" — appended to validation
    errors so they name the RESOLVED values, not the sentinel."""
    parts = []
    if auto_cfg.a2a == AUTO:
        parts.append(f"a2a={resolved.a2a!r} (a2a_inner="
                     f"{resolved.a2a_inner})")
    if auto_cfg.overlap_chunks == AUTO:
        parts.append(f"overlap_chunks={resolved.overlap_chunks}")
    if auto_cfg.grouped_block_m == AUTO:
        parts.append(f"grouped_block_m={resolved.grouped_block_m}")
    if auto_cfg.grouped_ep_bound_factor == AUTO:
        parts.append(
            f"grouped_ep_bound_factor={resolved.grouped_ep_bound_factor}")
    if auto_cfg.payload_dtype == AUTO:
        parts.append(f"payload_dtype={resolved.payload_dtype!r}")
    return "auto-tuned: resolved " + ", ".join(parts) if parts else ""


# ---------------------------------------------------------------------------
# measure-once startup calibration (--tune calibrate)
# ---------------------------------------------------------------------------

def fit_alpha_beta(points) -> LinkSpec:
    """Least-squares fit of ``time = α + β·bytes`` over ``(bytes, s)``
    samples, clamped positive (a throttled box can fit a negative slope
    on two noisy points; the cost functions need monotone specs)."""
    import numpy as np
    pts = [(float(b), float(t)) for b, t in points]
    if len(pts) < 2:
        raise ValueError(
            f"fit_alpha_beta needs >= 2 (bytes, seconds) samples, got "
            f"{len(pts)}")
    b = np.array([p[0] for p in pts])
    t = np.array([p[1] for p in pts])
    A = np.stack([np.ones_like(b), b], axis=1)
    (alpha, beta), *_ = np.linalg.lstsq(A, t, rcond=None)
    return LinkSpec(alpha=float(max(alpha, 1e-9)),
                    beta=float(max(beta, 1e-15)))


def _measure_a2a(mesh, axis_name: str, rows: int, d: int, *,
                 iters: int = 5) -> float:
    """Median wall seconds of one jitted flat AllToAll of (M·rows, d)
    f32 over ``axis_name``."""
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    M = mesh.shape[axis_name]
    x = jnp.zeros((M * rows, d), jnp.float32)
    fn = jax.jit(jax.shard_map(
        lambda v: alltoall.flat_all_to_all(v, axis_name), mesh=mesh,
        in_specs=P(axis_name), out_specs=P(axis_name), check_vma=False))
    jax.block_until_ready(fn(x))
    times = []
    for _ in range(iters):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(x))
        times.append(_time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def save_calibration(path, fast: LinkSpec, slow: LinkSpec,
                     points=None) -> None:
    doc = {"schema": TUNE_SCHEMA,
           "fast": {"alpha": fast.alpha, "beta": fast.beta},
           "slow": {"alpha": slow.alpha, "beta": slow.beta},
           "points": [[float(b), float(t)] for b, t in (points or [])]}
    pathlib.Path(path).write_text(json.dumps(doc, indent=2) + "\n")


def load_calibration(path=None):
    """``("calibrated", (fast, slow))`` from a TUNE_moe.json, or ``None``
    when the file is missing, unreadable, schema-mismatched, or carries
    non-positive constants — every failure mode falls back to the static
    ``alltoall.FABRICS`` table, never raises."""
    p = pathlib.Path(path) if path is not None else TUNE_PATH
    try:
        doc = json.loads(p.read_text())
        if doc.get("schema") != TUNE_SCHEMA:
            return None
        specs = []
        for level in ("fast", "slow"):
            alpha = float(doc[level]["alpha"])
            beta = float(doc[level]["beta"])
            if alpha <= 0 or beta <= 0:
                return None
            specs.append(LinkSpec(alpha=alpha, beta=beta))
        return "calibrated", (specs[0], specs[1])
    except (OSError, ValueError, KeyError, TypeError):
        return None


def calibrate_fabric(mesh=None, *, axis_name: str = "model", path=None,
                     remeasure: bool = False):
    """Measure-once α–β calibration: reuse an intact ``TUNE_moe.json``
    when present, else benchmark a handful of flat-AllToAll payloads on
    ``mesh`` and fit.  On a single-fabric host (this container's fake
    CPU devices, or a mesh with no ``axis_name``) the one measured level
    serves as both fast and slow — strategy crossovers then come only
    from message counts, which is the honest statement of what was
    measurable.  Returns ``(name, (fast, slow))`` and persists it."""
    p = pathlib.Path(path) if path is not None else TUNE_PATH
    if not remeasure:
        loaded = load_calibration(p)
        if loaded is not None:
            return loaded
    if mesh is None or mesh.shape.get(axis_name, 1) <= 1:
        # nothing to exchange across — persist the static default so the
        # artifact's provenance is explicit
        fast, slow = get_tuning()[1][1]
        save_calibration(p, fast, slow)
        return "calibrated", (fast, slow)
    d = 128
    points = [(mesh.shape[axis_name] * rows * d * 4,
               _measure_a2a(mesh, axis_name, rows, d))
              for rows in (8, 64, 512)]
    spec = fit_alpha_beta(points)
    save_calibration(p, spec, spec, points)
    return "calibrated", (spec, spec)


def configure(mode: str = "auto", fabric=None, *, mesh=None,
              path=None) -> Tuple[str, str]:
    """CLI entry for ``--tune``/``--fabric`` (train.py / serve.py).
    Returns ``(mode, fabric_name)`` for the launcher's banner."""
    if mode not in TUNE_MODES:
        raise ValueError(
            f"--tune must be one of {TUNE_MODES}, got {mode!r}")
    if mode == "off":
        set_tuning(mode="off", fabric=fabric)
        return "off", get_tuning()[1][0]
    if mode == "calibrate":
        fab = calibrate_fabric(mesh, path=path)
    else:
        fab = fabric
    set_tuning(mode="auto", fabric=fab)
    return mode, get_tuning()[1][0]
