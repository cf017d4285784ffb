"""The rule registry and the shipped graph-invariant rules.

Every rule encodes a hazard this repo has ALREADY hit (the PR number is
the regression it guards), expressed over the structured walkers in
``analysis.graph`` / ``analysis.hlo`` instead of jaxpr substring greps:

  collective-in-loop      PR 5: ``lax.scan`` folded the P pipelined
                          exchanges into ONE loop-body collective,
                          hiding the overlap from XLA's scheduler.
  overlap-chunk-count     PR 5: the pipeline must emit exactly 3P flat /
                          5P hierarchical all-to-alls with (M, B/P, d)
                          payload windows for ``overlap_chunks = P``.
  tuned-plan-consistency  PR 9: a graph traced under an "auto"-knob
                          config must carry exactly the AllToAll
                          count/payload windows of the TunedPlan
                          ``core/tuning.py`` resolves for that cell —
                          "auto" must never silently change a traced
                          graph shape.
  no-recompute-backward   PR 3: the grouped backward must run the Pallas
                          dlhs/drhs kernels off the residuals — a
                          ``ragged_dot`` in a grad graph is the VJP
                          re-running the whole forward.
  dtype-leak              PR 4: ``ragged_dot``'s transpose leaked f32
                          cotangents into bf16 dots (f32 compute, 2×
                          bytes) — mixed float operand dtypes on a
                          dot-like equation mean a missing cast.
  payload-dtype           PR 10: the grouped exchange's payload
                          AllToAlls must move the RESOLVED wire dtype
                          (int8/fp8 when ``payload_dtype`` is set, the
                          compute dtype otherwise), and no quantized
                          wire dtype may reach a dot-like equation —
                          dequantization happens between the exchange
                          and the matmul, never inside it.
  donation-alias          PR 6: donated ``TrainState`` leaves sharing a
                          buffer make XLA donation reject the alias.
  retrace-budget          PR 7: each serving step-builder key traces
                          once; more means a compiled-step cache leak.
  config-invalid          a config × mesh cell the validators reject
                          (``moe.validate_dispatch_config`` /
                          ``engine.validate_decode_config``) — the lint
                          CLI reports the rejection as a finding instead
                          of dying on a traceback.

New-graph-invariant convention (ROADMAP process note): a new rule ships
with a KNOWN-BAD case in ``tests/test_analysis.py`` that makes it fire,
plus the clean config matrix proving it stays quiet on healthy graphs.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from repro.analysis.graph import (RAGGED_DOT_PRIMITIVES, EqnSite, JaxprGraph,
                                  ProbeGraph)

LEVELS = ("error", "warn", "info")

# jaxpr primitive names that move data across mesh ranks (the psum-like
# reductions included: any of these inside a loop body serializes the
# pipeline the same way)
COLLECTIVE_PRIMITIVES = frozenset({
    "all_to_all", "all_gather", "all_gather_invariant", "psum",
    "psum_invariant", "psum_scatter", "reduce_scatter", "ppermute",
    "pgather", "pmax", "pmin",
})

# dot-like primitives whose operand dtypes must agree (group_sizes /
# index operands are integral and exempt)
DOT_PRIMITIVES = frozenset({"dot_general"}) | RAGGED_DOT_PRIMITIVES


@dataclass(frozen=True)
class Finding:
    """One lint hit.  ``location`` is a structural path
    (``shard_map/scan/all_to_all``) or a probe key; ``config`` is the
    matrix cell / graph label it was found under."""
    rule: str
    level: str
    location: str
    message: str
    config: str = ""

    def as_dict(self) -> Dict[str, str]:
        return {"rule": self.rule, "level": self.level,
                "location": self.location, "message": self.message,
                "config": self.config}


@dataclass(frozen=True)
class Rule:
    name: str
    level: str
    kinds: Tuple[str, ...]                  # graph kinds it applies to
    check: Callable[[Any], List[Finding]]   # Graph -> findings
    doc: str = ""


REGISTRY: Dict[str, Rule] = {}


def register(name: str, level: str, kinds: Tuple[str, ...]):
    """Decorator: register ``check(graph) -> [Finding]`` under ``name``.

    The wrapped checker may return ``Finding`` dicts without ``rule`` /
    ``level`` filled; they are stamped here so a rule cannot misreport
    its own identity.
    """
    if level not in LEVELS:
        raise ValueError(f"rule {name!r}: level must be one of {LEVELS}, "
                         f"got {level!r}")

    def wrap(fn: Callable) -> Callable:
        def check(graph) -> List[Finding]:
            out = []
            for f in fn(graph):
                if isinstance(f, Finding):
                    out.append(Finding(name, level, f.location, f.message,
                                       f.config or graph.label))
                else:  # (location, message) shorthand
                    loc, msg = f
                    out.append(Finding(name, level, loc, msg, graph.label))
            return out
        REGISTRY[name] = Rule(name, level, kinds, check, doc=fn.__doc__ or "")
        return fn
    return wrap


def rules_for(kind: str, names: Optional[Iterable[str]] = None) -> List[Rule]:
    wanted = set(names) if names is not None else None
    unknown = (wanted or set()) - set(REGISTRY)
    if unknown:
        raise ValueError(f"unknown lint rule(s) {sorted(unknown)}; "
                         f"registered: {sorted(REGISTRY)}")
    return [r for r in REGISTRY.values()
            if kind in r.kinds and (wanted is None or r.name in wanted)]


def run_rule(name: str, graph) -> List[Finding]:
    """Run ONE registered rule against a graph (the test-suite entry
    point for porting the old substring witnesses)."""
    if name not in REGISTRY:
        raise ValueError(f"unknown lint rule {name!r}; "
                         f"registered: {sorted(REGISTRY)}")
    return REGISTRY[name].check(graph)


def lint_graph(graph, rules: Optional[Iterable[str]] = None) -> List[Finding]:
    out: List[Finding] = []
    for rule in rules_for(graph.kind, rules):
        out.extend(rule.check(graph))
    return out


# ---------------------------------------------------------------------------
# shipped rules — jaxpr side
# ---------------------------------------------------------------------------

@register("collective-in-loop", "error", ("jaxpr", "hlo"))
def _collective_in_loop(graph) -> List:
    """A cross-rank collective inside a ``scan``/``while`` BODY.  XLA
    schedules one loop iteration at a time, so a collective folded into
    a loop body cannot overlap the next iteration's compute — exactly
    how the PR 5 pipeline silently lost its overlap when written as a
    ``fori_loop``.  The dispatch graphs this linter traces unroll every
    pipelined exchange statically; a per-layer scan over super-blocks is
    a different (whole-model) graph and is not linted by the matrix.
    """
    if graph.context.get("allow_loop_collectives"):
        return []
    out = []
    if graph.kind == "hlo":
        for site in graph.collectives():
            if site.in_loop:
                out.append((f"{site.computation}/{site.op.kind}",
                            f"HLO collective {site.op.kind!r} executes "
                            f"inside a while body "
                            f"(×{site.multiplier:.0f} trip multiplier) — "
                            f"it re-issues every iteration and cannot "
                            f"overlap the pipeline"))
        return out
    for site in graph.sites():
        if site.primitive in COLLECTIVE_PRIMITIVES and site.loop_depth > 0:
            out.append((site.describe(),
                        f"collective {site.primitive!r} traced inside a "
                        f"loop body (depth {site.loop_depth}, "
                        f"trip×{site.trip}) — a statically-unrolled "
                        f"pipeline must keep its exchanges out of "
                        f"scan/while bodies"))
    return out


def _payload_sites(graph: JaxprGraph, model_size: int, chunk_rows: int,
                   d_model: int) -> List[EqnSite]:
    """The all_to_all sites that move an (…, chunk_rows, d_model) token
    window across ``model_size`` ranks — hierarchical stages reshape the
    leading rank axis (M,) → (inner, outer), so match on the trailing
    window shape plus the leading-axis product."""
    out = []
    for site in graph.find("all_to_all"):
        shapes = site.out_shapes
        if not shapes:
            continue
        s = shapes[0]
        if (len(s) >= 3 and s[-1] == d_model and s[-2] == chunk_rows
                and int(np.prod(s[:-2])) == model_size):
            out.append(site)
    return out


@register("overlap-chunk-count", "error", ("jaxpr",))
def _overlap_chunk_count(graph: JaxprGraph) -> List:
    """The grouped dispatch path with ``overlap_chunks = P`` must emit
    exactly ``moe.expected_grouped_a2a_eqns(cfg, model_size)`` separate
    ``all_to_all`` equations — P × (1 counts + stages dispatch + stages
    combine) — and the payload exchanges must move (M, B/P, d) windows,
    not the full bound.  Fewer equations means the pipeline collapsed
    (scan-folded or short-circuited); full-bound payloads mean the
    windows never actually split.  Applies to forward graphs traced with
    ``cfg``/``model_size``/``tokens_per_shard``/``d_model`` context.
    """
    from repro.core import capacity
    from repro.core import moe as moe_lib

    from repro.core import tuning

    ctx = graph.context
    cfg = ctx.get("cfg")
    model_size = int(ctx.get("model_size", 1))
    if (cfg is None or cfg.dispatch != "grouped" or model_size <= 1
            or ctx.get("direction", "fwd") != "fwd"
            or tuning.has_auto_knobs(cfg)):
        # "auto"-knob cells are owned by tuned-plan-consistency, which
        # resolves the sentinels the same way the trace did
        return []
    expected = moe_lib.expected_grouped_a2a_eqns(cfg, model_size)
    got = graph.count("all_to_all")
    out = []
    if got != expected:
        out.append(("all_to_all",
                    f"grouped dispatch with overlap_chunks="
                    f"{cfg.overlap_chunks}, a2a={cfg.a2a!r} must emit "
                    f"{expected} all_to_all equations, traced {got} — "
                    f"the overlap pipeline folded or short-circuited"))
    T = ctx.get("tokens_per_shard")
    d = ctx.get("d_model")
    if T is None or d is None:
        return out
    B = (capacity.grouped_segment_bound(cfg, int(T), model_size))
    P = cfg.overlap_chunks
    if B % P:
        return out            # bound validation owns this failure mode
    stages = moe_lib.grouped_a2a_stages(cfg, model_size)
    payload = _payload_sites(graph, model_size, B // P, int(d))
    want_payload = 2 * stages * P
    if len(payload) != want_payload:
        out.append(("all_to_all",
                    f"expected {want_payload} payload all_to_all "
                    f"equations moving ({model_size}, {B // P}, {d}) "
                    f"windows (bound B={B}, P={P}), found "
                    f"{len(payload)} — the microchunk windows did not "
                    f"split the bound"))
    return out


@register("tuned-plan-consistency", "error", ("jaxpr",))
def _tuned_plan_consistency(graph: JaxprGraph) -> List:
    """A graph traced under an ``"auto"``-knob config must match the
    knobs ``core/tuning.py`` resolves for that cell: exactly
    ``moe.expected_grouped_a2a_eqns(resolved, M)`` ``all_to_all``
    equations, whose payload exchanges move the resolved plan's
    ``(M, B/P, d)`` windows.  A mismatch means the trace and the tuner
    disagreed — a non-deterministic resolver, a code path reading the
    sentinel directly, or a stale plan cache — i.e. ``"auto"`` silently
    changed a traced graph shape.  Applies to forward grouped-EP graphs
    traced with ``cfg``/``model_size``/``tokens_per_shard``/``d_model``
    context where ``cfg`` carries a sentinel (PR 9 convention: concrete
    configs stay owned by ``overlap-chunk-count``).
    """
    from repro.core import capacity
    from repro.core import moe as moe_lib
    from repro.core import tuning

    ctx = graph.context
    cfg = ctx.get("cfg")
    model_size = int(ctx.get("model_size", 1))
    T = ctx.get("tokens_per_shard")
    d = ctx.get("d_model")
    if (cfg is None or not tuning.has_auto_knobs(cfg)
            or cfg.dispatch != "grouped" or model_size <= 1
            or ctx.get("direction", "fwd") != "fwd"
            or T is None or d is None):
        return []
    rcfg = tuning.resolve_moe_config(
        cfg, model_size=model_size, tokens_per_shard=int(T),
        d_model=int(d), dtype=ctx.get("dtype"))
    expected = moe_lib.expected_grouped_a2a_eqns(rcfg, model_size)
    got = graph.count("all_to_all")
    out = []
    if got != expected:
        out.append(("all_to_all",
                    f"resolved TunedPlan (a2a={rcfg.a2a!r}, a2a_inner="
                    f"{rcfg.a2a_inner}, overlap_chunks="
                    f"{rcfg.overlap_chunks}) expects {expected} "
                    f"all_to_all equations, traced {got} — the graph "
                    f"does not match what the tuner resolved for this "
                    f"cell"))
    B = capacity.grouped_segment_bound(rcfg, int(T), model_size)
    P = rcfg.overlap_chunks
    if B % P:
        return out
    stages = moe_lib.grouped_a2a_stages(rcfg, model_size)
    payload = _payload_sites(graph, model_size, B // P, int(d))
    want_payload = 2 * stages * P
    if len(payload) != want_payload:
        out.append(("all_to_all",
                    f"resolved TunedPlan expects {want_payload} payload "
                    f"all_to_all equations moving ({model_size}, "
                    f"{B // P}, {d}) windows (bound B={B}, P={P}), "
                    f"found {len(payload)} — the traced windows differ "
                    f"from the resolved plan"))
    return out


@register("payload-dtype", "error", ("jaxpr",))
def _payload_dtype_rule(graph: JaxprGraph) -> List:
    """The grouped exchange's payload AllToAll element type must match
    the RESOLVED config: the quantized wire dtype (int8 / fp8) when
    ``payload_dtype`` is set, the compute dtype when it is ``None`` — a
    payload-shaped exchange at the wrong element type means the
    quantize/dequantize pair was dropped (full-width wire, no β saving)
    or never undone (silent low-precision compute).  When quantized, no
    dot-like equation may consume the wire dtype directly: dequant
    happens between the exchange and the grouped matmuls, which keep
    accumulating in f32.  Applies to forward grouped-EP graphs traced
    with ``cfg``/``model_size``/``tokens_per_shard``/``d_model``/
    ``dtype`` context.
    """
    import jax.numpy as jnp

    from repro.core import alltoall, capacity, tuning

    ctx = graph.context
    cfg = ctx.get("cfg")
    model_size = int(ctx.get("model_size", 1))
    T = ctx.get("tokens_per_shard")
    d = ctx.get("d_model")
    if (cfg is None or cfg.dispatch != "grouped" or model_size <= 1
            or ctx.get("direction", "fwd") != "fwd"
            or T is None or d is None):
        return []
    rcfg = cfg
    if tuning.has_auto_knobs(cfg):
        if ctx.get("dtype") is None:
            return []                 # cannot resolve without the dtype
        rcfg = tuning.resolve_moe_config(
            cfg, model_size=model_size, tokens_per_shard=int(T),
            d_model=int(d), dtype=ctx.get("dtype"))
    if rcfg.payload_dtype is not None:
        wire = jnp.dtype(alltoall._payload_jnp_dtype(rcfg.payload_dtype))
    elif ctx.get("dtype") is not None:
        wire = jnp.dtype(ctx["dtype"])
    else:
        return []                     # nothing concrete to assert against
    B = capacity.grouped_segment_bound(rcfg, int(T), model_size)
    P = rcfg.overlap_chunks
    if B % P:
        return []                     # bound validation owns this cell
    out = []
    for site in _payload_sites(graph, model_size, B // P, int(d)):
        got = jnp.dtype(site.out_avals[0].dtype)
        if got != wire:
            out.append((site.describe(),
                        f"payload all_to_all emitted {got.name}, but the "
                        f"resolved payload_dtype="
                        f"{rcfg.payload_dtype!r} requires {wire.name} on "
                        f"the wire — the quantize/dequantize pair is "
                        f"missing or misplaced"))
    if rcfg.payload_dtype is not None:
        for site in graph.sites():
            if site.primitive not in DOT_PRIMITIVES:
                continue
            bad = [dt for dt in site.in_dtypes if jnp.dtype(dt) == wire]
            if bad:
                out.append((site.describe(),
                            f"dot-like equation consumes the "
                            f"{wire.name} wire dtype directly — the "
                            f"payload must be dequantized between the "
                            f"exchange and the grouped matmul (f32 "
                            f"accumulation)"))
    return out


@register("no-recompute-backward", "error", ("jaxpr",))
def _no_recompute_backward(graph: JaxprGraph) -> List:
    """A ``ragged_dot`` equation in a grouped-path GRADIENT graph.  The
    custom_vjp backward (PR 3) computes dlhs/drhs straight off the
    residuals with the Pallas kernels; ``ragged_dot`` appearing in a
    grad graph means ``jax.vjp(ragged_dot)`` re-ran the whole forward
    (2× the FLOPs, plus the f32-cotangent leak its transpose causes).
    Applies when the graph was traced with ``expect_no_ragged`` set, or
    with ``direction="grad"`` under a Pallas-kernel grouped config.
    """
    ctx = graph.context
    cfg = ctx.get("cfg")
    applies = bool(ctx.get("expect_no_ragged")) or (
        ctx.get("direction") == "grad" and cfg is not None
        and cfg.dispatch == "grouped" and cfg.use_pallas_gate)
    if not applies:
        return []
    return [(site.describe(),
             "ragged_dot in a backward graph — the grouped VJP must run "
             "the Pallas dlhs/drhs kernels off the residuals, not "
             "re-derive the forward through jax.vjp(ragged_dot)")
            for site in graph.find(RAGGED_DOT_PRIMITIVES)]


@register("dtype-leak", "error", ("jaxpr",))
def _dtype_leak(graph: JaxprGraph) -> List:
    """Mixed float operand dtypes on a dot-like equation.  ``lax``
    accepts an f32 operand against a bf16 one without complaint (that is
    how PR 4's f32 cotangents slipped into bf16 training graphs via
    ``ragged_dot``'s transpose); the result silently computes and stores
    in f32 — 2× the bytes on exactly the tensors the bf16 config was
    meant to shrink.  Accumulating in f32 is fine (and intended): this
    rule only fires when the *inputs* disagree, i.e. a cast is missing.
    """
    import jax.numpy as jnp

    out = []
    for site in graph.sites():
        if site.primitive not in DOT_PRIMITIVES:
            continue
        float_dts = {str(dt) for dt in site.in_dtypes
                     if jnp.issubdtype(dt, jnp.floating)}
        if len(float_dts) > 1:
            out.append((site.describe(),
                        f"{site.primitive} mixes float operand dtypes "
                        f"{sorted(float_dts)} — insert an explicit cast "
                        f"(f32 accumulation belongs in "
                        f"preferred_element_type / an output cast, not "
                        f"in a widened operand)"))
    return out


# ---------------------------------------------------------------------------
# shipped rules — probe side (runtime evidence, no graph)
# ---------------------------------------------------------------------------

@register("donation-alias", "error", ("probe",))
def _donation_alias(graph: ProbeGraph) -> List:
    """Two leaves of a donated pytree share one buffer (see
    ``training.train_step.donation_alias_pairs``, the single source of
    the aliasing check).  Context: ``donated`` = the pytree the driver
    donates (e.g. a ``TrainState``)."""
    from repro.training.train_step import donation_alias_pairs

    donated = graph.context.get("donated")
    if donated is None:
        return []
    return [(f"{a} ~ {b}",
             f"donated leaves {a} and {b} alias the same buffer — XLA "
             f"donation rejects the alias (or silently un-donates, "
             f"doubling state HBM); build distinct buffers")
            for a, b in donation_alias_pairs(donated)]


@register("retrace-budget", "error", ("probe",))
def _retrace_budget(graph: ProbeGraph) -> List:
    """A serving step-builder cache key traced more than ``budget``
    times (default 1).  Context: ``trace_counts`` (the
    ``serving.engine.trace_counts`` Counter, or any mapping key→count)
    and optional ``budget``.  More than one trace per key is the seed's
    re-jit-per-call bug resurfacing through an unhashable cache key."""
    from repro.serving.engine import trace_budget_report

    counts = graph.context.get("trace_counts")
    if counts is None:
        return []
    budget = int(graph.context.get("budget", 1))
    return [(str(key),
             f"step-builder key traced {n}x (budget {budget}) — "
             f"compiled-step cache miss on a repeated shape; check the "
             f"cache key covers every knob that changed")
            for key, n in trace_budget_report(budget, counts).items()]


@register("config-invalid", "error", ("probe",))
def _config_invalid(graph: ProbeGraph) -> List:
    """A config × mesh combination the repo's own validators reject
    (``moe.validate_dispatch_config``, ``engine.validate_decode_config``).
    The lint CLI converts the ``ValueError`` into this finding so a bad
    overlap bound passed via ``--config`` yields a report entry and a
    nonzero exit, not a traceback.  Context: ``config_error`` = the
    validator's message, ``label`` = the cell name."""
    err = graph.context.get("config_error")
    if not err:
        return []
    return [(str(graph.context.get("label", "<config>")), str(err))]
