"""The HetuMoE layer — paper Algorithm 1, expert-parallel over a mesh axis.

Per-device flow (inside ``shard_map``):

    1. gate            route(cfg, x·W)                     [core/gating]
    2. layout xform    plan + dispatch → (E·C, d)           [core/layout]
    3. AllToAll        flat | hierarchical over ``model``   [core/alltoall]
    4. experts         vmapped FFN over local experts
    5. AllToAll        return path (same mode)
    6. reverse xform   gather + weighted combine            [core/layout]

``cfg.dispatch == "grouped"`` replaces 2–6 with the dropless path:
expert-sorted (T·K, d) buffer + grouped/ragged expert matmuls, no
capacity padding.  Under expert parallelism the grouped AllToAll runs
instead of the capacity-padded one: per-expert counts cross the
``model`` axis first (a (M, E_local) int exchange), then each
destination rank's rows packed to a static segment bound B
(capacity.grouped_segment_bound; B = T·K by default → never drops);
the receive side rebuilds expert-major offsets from the counts and
feeds the same ragged matmuls, and the combine reverses the path.
Both a2a modes (flat / hierarchical) carry the token payload, so the
paper's two-stage win composes with dropless dispatch.  Expert-TP mode
(``expert_tp_axis``) composes too: the bounded expert-sorted chunks and
their counts are all-gathered over the TP axis into one expert-major
order every TP rank agrees on, each rank runs the grouped matmuls over
its f-slice of the expert weights, and a psum_scatter returns the
f-reduced token rows — see ``moe_block_local``.

Overlapped pipeline (``cfg.overlap_chunks = P > 1``, grouped dispatch
only): the bounded expert-sorted buffer is split into P static
``(·, B/P, d)`` microchunk windows (``layout.grouped_chunk_counts``
window-clips the count matrices; ``capacity.grouped_overlap_chunk_bound``
checks P divides the bound) and the per-chunk exchange → grouped-matmul
→ combine stages run as a statically-unrolled, double-buffered software
pipeline: window i+1's dispatch AllToAll is issued before window i's
matmuls consume the carried receive buffer, and each window's combine
AllToAll is consumed only at the drain — XLA's async collectives then
hide the steady-state exchange behind compute, leaving only the fill
(first dispatch) and drain (last combine) exposed (the α–β trade is
``alltoall.cost_pipelined``).  Composes with grouped-EP, expert-TP and
both a2a modes; the backward differentiates through the unrolled
pipeline into the same custom_vjp grouped kernels.  P = 1 is exactly
the serial path.

Tokens are sharded over EVERY mesh axis (the token axis is the product
batch·seq flattened): each of the D·M devices routes its own T/(D·M)
tokens.  Experts shard over ``model`` and replicate over ``data``/``pod``
(classic EP×DP); the AllToAll therefore runs inside each data-group's
row of model-ranks, and expert-weight gradients all-reduce over
``data``/``pod`` automatically through the ``shard_map`` transpose.

Token counts that don't divide the device count (decode batches) are
padded; padded tokens are routed to a virtual expert E (dropped by the
plan) so they consume no real capacity.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import alltoall, balance, capacity, gating, layout, tuning
from repro.core.config import MoEConfig


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_moe_params(rng: jax.Array, cfg: MoEConfig, d_model: int, d_ff: int,
                    num_experts: int, *, act: str = "swiglu",
                    dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    d_ff = cfg.d_ff_expert or d_ff
    k_gate, k_up, k_gt, k_out = jax.random.split(rng, 4)
    scale_in = d_model ** -0.5
    scale_out = d_ff ** -0.5
    p = {
        # router always in f32 — numerics matter more than bytes here
        "gate_w": (jax.random.normal(k_gate, (d_model, num_experts), jnp.float32)
                   * scale_in),
        # up / gate kept SEPARATE (not fused 2f) so the f dim shards
        # cleanly in expert-TP mode (§Perf, llama4 decode hillclimb)
        "w_up": (jax.random.normal(k_up, (num_experts, d_model, d_ff), jnp.float32)
                 * scale_in).astype(dtype),
        "w_out": (jax.random.normal(k_out, (num_experts, d_ff, d_model), jnp.float32)
                  * scale_out).astype(dtype),
    }
    if act in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(
            k_gt, (num_experts, d_model, d_ff), jnp.float32)
            * scale_in).astype(dtype)
    return p


def expert_ffn(params: Dict[str, jax.Array], x: jax.Array,
               act: str) -> jax.Array:
    """(E_local, T, d) × expert weights → (E_local, T, d)."""
    h = jnp.einsum("etd,edf->etf", x, params["w_up"])
    if act in ("swiglu", "geglu"):
        g = jnp.einsum("etd,edf->etf", x, params["w_gate"])
        h = h * (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g))
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jax.nn.relu(h)
    return jnp.einsum("etf,efd->etd", h, params["w_out"])


# ---------------------------------------------------------------------------
# the per-device MoE block (runs inside shard_map)
# ---------------------------------------------------------------------------

def moe_block_local(cfg: MoEConfig, params: Dict[str, jax.Array], x: jax.Array,
                    *, num_experts: int, act: str,
                    model_axis: Optional[str] = None, model_size: int = 1,
                    pmean_axes: Tuple[str, ...] = (),
                    rng: Optional[jax.Array] = None,
                    token_ids: Optional[jax.Array] = None,
                    valid: Optional[jax.Array] = None,
                    expert_tp_axis: Optional[str] = None,
                    ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x: (T_local, d) → (y, aux_loss, metrics).  ``params`` hold LOCAL
    expert shards: w_up/w_gate/w_out have leading dim E_local (and, with
    ``expert_tp_axis`` set, a 1/R slice of the f dim, R the TP degree).

    Expert-TP ``dispatch="grouped"`` (the ragged-aware TP composition —
    no more silent rewrite to ``"sort"``): the per-rank bounded
    expert-sorted chunks and their count matrices are all-gathered over
    the TP axis, :func:`repro.core.layout.grouped_tp_gather_maps`
    rebuilds ONE expert-major row order every TP rank agrees on, each
    rank runs the grouped/ragged matmuls over its f-slice (swiglu/geglu
    are elementwise in f, so the up/gate slices compose locally), and a
    tiled ``psum_scatter`` over the token rows hands each rank back its
    own chunk with the f-contraction reduced.  Composes with grouped-EP:
    there the gathered chunks are the (M·B, d) exchange layouts, so the
    return AllToAll runs on the already-reduced rows unchanged."""
    T, d = x.shape
    E = num_experts
    E_local = E // model_size
    assert params["w_up"].shape[0] == E_local, (params["w_up"].shape, E_local)

    # Each stage runs under one flat named scope (moe_gate, moe_layout,
    # moe_exchange, moe_experts), so the compiled ops' op_name metadata,
    # forward and backward, says which stage a device op belongs to.

    # -- 1. gate ----------------------------------------------------------
    with jax.named_scope("moe_gate"):
        logits = gating.router_logits(cfg, x, params["gate_w"])
        gate = gating.route(cfg, logits, rng=rng, token_ids=token_ids)
        if valid is not None:
            # padded tokens → virtual expert E: dropped by the plan, zero
            # weight
            gate = gate._replace(
                expert_index=jnp.where(valid[:, None], gate.expert_index, E),
                combine_weights=jnp.where(valid[:, None],
                                          gate.combine_weights, 0.0))

    # -- 2. dispatch plan (ONE sort; aux metrics reuse its counts) ----------
    dispatch = cfg.dispatch
    tp = expert_tp_axis

    if dispatch == "grouped":
        # dropless: expert-sorted (T·K, d) buffer, no capacity padding;
        # the expert FFN runs as grouped/ragged matmuls over the segments.
        from repro.kernels import grouped_ffn as gffn
        from repro.kernels import ops as kops
        gather = kops.gather_rows if cfg.use_pallas_gate else layout.take_rows
        with jax.named_scope("moe_layout"):
            gplan = layout.plan_grouped(gate, E, drop_bucket=True)
            if model_size > 1:
                # grouped AllToAll (dropless EP): the expert-sorted buffer
                # is destination-rank-sorted too, so dispatch is one
                # gather into the static (M, B, d) exchange layout; counts
                # cross first so the receive side can rebuild its ragged
                # offsets.
                B = capacity.grouped_segment_bound(cfg, T, model_size)
                eplan = layout.plan_grouped_ep(gplan, E, model_size, B)
                packed = gather(x, eplan.pack_map).reshape(model_size, B, d)
                send_counts = eplan.send_counts        # (M, E_local)
                # assignments past a destination rank's bound
                dropped = (jnp.sum(gplan.counts)
                           - jnp.sum(eplan.send_counts))
            else:
                B = capacity.grouped_tp_gather_bound(cfg, T)
                xs0 = (gather(x, gplan.token) if cfg.use_pallas_gate
                       else layout.dispatch_grouped(x, gplan))
                packed = xs0.reshape(1, B, d)          # the sorted buffer
                send_counts = gplan.counts[None]       # (1, E)
                dropped = None                         # B = T·K: dropless
        n_src = packed.shape[0]
        block_m = cfg.grouped_block_m or gffn.DEFAULT_BLOCK_M
        # each window's grouped Pallas kernels' share of grid steps that
        # do work (the routing counter grouped_visit_share)
        visit_shares = []
        # Wire dtype for the exchange payloads (MegaScale-MoE).  A no-op
        # without expert parallelism: the exchange is the identity, so
        # there is no wire to quantize — pure-TP meshes keep full
        # precision end to end.
        qdt = cfg.payload_dtype if model_size > 1 else None

        def exchange(chunk, counts):
            """Dispatch exchange of one bounded window (identity without
            expert parallelism).  With ``cfg.payload_dtype`` set the
            window crosses the mesh quantized (per-source-chunk amax
            scales riding the count matrix) and arrives dequantized back
            at the compute dtype — the downstream TP gather / row maps /
            grouped matmuls are unchanged."""
            if model_size > 1:
                with jax.named_scope("moe_exchange"):
                    if qdt is not None:
                        return alltoall.quantized_exchange(
                            chunk, counts, model_axis, mode=cfg.a2a,
                            inner=cfg.a2a_inner, payload_dtype=qdt)
                    return alltoall.grouped_all_to_all(
                        chunk, counts, model_axis,
                        mode=cfg.a2a, inner=cfg.a2a_inner)
            return chunk, counts

        def compute(recv, counts, bc):
            """Grouped matmuls over one received window ``(n_src, bc, d)``
            + its count matrix, returning the FFN output in the SAME
            home/exchange layout (TP gathered & f-reduced, EP combine
            AllToAll'd back to the source ranks)."""
            if tp is not None:
                # ragged-aware expert TP: gather every TP rank's bounded
                # chunks + counts (the chunk layout is identical on all
                # ranks — the bound derives from static shapes only, see
                # capacity.grouped_tp_gather_bound), merge into one shared
                # expert-major order, and run this rank's f-slice.
                with jax.named_scope("moe_exchange"):
                    recv = lax.all_gather(recv, tp, axis=0, tiled=True)
                    counts = lax.all_gather(counts, tp, axis=0, tiled=True)
            # the gathered chunk count is R·M by all_gather construction
            # (1 with neither TP nor EP) — the merged maps key off it
            n_chunks = recv.shape[0]
            if model_size > 1 or tp is not None:
                with jax.named_scope("moe_layout"):
                    ffn_src, dst_map, group_sizes = (
                        layout.grouped_tp_gather_maps(counts, bc))
                    xs = gather(recv.reshape(n_chunks * bc, d), ffn_src)
            else:
                xs = recv.reshape(bc, d)
                group_sizes = counts[0]
            if cfg.use_pallas_gate:
                with jax.named_scope("moe_gate"):
                    visit_shares.append(gffn.visit_share(
                        group_sizes, xs.shape[0], block_m))
            with jax.named_scope("moe_experts"):
                ys = gffn.grouped_ffn(params,
                                      xs.astype(params["w_up"].dtype),
                                      group_sizes, act,
                                      use_pallas=cfg.use_pallas_gate,
                                      interpret=kops.INTERPRET,
                                      block_m=block_m)
            if tp is not None:
                # back to chunk layout, then reduce the f-contraction
                # while scattering each TP rank its own rows (tiled:
                # chunk r of the summed (R·M·bc, d) array is rank r's
                # (M·bc, d) layout)
                with jax.named_scope("moe_layout"):
                    h = gather(ys, dst_map)
                with jax.named_scope("moe_exchange"):
                    ys = lax.psum_scatter(h, tp, scatter_dimension=0,
                                          tiled=True)
            if model_size > 1:
                # expert-major FFN rows → exchange layout → AllToAll home
                with jax.named_scope("moe_layout"):
                    h = (ys.reshape(model_size, bc, d) if tp is not None
                         else gather(ys, dst_map).reshape(model_size, bc, d))
                with jax.named_scope("moe_exchange"):
                    if qdt is not None:
                        # combine payload quantized like dispatch (the
                        # scales go over their own tiny flat exchange —
                        # no count matrix travels this direction) and
                        # dequantized to f32, so the weighted combine
                        # reduction below runs in f32 regardless of the
                        # compute dtype.
                        out, _ = alltoall.quantized_exchange(
                            h, None, model_axis, mode=cfg.a2a,
                            inner=cfg.a2a_inner, payload_dtype=qdt,
                            out_dtype=jnp.float32)
                        return out
                    return alltoall.all_to_all(h, model_axis, mode=cfg.a2a,
                                               inner=cfg.a2a_inner)
            return ys.reshape(1, bc, d)

        n_overlap = cfg.overlap_chunks
        if n_overlap > 1:
            # overlapped pipeline: P static (n_src, Bc, d) windows of the
            # bounded buffer, software-pipelined with a double buffer —
            # window i+1's dispatch exchange is issued BEFORE window i's
            # grouped matmuls consume the carried receive buffer, and
            # each window's combine AllToAll is consumed only at the
            # drain, so XLA's async collectives overlap both directions
            # with compute.  Statically unrolled (P is a config int):
            # a fori_loop would fold the P exchanges into one loop-body
            # collective, hiding the pipeline from the scheduler (and
            # from the jaxpr witness tests).
            Bc = capacity.grouped_overlap_chunk_bound(cfg, B)
            with jax.named_scope("moe_layout"):
                chunk_counts = layout.grouped_chunk_counts(
                    send_counts, B, n_overlap)         # (P, n_src, E_seg)
            windows = packed.reshape(n_src, n_overlap, Bc, d)
            recv, rcounts = exchange(windows[:, 0], chunk_counts[0])
            outs = []
            for i in range(n_overlap):
                if i + 1 < n_overlap:   # prefetch the next window's a2a
                    recv_nxt, rcounts_nxt = exchange(windows[:, i + 1],
                                                     chunk_counts[i + 1])
                outs.append(compute(recv, rcounts, Bc))
                if i + 1 < n_overlap:
                    recv, rcounts = recv_nxt, rcounts_nxt
            out = jnp.stack(outs, axis=1).reshape(n_src, B, d)
        else:
            out = compute(*exchange(packed, send_counts), B)
        with jax.named_scope("moe_gate"):
            aux, metrics = balance.aux_losses(
                cfg, gate, expert_counts=gplan.counts, valid=valid,
                axes=pmean_axes, dropped=dropped,
                visit_share=(sum(visit_shares) / len(visit_shares)
                             if visit_shares else None))

        with jax.named_scope("moe_layout"):
            if model_size > 1:
                # reverse path: combined exchange layout → this rank's
                # sorted rows → weighted combine
                ys = gather(out.reshape(model_size * B, d), eplan.back_map)
            else:
                ys = out.reshape(B, d)
            y = layout.combine_grouped(ys, gplan, T)
        if pmean_axes:
            with jax.named_scope("moe_gate"):
                aux = lax.pmean(aux, pmean_axes)
                metrics = {k: lax.pmean(v, pmean_axes)
                           for k, v in metrics.items()}
        return y.astype(x.dtype), aux, metrics

    C = capacity.expert_capacity(cfg, T, E)
    with jax.named_scope("moe_layout"):
        if dispatch == "sort":
            plan = layout.plan_sort(gate, E, C, drop_bucket=True)
            if cfg.use_pallas_gate:
                # the blocked Pallas layout kernel replaces the jnp gather
                # on TPU, driven by the plan's sort-derived inverse row
                # map; interpret-mode equivalence is asserted in tests
                from repro.kernels import ops as kops
                buf = kops.layout_dispatch(x, plan.slot, E, C, inv=plan.inv)
            else:
                buf = layout.dispatch_scatter(x, plan, E, C)
        else:
            plan = layout.plan_cumsum(gate, E, C, drop_bucket=True)
            buf = layout.dispatch_dense(x, plan, E, C)
    with jax.named_scope("moe_gate"):
        # the plan's counts are pre-capacity: past C an expert drops
        dropped = jnp.sum(jnp.maximum(plan.counts - C, 0))
        aux, metrics = balance.aux_losses(cfg, gate, expert_counts=plan.counts,
                                          valid=valid, axes=pmean_axes,
                                          dropped=dropped)

    # -- 3. AllToAll (dispatch) ---------------------------------------------
    if model_size > 1:
        with jax.named_scope("moe_exchange"):
            buf = buf.reshape(model_size, E_local * C, d)
            buf = alltoall.all_to_all(buf, model_axis, mode=cfg.a2a,
                                      inner=cfg.a2a_inner)
            # (M, E_local·C, d) source-major → (E_local, M·C, d)
            buf = (buf.reshape(model_size, E_local, C, d)
                   .transpose(1, 0, 2, 3).reshape(E_local, model_size * C, d))
    else:
        buf = buf.reshape(E_local, C, d)

    # -- 4. experts -----------------------------------------------------------
    if expert_tp_axis is not None:
        # §Perf (llama4/dbrx decode hillclimb): expert TENSOR parallelism
        # over the data axis — weights stay sharded on their f dim; the
        # (tiny, decode-sized) token buffers are gathered across data,
        # every data-rank computes its f-slice of every local expert, and
        # a reduce-scatter returns each rank's own tokens.  Replaces the
        # per-layer multi-GB ZeRO-3 weight gather with MB-scale token
        # collectives.
        with jax.named_scope("moe_exchange"):
            buf = lax.all_gather(buf, expert_tp_axis, axis=1, tiled=True)
        with jax.named_scope("moe_experts"):
            h = expert_ffn(params, buf.astype(params["w_up"].dtype), act)
        with jax.named_scope("moe_exchange"):
            h = lax.psum_scatter(h, expert_tp_axis, scatter_dimension=1,
                                 tiled=True)
    else:
        with jax.named_scope("moe_experts"):
            h = expert_ffn(params, buf.astype(params["w_up"].dtype), act)

    # -- 5. AllToAll (return) -------------------------------------------------
    if model_size > 1:
        with jax.named_scope("moe_exchange"):
            h = (h.reshape(E_local, model_size, C, d)
                 .transpose(1, 0, 2, 3).reshape(model_size, E_local * C, d))
            h = alltoall.all_to_all(h, model_axis, mode=cfg.a2a,
                                    inner=cfg.a2a_inner)
            h = h.reshape(E * C, d)
    else:
        h = h.reshape(E * C, d)

    # -- 6. reverse layout transform + combine --------------------------------
    with jax.named_scope("moe_layout"):
        if dispatch == "sort":
            if cfg.use_pallas_gate:
                from repro.kernels import ops as kops
                y = kops.layout_combine(h, plan.slot, plan.weight)
            else:
                y = layout.combine_gather(h, plan)
        else:
            y = layout.combine_dense(h, plan, E, C)

    if pmean_axes:
        with jax.named_scope("moe_gate"):
            aux = lax.pmean(aux, pmean_axes)
            metrics = {k: lax.pmean(v, pmean_axes) for k, v in metrics.items()}
    return y.astype(x.dtype), aux, metrics


# ---------------------------------------------------------------------------
# shard_map wrapper — the public MoE layer
# ---------------------------------------------------------------------------

def _pad_to(x: jax.Array, mult: int, axis: int = 0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def grouped_a2a_stages(cfg: MoEConfig, model_size: int) -> int:
    """Equations one payload exchange emits: 1 for flat, 2 for an
    EFFECTIVE hierarchical a2a (two-stage only when
    ``1 < a2a_inner < model_size`` divides evenly; otherwise
    ``core.alltoall`` runs flat).  The lint rules derive their
    payload-site expectations from this instead of back-solving the
    total equation count — the quantized path's extra scales exchange
    made that inversion ambiguous."""
    if (cfg.a2a == "hierarchical" and 1 < cfg.a2a_inner
            and model_size % cfg.a2a_inner == 0
            and model_size // cfg.a2a_inner > 1):
        return 2
    return 1


def expected_grouped_a2a_eqns(cfg: MoEConfig, model_size: int) -> int:
    """How many ``all_to_all`` equations the grouped dispatch path emits
    per layer application — the single source of truth for the
    ``overlap-chunk-count`` lint rule (``repro.analysis``) and the jaxpr
    witness tests, kept next to the pipeline that emits them.

    Per overlap window: one (flat) counts exchange, plus a dispatch and
    a combine payload exchange of :func:`grouped_a2a_stages` equations
    each.  With ``payload_dtype`` set, the combine direction adds one
    tiny flat scales exchange per window (the dispatch direction's
    scales ride the counts exchange as a bitcast column — zero extra
    equations; see ``alltoall.quantized_grouped_all_to_all``).
    ``overlap_chunks = P`` multiplies everything: the statically
    unrolled pipeline must emit P separate window exchanges — a ``fori_loop``
    would fold them into ONE loop-body equation (the PR 5 scheduler-
    hiding hazard the lint rule exists to catch).
    """
    if tuning.has_auto_knobs(cfg):
        # a sentinel here would be silently counted as flat/P="auto" —
        # the caller must hand over the concrete cell it actually traced
        raise ValueError(
            "expected_grouped_a2a_eqns needs a concrete config — resolve "
            "'auto' knobs first (core/tuning.resolve_moe_config)")
    if cfg.dispatch != "grouped" or model_size <= 1:
        return 0
    stages = grouped_a2a_stages(cfg, model_size)
    per_window = 1 + 2 * stages
    if cfg.payload_dtype is not None:
        per_window += 1                     # the combine scales exchange
    return cfg.overlap_chunks * per_window


def validate_dispatch_config(cfg: MoEConfig, *, model_size: int,
                             model_axis: str = "model",
                             tokens_per_shard: Optional[int] = None,
                             d_model: Optional[int] = None,
                             dtype=None) -> None:
    """Raise ``ValueError`` for cfg × mesh combinations that would
    otherwise only surface at trace time, deep inside ``shard_map``.

    Called by :func:`sharded_moe_apply` on every trace, and by the
    serving step-builder (``serving/engine.py``) at STEP-BUILD time so a
    bad serving configuration fails when the step is constructed — with
    the config fields named — instead of minutes later inside a decode
    trace.  With ``tokens_per_shard`` given (the static per-shard token
    count is known to the caller, e.g. the decode batch), the grouped
    overlap-pipeline bound divisibility is checked too
    (:func:`capacity.grouped_overlap_chunk_bound`).

    ``"auto"`` knobs (core/tuning.py) are resolved first when
    ``tokens_per_shard`` is known — the checks then run against, and any
    error message names, the RESOLVED values.  Without a token count
    there is nothing concrete to check yet: every sentinel resolves at a
    choke point where the count is static, and the resolver only emits
    combinations these checks accept.
    """
    auto_cfg = None
    if tuning.has_auto_knobs(cfg):
        if tokens_per_shard is None:
            return
        auto_cfg = cfg
        cfg = tuning.resolve_moe_config(
            cfg, model_size=model_size, tokens_per_shard=tokens_per_shard,
            d_model=d_model if d_model is not None else 1024, dtype=dtype)
    try:
        _validate_concrete(cfg, model_size=model_size, model_axis=model_axis,
                           tokens_per_shard=tokens_per_shard)
    except ValueError as e:
        if auto_cfg is not None:
            raise ValueError(
                f"{e} [{tuning.describe_resolution(auto_cfg, cfg)}]"
            ) from None
        raise


def _validate_concrete(cfg: MoEConfig, *, model_size: int,
                       model_axis: str,
                       tokens_per_shard: Optional[int]) -> None:
    if cfg.overlap_chunks > 1 and cfg.dispatch != "grouped":
        # the pipeline chunks the bounded expert-sorted buffer, which
        # only the grouped path builds — silently ignoring the setting
        # would fake an overlap win on the capacity-padded paths
        raise ValueError(
            f"MoEConfig.overlap_chunks={cfg.overlap_chunks} requires "
            f"dispatch='grouped' (the overlapped pipeline chunks the "
            f"grouped dispatch buffer), got dispatch="
            f"{cfg.dispatch!r}")
    if (cfg.a2a == "hierarchical" and cfg.a2a_inner > 1
            and model_size > 1 and model_size % cfg.a2a_inner != 0):
        raise ValueError(
            f"MoEConfig.a2a='hierarchical' with a2a_inner={cfg.a2a_inner} "
            f"does not divide the mesh {model_axis!r} axis size "
            f"{model_size} — pick a2a_inner from its divisors or use "
            f"a2a='flat'")
    if (tokens_per_shard is not None and cfg.dispatch == "grouped"
            and cfg.overlap_chunks > 1):
        B = (capacity.grouped_segment_bound(cfg, tokens_per_shard, model_size)
             if model_size > 1
             else capacity.grouped_tp_gather_bound(cfg, tokens_per_shard))
        capacity.grouped_overlap_chunk_bound(cfg, B)   # raises when P ∤ B


def sharded_moe_apply(mesh: jax.sharding.Mesh, cfg: MoEConfig,
                      params: Dict[str, jax.Array], x: jax.Array, *,
                      num_experts: int, act: str = "swiglu",
                      model_axis: str = "model",
                      rng: Optional[jax.Array] = None,
                      token_ids: Optional[jax.Array] = None,
                      expert_tp_axis: Optional[str] = None,
                      ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Apply the MoE layer to ``x: (..., d)`` under ``mesh``.

    Leading dims are flattened into one token axis, sharded over EVERY
    mesh axis; expert weights shard over ``model_axis``.
    """
    lead = x.shape[:-1]
    d = x.shape[-1]
    toks = x.reshape(-1, d)
    axis_names = tuple(mesh.axis_names)
    n_dev = mesh.devices.size
    model_size = mesh.shape[model_axis]

    toks, n_real = _pad_to(toks, n_dev)
    valid = (jnp.arange(toks.shape[0]) < n_real)
    if token_ids is not None:
        tid, _ = _pad_to(token_ids.reshape(-1), n_dev)
    elif cfg.gate == "hash":
        # the zeros placeholder below would hash EVERY token to the same
        # bucket — one expert takes all load and _gate_hash never notices
        raise ValueError(
            "cfg.gate='hash' routes by token id: pass token_ids to "
            "sharded_moe_apply (the zeros fallback would silently send "
            "every token to one expert)")
    else:
        tid = jnp.zeros((toks.shape[0],), jnp.int32)

    if rng is None:
        rng = jax.random.PRNGKey(0)

    # §Perf H2 (dbrx train hillclimb): gather expert weights in the
    # COMPUTE dtype.  The cast is outside shard_map, so the ZeRO-3
    # all-gather XLA inserts at the shard_map boundary moves bf16, not
    # f32 — halving the largest FSDP collective and its HBM transient.
    with jax.named_scope("moe_experts"):
        params = {k: (v.astype(x.dtype) if k != "gate_w" else v)
                  for k, v in params.items()}

    # trace-time "auto" resolution (core/tuning.py): the per-shard token
    # count, width and dtype are all static here, so the resolved cfg is
    # a pure function of the traced shapes — the same call shape always
    # resolves (and therefore traces) identically.
    cfg = tuning.resolve_moe_config(
        cfg, model_size=model_size, tokens_per_shard=toks.shape[0] // n_dev,
        d_model=d, dtype=x.dtype)
    validate_dispatch_config(cfg, model_size=model_size,
                             model_axis=model_axis)

    tok_spec = P(axis_names)
    tp = None
    if expert_tp_axis is not None:
        if expert_tp_axis not in axis_names:
            # a typo'd axis must not silently disable expert TP
            raise ValueError(
                f"expert_tp_axis={expert_tp_axis!r} is not an axis of the "
                f"mesh; valid axis names: {axis_names}")
        tp = expert_tp_axis
    param_specs = {"gate_w": P(None, None),
                   "w_up": P(model_axis, None, tp),
                   "w_out": P(model_axis, tp, None)}
    if "w_gate" in params:
        param_specs["w_gate"] = P(model_axis, None, tp)

    def local_fn(params, toks, valid, tid, rng):
        idx = lax.axis_index(axis_names)
        rng = jax.random.fold_in(rng, idx)
        return moe_block_local(
            cfg, params, toks, num_experts=num_experts, act=act,
            model_axis=model_axis, model_size=model_size,
            pmean_axes=axis_names, rng=rng,
            token_ids=tid, valid=valid, expert_tp_axis=tp)

    # metric out_specs come from balance's canonical key list — a metric
    # added there must not desync this spec tree (shard_map fails with an
    # opaque pytree-mismatch error when it does)
    y, aux, metrics = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(param_specs, tok_spec, tok_spec, tok_spec, P()),
        out_specs=(tok_spec, P(), {k: P() for k in balance.METRIC_KEYS}),
        check_vma=False,
    )(params, toks, valid, tid, rng)

    y = y[:n_real].reshape(*lead, d)
    return y, aux, metrics
