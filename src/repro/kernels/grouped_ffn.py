"""Grouped (ragged) expert matmuls for the dropless dispatch mode.

The ``grouped`` dispatch packs tokens into an expert-sorted ``(M, d)``
buffer whose per-expert segment lengths are data-dependent; the expert
FFN then needs ``y[offs[e]:offs[e+1]] = x[offs[e]:offs[e+1]] @ w[e]`` —
a grouped matmul (MegaBlocks' dMoE primitive).  Two implementations:

``ragged``  ``jax.lax.ragged_dot`` — XLA's native ragged primitive, used
            as the jnp reference path.
``pallas``  Blocked kernels over a visit list (megablox's ``gmm`` /
            ``tgmm`` design).  The rows are cut into ``nb`` blocks of
            ``block_m``; :func:`visit_list` turns the expert offsets into
            the (expert, row block) pairs whose rows overlap, in row
            order — each block once per expert it meets, so at most
            ``V = nb + E - 1`` visits, against ``E · nb`` steps of a grid
            over every pair.  The list (``group_of[V]``, ``block_of[V]``)
            and its live count are scalar-prefetched into SMEM with the
            offsets, and the kernel grid's row axis walks it: the index
            maps read ``block_of[t]`` for the row blocks and
            ``group_of[t]`` for the expert, so consecutive visits that
            keep a block fetch nothing new.  The list is padded to ``V``
            by repeating its last live visit; steps from the live count
            on do nothing.  Rows outside the visited expert's range are
            masked before the dot.  The weights' lane dim is tiled by
            ``_lane_tile`` so a block fits the default scoped VMEM at
            d = f = 2048.

Rows past ``offsets[-1]`` (the virtual drop bucket's tail under token
padding) belong to no expert and come out zero — matching ragged_dot:
the forward's list hands them to the last expert, whose row mask then
keeps none of them.

Expert tensor parallelism needs no kernel variant: the kernels are
shape-polymorphic in the weights' f dim, so the TP path simply passes
the local f-slice — ``w_up/w_gate (E, d, f/R)`` and ``w_out (E, f/R,
d)``.  The up/gate matmuls then emit f/R-wide activations (swiglu /
geglu are elementwise in f, so the slices compose locally), the out
matmul contracts the f/R slice into a PARTIAL (M, d) sum, and the
caller's psum_scatter over the TP axis completes the contraction.  The
Pallas backward inherits this for free — dlhs sums R partials through
the same psum (the psum_scatter transpose), drhs produces each rank's
own (d, f/R) / (f/R, d) weight-gradient slice locally.

Forward and dlhs share one kernel, grid ``(n_out / bn, V)``: the output
block ``(block_of[t], j)`` stays resident while the experts that share
its rows accumulate into it (f32), zeroed on the block's first visit.
The ``custom_vjp`` backward is kernelized too (MegaBlocks trains the
dMoE primitive in both directions) — no forward recompute, both
gradients straight off the residuals:

  dlhs  the SAME kernel with ``rhs`` transposed on its last two dims
        (the ``transpose_rhs`` flag — a tile-level transpose in-kernel,
        no HBM copy of the expert weights):
        ``dlhs[seg_e] = g[seg_e] @ rhs[e]ᵀ``.
  drhs  a segment-wise outer-product accumulation kernel, grid
        ``(K / bk, N / bn, V)`` over its own visit list: the output tile
        ``(group_of[t], a, b)`` stays resident across one expert's
        consecutive visits, zeroed on the first, masked rows zeroed,
        partial products accumulated in f32
        (``drhs[e] = lhs[seg_e]ᵀ @ g[seg_e]``).  Its list visits an
        expert with no rows once, so its gradient is an exact zero.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_M = 128


# Lane-dim tile of the expert weights / weight gradient.  A whole
# (K, N) = (2048, 2048) bf16 expert tile is 8 MiB, double-buffered 16 MiB:
# over v5e's 16 MiB default scoped VMEM once the row blocks are added.
DEFAULT_BLOCK_N = 512


def _lane_tile(n: int, cap: int = DEFAULT_BLOCK_N) -> int:
    """Largest multiple of 128 that divides ``n`` and is at most ``cap``;
    ``n`` itself when it is that small or has no such divisor (a block
    equal to the full dim always lowers)."""
    if n <= cap:
        return n
    for t in range(cap - cap % 128, 0, -128):
        if n % t == 0:
            return t
    return n


def visit_list(offsets: jax.Array, n_blocks: int, block_m: int, *,
               visit_empty: bool):
    """The (expert, row block) pairs whose rows overlap, in row order.

    ``offsets`` (E + 1,) int32: expert e owns rows
    ``[offsets[e], offsets[e + 1])`` of ``n_blocks · block_m``.  An expert
    with rows visits each block its range meets; with ``visit_empty`` an
    expert with none visits once the block its offset falls in (the last
    block past the end), so a per-expert output is still written.  A
    block two experts share is visited by both, one after the other.

    Returns ``(group_of, block_of, live)``: int32 ``(V,)``, ``(V,)`` and
    ``(1,)``, ``V = n_blocks + E - 1``.  Visits from ``live`` on repeat the
    last live one.  Sorted segments meet at most ``n_blocks`` blocks plus
    one more per boundary inside a block, and each empty expert takes a
    boundary's place, so ``live <= V``.
    """
    E = offsets.shape[0] - 1
    V = n_blocks + E - 1
    lo, hi = offsets[:-1], offsets[1:]
    n_vis = jnp.where(hi > lo, (hi - 1) // block_m - lo // block_m + 1,
                      int(visit_empty))
    first = jnp.minimum(lo // block_m, n_blocks - 1)
    ends = jnp.cumsum(n_vis)
    live = ends[-1]
    t = jnp.minimum(jnp.arange(V, dtype=jnp.int32),
                    jnp.maximum(live - 1, 0))
    group = jnp.minimum(jnp.sum(t[:, None] >= ends[None, :], axis=1), E - 1)
    block = first[group] + t - (ends[group] - n_vis[group])
    return (group.astype(jnp.int32), block.astype(jnp.int32),
            live.reshape(1).astype(jnp.int32))


def _row_blocks(M: int, block_m: int):
    """``(bm, nb)``: the row block (``block_m``, at most ``M``) and the
    number of blocks over ``M`` rows padded to a multiple of it."""
    bm = min(block_m, M)
    return bm, -(-M // bm)


def _fwd_visits(offs: jax.Array, nb: int, bm: int):
    """The forward / dlhs visit list: the last expert's visits run on to
    the end of the padded rows, so the tail past ``offs[-1]`` is visited
    (and zeroed by its row mask); an expert with no rows is skipped."""
    return visit_list(offs.at[-1].set(nb * bm), nb, bm, visit_empty=False)


def visit_share(group_sizes: jax.Array, rows: int,
                block_m: int = DEFAULT_BLOCK_M) -> jax.Array:
    """Share of the forward / dlhs kernel grid's row-axis steps that do
    work (live visits over ``V``) for ``rows`` rows in expert segments of
    ``group_sizes``: 1 when every step visits a pair; a grid over every
    (expert, row block) pair would read about ``1 / E``."""
    bm, nb = _row_blocks(rows, block_m)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(group_sizes).astype(jnp.int32)])
    live = _fwd_visits(offs, nb, bm)[2][0]
    return live.astype(jnp.float32) / (nb + group_sizes.shape[0] - 1)


def _row_mask(offs_ref, e, row0, block_m):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)
    return (rows >= offs_ref[e]) & (rows < offs_ref[e + 1])


def _does_work(t, live_ref, offs_ref, e, row0, block_m):
    """A live visit whose expert has rows in ``[row0, row0 + block_m)``."""
    return jnp.logical_and(
        t < live_ref[0],
        jnp.maximum(offs_ref[e], row0)
        < jnp.minimum(offs_ref[e + 1], row0 + block_m))


def _first_visit(ref, t):
    """Whether ``ref[t]`` differs from the previous visit's (or t is 0)."""
    return jnp.logical_or(t == 0, ref[t] != ref[jnp.maximum(t - 1, 0)])


def _grouped_matmul_kernel(offs_ref, grp_ref, blk_ref, live_ref, lhs_ref,
                           rhs_ref, out_ref, *, block_m: int,
                           transpose_rhs: bool):
    t = pl.program_id(1)

    @pl.when(_first_visit(blk_ref, t))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    e, row0 = grp_ref[t], blk_ref[t] * block_m

    @pl.when(_does_work(t, live_ref, offs_ref, e, row0, block_m))
    def _tile():
        x = jnp.where(_row_mask(offs_ref, e, row0, block_m), lhs_ref[...], 0)
        # transpose_rhs serves the dlhs backward: the weight tile is
        # contracted on its last dim in-kernel, so the caller never
        # materializes an (E, N, K) copy of the expert weights in HBM.
        # out_ref is f32 regardless of input dtype: partial sums must not
        # round to bf16 (the sort path's einsum accumulates f32 too)
        dims = (((1,), (1,)) if transpose_rhs else ((1,), (0,)), ((), ()))
        out_ref[...] += lax.dot_general(x, rhs_ref[0], dims,
                                        preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "block_m", "transpose_rhs"))
def _grouped_matmul_impl(lhs: jax.Array, rhs: jax.Array, offsets: jax.Array,
                         *, interpret: bool = True,
                         block_m: int = DEFAULT_BLOCK_M,
                         transpose_rhs: bool = False) -> jax.Array:
    """y[seg_e] = lhs[seg_e] @ rhs[e] — or @ rhs[e].T with
    ``transpose_rhs`` (the dlhs backward; lhs is then (M, N) → (M, K)).

    Grid (out tiles, visits): each (row block, out tile) output block
    stays resident while the experts that share the block accumulate
    into it."""
    M, C = lhs.shape
    E, K, N = rhs.shape
    n_out = K if transpose_rhs else N
    bm, nb = _row_blocks(M, block_m)
    bn = _lane_tile(n_out)
    pad = nb * bm - M
    if pad:
        lhs = jnp.concatenate([lhs, jnp.zeros((pad, C), lhs.dtype)])
    offs = offsets.astype(jnp.int32)
    grp, blk, live = _fwd_visits(offs, nb, bm)

    def w_map(j, t, offs, grp, blk, live):
        return (grp[t], j, 0) if transpose_rhs else (grp[t], 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_out // bn, nb + E - 1),
        in_specs=[
            pl.BlockSpec((bm, C), lambda j, t, offs, grp, blk, live:
                         (blk[t], 0)),
            pl.BlockSpec((1, bn, N) if transpose_rhs else (1, K, bn), w_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, t, offs, grp, blk, live:
                               (blk[t], j)),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, block_m=bm,
                          transpose_rhs=transpose_rhs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M + pad, n_out), jnp.float32),
        interpret=interpret,
    )(offs, grp, blk, live, lhs, rhs)
    return (out[:M] if pad else out).astype(lhs.dtype)


# drhs tiles: its row block is the contraction dim, so a deeper one means
# fewer grid steps for the same MXU work.  A (1024, 1024) f32 output tile
# is 4 MiB, double-buffered 8 MiB, and fits the default scoped VMEM with
# (256, 1024) bf16 operand blocks.
DRHS_BLOCK_M = 256
DRHS_BLOCK_N = 1024


def _drhs_tiles(M: int, K: int, N: int, block_m: int):
    """``(bm, bk, bn)`` of the drhs kernel: its row block (the contraction
    dim, at least ``block_m``) and the lane tiles of its (K, N) output."""
    return (min(max(block_m, DRHS_BLOCK_M), M),
            _lane_tile(K, DRHS_BLOCK_N), _lane_tile(N, DRHS_BLOCK_N))


def _grouped_drhs_kernel(offs_ref, grp_ref, blk_ref, live_ref, lhs_ref,
                         g_ref, out_ref, *, block_m: int):
    t = pl.program_id(2)

    @pl.when(_first_visit(grp_ref, t))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    e, row0 = grp_ref[t], blk_ref[t] * block_m

    @pl.when(_does_work(t, live_ref, offs_ref, e, row0, block_m))
    def _tile():
        # masking ONE operand suffices: rows outside [lo, hi) — including
        # the virtual drop bucket's tail — contribute a zero outer product
        x = jnp.where(_row_mask(offs_ref, e, row0, block_m), lhs_ref[...], 0)
        out_ref[0] += lax.dot_general(x, g_ref[...], (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret", "block_m"))
def _grouped_drhs_impl(lhs: jax.Array, g: jax.Array, offsets: jax.Array,
                       *, interpret: bool = True,
                       block_m: int = DEFAULT_BLOCK_M) -> jax.Array:
    """drhs (E, K, N) f32 with drhs[e] = lhs[seg_e].T @ g[seg_e].

    Grid (K tiles, N tiles, visits): each expert's (bk, bn) output tile
    stays resident while its row blocks accumulate into it; an expert
    with no rows is visited once and left at zero."""
    M, K = lhs.shape
    _, N = g.shape
    E = offsets.shape[0] - 1
    bm, bk, bn = _drhs_tiles(M, K, N, block_m)
    nb = -(-M // bm)
    pad = nb * bm - M
    if pad:
        lhs = jnp.concatenate([lhs, jnp.zeros((pad, K), lhs.dtype)])
        g = jnp.concatenate([g, jnp.zeros((pad, N), g.dtype)])
    offs = offsets.astype(jnp.int32)
    grp, blk, live = visit_list(offs, nb, bm, visit_empty=True)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(K // bk, N // bn, nb + E - 1),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda a, b, t, offs, grp, blk, live:
                         (blk[t], a)),
            pl.BlockSpec((bm, bn), lambda a, b, t, offs, grp, blk, live:
                         (blk[t], b)),
        ],
        out_specs=pl.BlockSpec((1, bk, bn), lambda a, b, t, offs, grp, blk,
                               live: (grp[t], a, b)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_drhs_kernel, block_m=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, K, N), jnp.float32),
        interpret=interpret,
    )(offs, grp, blk, live, lhs, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   interpret: bool = True,
                   block_m: int = DEFAULT_BLOCK_M) -> jax.Array:
    """y (M, N) with y[seg_e] = lhs[seg_e] @ rhs[e] per expert segment.

    lhs (M, K) expert-sorted rows, rhs (E, K, N), group_sizes (E,).
    Rows past sum(group_sizes) produce zeros.
    """
    return _grouped_fwd(lhs, rhs, group_sizes, interpret, block_m)[0]


def _grouped_fwd(lhs, rhs, group_sizes, interpret, block_m):
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(group_sizes).astype(jnp.int32)])
    out = _grouped_matmul_impl(lhs, rhs, offs, interpret=interpret,
                               block_m=block_m)
    return out, (lhs, rhs, offs)


def _grouped_bwd(interpret, block_m, res, g):
    # Both gradients are Pallas kernels off the residuals — NO forward
    # recompute (the old path re-ran the whole forward through jax.vjp of
    # ragged_dot just to reach its transpose rule):
    #   dlhs[seg_e] = g[seg_e] @ rhs[e]ᵀ  — the forward kernel with its
    #                                       (K, N) tile transposed in-kernel
    #   drhs[e]     = lhs[seg_e]ᵀ @ g[seg_e]  — segment outer-product sum
    lhs, rhs, offs = res
    g = g.astype(lhs.dtype)
    dlhs = _grouped_matmul_impl(g, rhs, offs, transpose_rhs=True,
                                interpret=interpret, block_m=block_m)
    drhs = _grouped_drhs_impl(lhs, g, offs,
                              interpret=interpret, block_m=block_m)
    return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_ffn(params: Dict[str, jax.Array], xs: jax.Array,
                group_sizes: jax.Array, act: str, *,
                use_pallas: bool = False, interpret: bool = True,
                block_m: int = DEFAULT_BLOCK_M) -> jax.Array:
    """Expert FFN over the expert-sorted (M, d) buffer — dropless twin of
    ``moe.expert_ffn``.  w_up/w_gate/w_out have leading dim E; their f
    dim may be a TP slice (see module docstring) — the output is then a
    partial sum the caller must reduce over the TP axis."""
    if "w_gate" in params and params["w_gate"].shape != params["w_up"].shape:
        # a mixed TP/unsliced param tree would silently produce a wrong
        # elementwise swiglu on the narrower slice
        raise ValueError(
            f"grouped_ffn: w_gate shape {params['w_gate'].shape} != w_up "
            f"shape {params['w_up'].shape} — up/gate must carry the same "
            f"(E, d, f) slice (expert-TP shards both on f together)")
    if use_pallas:
        mm = functools.partial(grouped_matmul, interpret=interpret,
                               block_m=block_m)
    else:
        def mm(l, r, sizes):
            # f32 accumulation, rounded back per matmul — matches the
            # sort path's einsum precision in bf16.  The f32 compute is
            # expressed as input casts, NOT preferred_element_type: the
            # ragged_dot transpose emits cotangents in the ACCUMULATE
            # dtype, and that f32 leak into a bf16 graph trips the
            # lowering verifier once TP collectives surround it (the
            # cast form transposes dtype-soundly; bwd dtypes asserted
            # in tests).
            dt = l.dtype
            return lax.ragged_dot(l.astype(jnp.float32),
                                  r.astype(jnp.float32), sizes).astype(dt)
    h = mm(xs, params["w_up"], group_sizes)
    if act in ("swiglu", "geglu"):
        gt = mm(xs, params["w_gate"], group_sizes)
        h = h * (jax.nn.silu(gt) if act == "swiglu" else jax.nn.gelu(gt))
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jax.nn.relu(h)
    return mm(h, params["w_out"], group_sizes)
