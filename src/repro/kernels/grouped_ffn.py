"""Grouped (ragged) expert matmuls for the dropless dispatch mode.

The ``grouped`` dispatch packs tokens into an expert-sorted ``(M, d)``
buffer whose per-expert segment lengths are data-dependent; the expert
FFN then needs ``y[offs[e]:offs[e+1]] = x[offs[e]:offs[e+1]] @ w[e]`` —
a grouped matmul (MegaBlocks' dMoE primitive).  Two implementations:

``ragged``  ``jax.lax.ragged_dot`` — XLA's native ragged primitive, used
            as the jnp reference path.
``pallas``  Blocked kernel: grid ``(N/block_n, M/block_m, E)``; each
            row-block visits each expert, but a ``pl.when`` predicate
            skips (expert, block) pairs whose row ranges don't overlap —
            with sorted rows a block overlaps ~1-2 experts, so the MXU
            work is Σ_e ceil(n_e / block_m) tiles, not M/block_m · E.
            The group offsets (and each block's first / last expert, which
            clamp the weight index map so skipped steps fetch nothing new)
            are scalar-prefetched into SMEM; rows outside the active
            expert's range are masked before the dot.  The weights' lane
            dim is tiled by ``block_n`` so a block fits the default scoped
            VMEM at d = f = 2048.

Rows past ``offsets[-1]`` (the virtual drop bucket's tail under token
padding) belong to no expert and come out zero — matching ragged_dot.

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

The ``custom_vjp`` backward is kernelized too (MegaBlocks trains the
dMoE primitive in both directions) — no forward recompute, both
gradients straight off the residuals:

  dlhs  the SAME blocked grouped-matmul kernel with ``rhs`` transposed
        on its last two dims (the ``transpose_rhs`` flag — a tile-level
        transpose in-kernel, no HBM copy of the expert weights):
        ``dlhs[seg_e] = g[seg_e] @ rhs[e]ᵀ``.
  drhs  a segment-wise outer-product accumulation kernel: grid
        ``(E, K/block_n, N/block_n, M/block_m)``, the scalar-prefetched
        offsets predicate which row-blocks contribute to expert e's
        ``(K, N)`` gradient tile, masked rows zeroed, partial products
        accumulated in f32 (``drhs[e] = lhs[seg_e]ᵀ @ g[seg_e]``).
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


def _lane_tile(n: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is at most
    ``DEFAULT_BLOCK_N``; ``n`` itself when it is that small or has no such
    divisor (a block equal to the full dim always lowers)."""
    if n <= DEFAULT_BLOCK_N:
        return n
    for t in range(DEFAULT_BLOCK_N, 0, -128):
        if n % t == 0:
            return t
    return n


def _block_experts(offs: jax.Array, n_blocks: int, bm: int, M: int):
    """First and last expert whose segment can meet each row block.

    The weight index maps clamp the expert grid axis into this range, so
    the pipeline re-fetches an expert tile only when the expert actually
    changes — not once per (row block, expert) grid step."""
    E = offs.shape[0] - 1
    first = jnp.arange(n_blocks, dtype=jnp.int32) * bm
    last = jnp.minimum(first + bm, M) - 1
    seg = lambda r: jnp.minimum(
        jnp.searchsorted(offs[1:], r, side="right"), E - 1).astype(jnp.int32)
    return seg(first), seg(last)


def _row_mask(offs_ref, e, row0, block_m):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)
    return (rows >= offs_ref[e]) & (rows < offs_ref[e + 1])


def _overlaps(offs_ref, e, row0, block_m):
    return jnp.logical_and(offs_ref[e + 1] > row0,
                           offs_ref[e] < row0 + block_m)


def _grouped_matmul_kernel(offs_ref, elo_ref, ehi_ref, lhs_ref, rhs_ref,
                           out_ref, *, block_m: int, transpose_rhs: bool):
    i, e = pl.program_id(1), pl.program_id(2)

    @pl.when(e == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    row0 = i * block_m

    @pl.when(_overlaps(offs_ref, e, row0, block_m))
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

    Grid (out tiles, row blocks, E): each (row block, out tile) output
    block stays resident while the experts accumulate into it."""
    M, C = lhs.shape
    E, K, N = rhs.shape
    n_out = K if transpose_rhs else N
    bm = min(block_m, M)
    bn = _lane_tile(n_out)
    pad = (-M) % bm
    if pad:
        lhs = jnp.concatenate([lhs, jnp.zeros((pad, C), lhs.dtype)])
    nb = (M + pad) // bm
    offs = offsets.astype(jnp.int32)
    e_lo, e_hi = _block_experts(offs, nb, bm, M + pad)

    def w_map(j, i, e, offs, lo, hi):
        ee = jnp.clip(e, lo[i], hi[i])
        return (ee, j, 0) if transpose_rhs else (ee, 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_out // bn, nb, E),
        in_specs=[
            pl.BlockSpec((bm, C), lambda j, i, e, *_: (i, 0)),
            pl.BlockSpec((1, bn, N) if transpose_rhs else (1, K, bn), w_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, e, *_: (i, j)),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, block_m=bm,
                          transpose_rhs=transpose_rhs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M + pad, n_out), jnp.float32),
        interpret=interpret,
    )(offs, e_lo, e_hi, lhs, rhs)
    return (out[:M] if pad else out).astype(lhs.dtype)


def _grouped_drhs_kernel(offs_ref, blo_ref, bhi_ref, lhs_ref, g_ref, out_ref,
                         *, block_m: int):
    e, i = pl.program_id(0), pl.program_id(3)

    @pl.when(i == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    row0 = i * block_m

    @pl.when(_overlaps(offs_ref, e, row0, block_m))
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

    Grid (E, K tiles, N tiles, M/block_m): expert-major so each expert's
    (bk, bn) output tile stays resident while its row-blocks accumulate
    into it; the row-block index maps clamp into the expert's own blocks
    (no fetch of rows it never reads), and the offsets predicate skips
    blocks outside [offs[e], offs[e+1])."""
    M, K = lhs.shape
    _, N = g.shape
    E = offsets.shape[0] - 1
    bm = min(block_m, M)
    bk, bn = _lane_tile(K), _lane_tile(N)
    pad = (-M) % bm
    if pad:
        lhs = jnp.concatenate([lhs, jnp.zeros((pad, K), lhs.dtype)])
        g = jnp.concatenate([g, jnp.zeros((pad, N), g.dtype)])
    nb = (M + pad) // bm
    offs = offsets.astype(jnp.int32)
    b_lo = jnp.minimum(offs[:-1] // bm, nb - 1)
    b_hi = jnp.minimum(jnp.maximum(offs[1:] - 1, offs[:-1]) // bm, nb - 1)
    row = lambda e, i, lo, hi: jnp.clip(i, lo[e], hi[e])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(E, K // bk, N // bn, nb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda e, a, b, i, offs, lo, hi:
                         (row(e, i, lo, hi), a)),
            pl.BlockSpec((bm, bn), lambda e, a, b, i, offs, lo, hi:
                         (row(e, i, lo, hi), b)),
        ],
        out_specs=pl.BlockSpec((1, bk, bn), lambda e, a, b, i, *_: (e, a, b)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_drhs_kernel, block_m=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, K, N), jnp.float32),
        interpret=interpret,
    )(offs, b_lo.astype(jnp.int32), b_hi.astype(jnp.int32), lhs, g)


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
