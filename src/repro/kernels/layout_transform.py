"""Layout-transform kernel (paper §3.2 "Layout Transform Optimization", Fig. 4).

HetuMoE's CUDA kernel packs tokens bound for the same expert into
contiguous memory with a warp-per-token gather.  The TPU version moves
rows by DMA: the index vector is scalar-prefetched into SMEM, source and
output stay in HBM (``memory_space=pl.ANY``), and each grid step starts
one row DMA per output row of its ``block_m`` slab, then waits for them.
Rows with idx < 0 copy a zero row (dropped slots).  Mosaic slices an HBM
array only along untiled dims or at whole (8, 128) tiles, so the kernels
see ``(N, d)`` rows as ``(N, d/128, 128)`` and a row is a leading index.

The VJP is the scatter-add kernel, the same transform run in the
opposite direction: its f32 accumulator stays in HBM too, and each grid
step reads the rows its gradient slab lands on by DMA, adds the slab and
writes the rows back.

Both directions of the MoE layout use ONE gather kernel:
  dispatch  out[r] = tokens[inv[r]]   (inv from the plan; -1 → zeros)
  combine   out[s·K+j] = buffer[slot[s,j]]  (then weighted-sum in jnp)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_M = 128


def _rows3(x: jax.Array) -> jax.Array:
    """View (N, d) rows as (N, d/128, 128).

    Mosaic slices an HBM array only along untiled dims or at whole
    (8, 128) tiles, so a single-row DMA needs the row on a leading dim;
    with d a multiple of 128 each row is then whole tiles."""
    N, d = x.shape
    return x.reshape(N, d // 128, 128) if d % 128 == 0 else x.reshape(N, 1, d)


def _gather_rows_kernel(idx_ref, src_hbm, zero_hbm, out_hbm, sem, *,
                        block_m: int, m: int):
    base = pl.program_id(0) * block_m
    n = jnp.minimum(block_m, m - base)
    dst = lambda r: out_hbm.at[pl.ds(base + r, 1)]

    # one row DMA per output row, straight HBM → HBM: the index is read
    # as an SMEM scalar, a dropped slot (idx < 0) copies the zero row
    def start(r, c):
        j = idx_ref[base + r]

        @pl.when(j >= 0)
        def _row():
            pltpu.make_async_copy(src_hbm.at[pl.ds(j, 1)], dst(r), sem).start()

        @pl.when(j < 0)
        def _zero():
            pltpu.make_async_copy(zero_hbm, dst(r), sem).start()
        return c

    def wait(r, c):
        pltpu.make_async_copy(zero_hbm, dst(r), sem).wait()   # same size
        return c

    lax.fori_loop(0, n, start, 0)
    lax.fori_loop(0, n, wait, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def gather_rows(src: jax.Array, idx: jax.Array, interpret: bool = True,
                block_m: int = DEFAULT_BLOCK_M):
    """out[i] = src[idx[i]] (0 where idx[i] < 0).  src (N, d), idx (M,).

    Differentiable: the VJP is the scatter-add kernel below (on TPU that
    is the same layout-transform run in the opposite direction).
    """
    return _gather_rows_fwd(src, idx, interpret, block_m)[0]


def _gather_rows_fwd(src, idx, interpret, block_m):
    # the (N, 0) token carries src's row count + dtype into the bwd pass
    # (shapes/dtypes are not valid residual leaves themselves)
    token = jnp.zeros((src.shape[0], 0), src.dtype)
    return _gather_rows_impl(src, idx, interpret=interpret,
                             block_m=block_m), (idx, token)


def _gather_rows_bwd(interpret, block_m, res, g):
    idx, token = res
    dsrc = scatter_add_rows(g, idx, token.shape[0], interpret=interpret,
                            block_m=block_m)
    return dsrc.astype(token.dtype), None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)

_HBM = pl.BlockSpec(memory_space=pl.ANY)


@functools.partial(jax.jit, static_argnames=("interpret", "block_m"))
def _gather_rows_impl(src: jax.Array, idx: jax.Array, *,
                      interpret: bool = True,
                      block_m: int = DEFAULT_BLOCK_M):
    M, = idx.shape
    src3 = _rows3(src)
    bm = min(block_m, M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(M, bm),),
        in_specs=[_HBM, _HBM],
        out_specs=_HBM,
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_gather_rows_kernel, block_m=bm, m=M),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M,) + src3.shape[1:], src.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), src3, jnp.zeros((1,) + src3.shape[1:], src.dtype))
    return out.reshape(M, src.shape[1])


def _scatter_add_kernel(idx_ref, g_ref, acc_hbm, out_hbm, buf, sem, *,
                        block_m: int, m: int):
    del acc_hbm                          # aliased to out_hbm
    base = pl.program_id(0) * block_m
    n = jnp.minimum(block_m, m - base)

    def read(j, r):
        return pltpu.make_async_copy(out_hbm.at[pl.ds(j, 1)],
                                     buf.at[pl.ds(r, 1)], sem)

    def write(j, r):
        return pltpu.make_async_copy(buf.at[pl.ds(r, 1)],
                                     out_hbm.at[pl.ds(j, 1)], sem)

    def each_valid_row(fn):
        def body(r, c):
            j = idx_ref[base + r]

            @pl.when(j >= 0)
            def _():
                fn(j, r)
            return c
        lax.fori_loop(0, n, body, 0)

    # read-modify-write of the accumulator rows this block lands on
    each_valid_row(lambda j, r: read(j, r).start())
    each_valid_row(lambda j, r: read(j, r).wait())
    buf[...] += g_ref[...]
    each_valid_row(lambda j, r: write(j, r).start())
    each_valid_row(lambda j, r: write(j, r).wait())


@functools.partial(jax.jit, static_argnames=("n", "interpret", "block_m"))
def scatter_add_rows(g: jax.Array, idx: jax.Array, n: int, *,
                     interpret: bool = True,
                     block_m: int = DEFAULT_BLOCK_M) -> jax.Array:
    """out (n, d) f32 with out[idx[i]] += g[i] (idx[i] < 0 skipped).

    The accumulator stays in HBM: each grid step DMAs in the rows its
    ``block_m`` gradient rows land on, adds, and DMAs them back.  Steps
    run in order, so duplicates in different blocks accumulate; within a
    block, rows sharing a destination are summed first (a same-index
    matrix times the block), so every duplicate writes back the same
    complete row and the DMA order does not matter."""
    M, d = g.shape
    bm = min(block_m, M)
    pad = (-M) % bm
    idx = jnp.concatenate([idx.astype(jnp.int32),
                           jnp.full((pad,), -1, jnp.int32)])
    gb = jnp.concatenate([g, jnp.zeros((pad, d), g.dtype)]).reshape(-1, bm, d)
    ib = idx.reshape(-1, bm)
    same = (ib[:, :, None] == ib[:, None, :]).astype(g.dtype)
    gsum = jnp.einsum("bij,bjd->bid", same, gb,
                      preferred_element_type=jnp.float32)
    g3 = _rows3(gsum.reshape(-1, d))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g3.shape[0] // bm,),
        in_specs=[pl.BlockSpec((bm,) + g3.shape[1:],
                               lambda i, idx_ref: (i, 0, 0)),
                  _HBM],
        out_specs=_HBM,
        scratch_shapes=[pltpu.VMEM((bm,) + g3.shape[1:], jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_scatter_add_kernel, block_m=bm, m=M),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n,) + g3.shape[1:], jnp.float32),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx, g3, jnp.zeros((n,) + g3.shape[1:], jnp.float32))
    return out.reshape(n, d)


# ---------------------------------------------------------------------------
# seed reference: the original row-per-step tiling, kept for benchmarking
# the blocked kernel against (bench_layout) and as the worst-case bound.
# ---------------------------------------------------------------------------

def _gather_row_kernel(idx_ref, src_ref, out_ref):
    i = pl.program_id(0)
    out_ref[...] = jnp.where(idx_ref[i] >= 0, src_ref[...], 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_rowstep(src: jax.Array, idx: jax.Array, *,
                        interpret: bool = True):
    """One (1, d) DMA per grid step — the seed tiling (do not use on the
    hot path; exists so benchmarks can quantify the blocked kernel's win)."""
    M, = idx.shape
    N, d = src.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[pl.BlockSpec((1, d),
                               lambda i, idx_ref: (jnp.maximum(idx_ref[i], 0), 0))],
        out_specs=pl.BlockSpec((1, d), lambda i, idx_ref: (i, 0)),
    )
    return pl.pallas_call(
        _gather_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), src.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), src)
