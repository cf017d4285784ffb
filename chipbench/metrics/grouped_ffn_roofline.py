"""MoE kernels / experts: the least time the chip could take for the
expert matmuls of a step (the cell's counts module's
``grouped_matmul_work``, per chip), the larger of operations over peak
and bytes over HBM bandwidth, as a share of the grouped kernels' device
time (``grouped_ffn_ms``)."""
from chipbench.metrics import grouped_ffn_ms


def read(ctx):
    ms = grouped_ffn_ms.read(ctx)
    if ms is None:
        return None
    cell, peak = ctx.cell, ctx.peaks()
    flops, nbytes = ctx.counts.grouped_matmul_work(cell.model, cell.batch,
                                                   cell.seq, ctx.chips)
    least = max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
