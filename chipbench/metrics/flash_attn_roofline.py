"""Attention kernel: the least time the chip could take for the
attention work of a step (``counts.flash_attention_work``, shared by the
chips), the larger of operations over peak and bytes over HBM bandwidth,
as a share of the kernels' device time.  At these shapes operations
bound it."""
from chipbench import counts
from chipbench.metrics import flash_attn_ms


def read(ctx):
    ms = flash_attn_ms.read(ctx)
    if ms is None:
        return None
    cell, peak = ctx.cell, ctx.peaks()
    flops, nbytes = counts.flash_attention_work(cell.model, cell.batch,
                                                cell.seq)
    least = max(flops / peak["bf16_flops"],
                nbytes / peak["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * least / (ms / 1e3)
