"""Train step: the model operations of the traced steps (the cell's
counts module's ``step_model_flops``: routed experts only, no
recomputation) over the traced window's host seconds, the chips and one
chip's bf16 peak."""


def read(ctx):
    cell = ctx.cell
    flops = ctx.counts.step_model_flops(cell.model, cell.batch, cell.seq)
    peak = ctx.peaks()["bf16_flops"]
    return 100.0 * flops * ctx.steps / ctx.window_s / ctx.chips / peak
