"""Attention kernel: device milliseconds per step of the flash-attention
kernels, forward and backward (the ops under ``kernels/flash_attention``'s
``pallas_vmem`` scope), per chip."""
NEEDLES = ("pallas_vmem/pallas_call",)


def read(ctx):
    s = ctx.reduced.op_seconds(NEEDLES)
    return 1e3 * s / ctx.steps if s > 0 else None
