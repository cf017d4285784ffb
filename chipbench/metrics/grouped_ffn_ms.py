"""MoE kernels / experts: device milliseconds per step, per chip, of the
grouped-matmul Pallas kernels of the dropless path (``kernels/
grouped_ffn.py``, under the program's ``moe_experts`` scope): the
forward and the input-gradient kernel (``_grouped_matmul_impl``) and the
weight-gradient kernel (``_grouped_drhs_impl``), found by the compiled
step's op names.  ``None`` on a path that does not run them."""
NEEDLES = ("jit(_grouped_matmul_impl)/pallas_call",
           "jit(_grouped_drhs_impl)/pallas_call")


def read(ctx):
    s = ctx.reduced.op_seconds(NEEDLES)
    return 1e3 * s / ctx.steps if s > 0 else None
