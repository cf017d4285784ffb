"""MoE kernels / experts: device milliseconds per step, per chip, of the
ops under the program's ``moe_experts`` scope: the expert FFN matmuls
(the grouped kernels on the grouped path) and the expert weights' cast
to the compute dtype."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "moe_experts")
