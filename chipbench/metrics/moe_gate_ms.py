"""Model, MoE layer: device milliseconds per step, per chip, of the ops
under the program's ``moe_gate`` scope: norm 2, the router logits,
routing, the aux and z losses and the routing counters."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "moe_gate")
