"""Model, MoE layer: device milliseconds per step, per chip, of the ops
under the program's ``moe_layout`` scope: the dispatch plan (the one
sort), the dispatch layout transform, its reverse and the combine."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "moe_layout")
