"""Train step, optimizer: device milliseconds per step, per chip, of the
ops under the program's ``optimizer`` scope: the non-finite guard,
clipping, AdamW and the guard's select."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "optimizer")
