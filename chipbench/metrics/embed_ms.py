"""Model, embedding: device milliseconds per step, per chip, of the ops
under the program's ``embed`` scope: the token gather and, in the
backward, its scatter-add gradient."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "embed")
