"""Train step, head: device milliseconds per step, per chip, of the ops
under the program's ``head_loss`` scope: the final norm, the output
head, its recomputed logits and the cross-entropy."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "head_loss")
