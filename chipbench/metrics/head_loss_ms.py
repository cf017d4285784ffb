"""Train step, head: device milliseconds per step, per chip, of the ops
under the program's ``head_loss`` scope: the final norm, the output
head's logits, the cross-entropy and the head's gradients, which the
fused cross-entropy takes in its forward pass."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "head_loss")
