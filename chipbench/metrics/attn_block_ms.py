"""Attention: device milliseconds per step, per chip, of the ops under
the program's ``attention`` scope: norm 1, the projections, RoPE and
the flash kernels (``flash_attn_ms`` is the kernels' part)."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "attention")
