"""The program's own names for the layers of its train step.

The train step runs each layer under one flat ``jax.named_scope``
(``models/transformer.py``, ``core/moe.py``, ``training/train_step.py``),
so every compiled op carries its layer in its ``op_name`` metadata:
``jit(train_step)/jvp(embed)/...`` outside the layer scan,
``.../closed_call/attention/...`` inside it, and ``transpose(jvp(...))``
for the backward.  An op belongs to a scope when the scope is a whole
component of that path (``/<scope>/`` or ``(<scope>)``), never a part of
a longer name.  A program without the scopes reads ``None`` here.
"""
from __future__ import annotations

from typing import Optional, Tuple

SCOPES = ("embed", "attention", "moe_gate", "moe_layout", "moe_exchange",
          "moe_experts", "head_loss", "optimizer")


def needles(scope: str) -> Tuple[str, str]:
    """What an op's metadata holds when it ran under ``scope``."""
    return f"/{scope}/", f"({scope})"


def _seconds(ctx, scopes) -> float:
    return ctx.reduced.op_seconds([n for s in scopes for n in needles(s)])


def scope_ms(ctx, scope: str) -> Optional[float]:
    """Device milliseconds per step, per chip, of the ops under ``scope``;
    ``None`` where no op ran under it."""
    s = _seconds(ctx, [scope])
    return 1e3 * s / ctx.steps if s > 0 else None


def unscoped_pct(ctx) -> Optional[float]:
    """The share of busy time in which no op under any program scope ran;
    ``None`` where the program has no scope at all."""
    scoped = _seconds(ctx, SCOPES)
    if scoped <= 0:
        return None
    busy = ctx.reduced.busy_s
    return 100.0 * (busy - scoped) / busy
