"""Device: the compiler's memory plan of the step per chip (arguments +
temporaries + outputs - aliased, from ``memory_analysis()``): a plan,
not a measured peak."""
from chipbench import hlo


def read(ctx):
    return hlo.plan_bytes(ctx.memory) / 2 ** 30
