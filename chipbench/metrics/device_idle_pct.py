"""Device: the share of the traced window in which no op ran on a chip,
averaged over the chips."""


def read(ctx):
    return 100.0 * ctx.reduced.idle_share
