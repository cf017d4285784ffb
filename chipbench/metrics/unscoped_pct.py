"""Device: the share of busy time in which no op under any of the
program's scopes (``scopes.SCOPES``) ran: the layer scan's own
machinery, the residual adds and ops the compiler made without
metadata.  With the seven ``*_ms`` scope metrics (and the exchange,
none on one chip) it partitions busy time."""
from chipbench import scopes


def read(ctx):
    return scopes.unscoped_pct(ctx)
