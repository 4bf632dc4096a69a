"""Share of the traced window in which the device is idle while the host
is inside a ``fed.eval`` span (the per-round evaluation), in %."""
from bench.scopes import idle_share


def read(ctx):
    return idle_share(ctx, "fed.eval")
