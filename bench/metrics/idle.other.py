"""The rest of the device's idle share of the traced window, outside the
``fed.stage`` and ``fed.eval`` spans, in %: with ``idle.stage`` and
``idle.eval`` it adds up to ``idle_share.round``."""
from bench.scopes import idle_share


def read(ctx):
    return idle_share(ctx, "other")
