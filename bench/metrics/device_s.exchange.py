"""Device seconds per traced round of the round program's operations in
the ``round.codec`` and ``round.mix`` scopes: the wire codec, the gossip
mix and the Eq. 4 aggregate (their union; mean over the devices)."""
from bench.scopes import device_s


def read(ctx):
    return device_s(ctx, ("round.codec", "round.mix"))
