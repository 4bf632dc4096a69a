"""Device seconds per traced round of the round program's operations in
the ``round.student`` scope (their union, so overlapping operations count
once; mean over the devices)."""
from bench.scopes import device_s


def read(ctx):
    return device_s(ctx, ("round.student",))
