"""The clip+AdamW plane sweep's share of its HBM roofline, in %: the
bytes of one round's sweeps over every node's plane
(``bench/counts/plane.opt_sweep_round_bytes``) times the traced rounds,
over the device time of the Pallas calls under ``round.student`` in the
traced window and the chip's HBM bandwidth."""
from bench.counts.plane import opt_sweep_round_bytes
from bench.roofline import hbm_share


def read(ctx):
    return hbm_share(ctx, "round.student",
                     opt_sweep_round_bytes(ctx.cell["config"],
                                           ctx.cell["traffic"]))
