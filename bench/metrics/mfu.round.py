"""Model FLOPs of the traced rounds (``bench/counts/resnet.round_flops``)
over the traced window and the chip's bf16 peak, in %: the whole round's
share of the peak, staging, evaluation and idle gaps included.  The
window is the profiler's, with the Python tracer off."""
from bench.counts.resnet import round_flops


def read(ctx):
    cfg, traffic = ctx.cell["config"], ctx.cell["traffic"]
    rounds = ctx.out["traced_rounds"]
    if not rounds or ctx.window_s <= 0:
        return None
    teacher_on = traffic["alpha_s"] >= traffic["alpha_limit"]
    flops = rounds * round_flops(cfg, teacher_on, cfg["test_images"])
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops"])
