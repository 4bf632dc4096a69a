"""Model FLOPs of the traced rounds over the traced window and the bf16
peak of all the cell's chips, in %: the whole round's share of the peak,
staging, evaluation and idle gaps included.  The window is the
profiler's, with the Python tracer off.

A round is composed here, once for every family: over all the cell's
nodes, student training (three forwards a training sample), teacher
training where the traffic trains it (``alpha_s >= alpha_limit``), the
exact Eq. 3 pass (one student forward a local sample) and the evaluation
of one node's student on the test samples.  The forward of one sample is
the family's count, ``bench/counts/<family>.py``'s
``sample_flops(config, part)``."""
from pathlib import Path

from bench.harness import family_count, nodes

BENCH = Path(__file__).resolve().parent.parent


def round_flops(cell) -> int:
    config, traffic = cell["config"], cell["traffic"]
    count = family_count(config["family"], BENCH)
    images = nodes(cell) * config["images_per_node"]
    student = count.sample_flops(config, "student")
    total = images * (3 * student + student)
    if traffic["alpha_s"] >= traffic["alpha_limit"]:
        total += images * 3 * count.sample_flops(config, "teacher")
    return total + config["test_images"] * student


def read(ctx):
    rounds = ctx.out["traced_rounds"]
    if not rounds or ctx.window_s <= 0:
        return None
    flops = rounds * round_flops(ctx.cell)
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops"]
                            * ctx.cell["chips"])
