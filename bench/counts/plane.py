"""HBM bytes of the student plane's optimizer sweep, counted from the layout.

The stacked engine keeps every node's student in one fp32 plane
``[N, R, 512]``: each parameter tensor takes ``ceil(size / 512)`` rows,
and ``R`` is their sum rounded up to a multiple of 8.  The clip+AdamW
sweep (``kernels/opt_update``), one call per local step over all nodes,
reads the gradient, the parameters and both moments and writes the
parameters and both moments, each once: ``7 * N * R * 512 * 4`` bytes,
and a round makes ``local_epochs * images_per_node // batch_size`` of
them (``N`` from ``harness.nodes``).  Its runtime scalars (learning
rate, clip scale, two bias corrections: at most 16 bytes a node) are
left out; the clip's global-norm reduction is an XLA fusion of its own,
outside the kernel.
"""
from __future__ import annotations

from bench import harness
from bench.counts.resnet import param_sizes

COLS = 512
F32 = 4


def _rows(size: int) -> int:
    return -(-size // COLS)


def plane_rows(config: dict) -> int:
    """``R`` of the student plane ``[N, R, 512]``."""
    rows = sum(_rows(s) for s in param_sizes(config, "student"))
    return -(-rows // 8) * 8


def opt_sweep_bytes(cell: dict) -> int:
    """HBM bytes of one clip+AdamW sweep call over every node's plane."""
    return 7 * harness.nodes(cell) * plane_rows(cell["config"]) * COLS * F32


def opt_sweep_round_bytes(cell: dict) -> int:
    """HBM bytes of one round's sweeps: one a local step."""
    config = cell["config"]
    steps = config["images_per_node"] // config["batch_size"]
    return cell["traffic"]["local_epochs"] * steps * opt_sweep_bytes(cell)

