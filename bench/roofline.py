"""A Pallas kernel's share of its HBM roofline, from the ``plain`` trace.

The round program's Pallas kernels reach the trace as custom calls whose
``op_name`` ends in ``pallas_call`` (the trace adds a ``:``) under the
scope of the phase that calls them
(``jit(round_fn)/.../vmap(round.student)/pallas_call:``).  The share is
the bytes the traced rounds' calls need (``bench/counts/plane.py``) over
their device time and the chip's HBM bandwidth, in %: the least time
the chip could take over the time it took.  The kernels are elementwise
sweeps, so bandwidth bounds them, not operations.
"""
from __future__ import annotations

import re
from typing import Optional

from bench import scopes as S

PALLAS = re.compile(r"(?:^|/)pallas_call(?=:|$)")


def hbm_share(ctx, scope: str, round_bytes: int) -> Optional[float]:
    """Share of the HBM roofline of the Pallas calls under ``scope``
    inside the traced window, in % (mean over the devices);
    ``round_bytes`` are the bytes those calls need in one round.  None
    where the trace holds no such call."""
    from bench.harness import TRACE_DIR
    rounds = ctx.out.get("traced_rounds")
    lo, hi = ctx.trace.window
    shares = []
    for ops in S.load_ops(str(TRACE_DIR / "plain")).values():
        ns = sum(o.end - o.start for o in ops
                 if lo <= o.start and o.end <= hi
                 and PALLAS.search(o.op_name)
                 and S.has_scope(o.op_name, scope))
        if rounds and ns > 0:
            shares.append(100.0 * rounds * round_bytes
                          / (ns / 1e9 * ctx.peaks["hbm_bytes_per_s"]))
    return sum(shares) / len(shares) if shares else None
