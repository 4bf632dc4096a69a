"""Bytes a node puts on the wire per round, in MB (1e6 bytes): the
program's packed accounting (``extras["avg_sent_packed_gb"]``, every
copy of the student and prototypes it sends, at the codec's width) over
the rounds of the run."""


def read(ctx):
    v = ctx.out.get("wire_mb_per_node_round")
    return v if v else None
