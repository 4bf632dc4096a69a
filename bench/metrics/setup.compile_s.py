"""Seconds jax spent tracing, lowering, compiling and loading compiled
programs inside the program's ``run_federation`` call (the ``fed.run``
span's ``compile_s`` counter; nested events count once)."""
from bench.scopes import counter


def read(ctx):
    if counter("fed.run.s") is None:
        return None
    return counter("fed.run.compile_s") or 0.0
