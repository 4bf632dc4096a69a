"""Host seconds of the program's node-state set-up (the ``fed.init``
span) less the compiles inside it (``fed.init.compile_s``), so that it
and ``setup.compile_s`` do not overlap."""
from bench.scopes import counter


def read(ctx):
    init = counter("fed.init.s")
    if init is None:
        return None
    return init - (counter("fed.init.compile_s") or 0.0)
