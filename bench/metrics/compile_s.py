"""Seconds of ``.compile()`` of the cell's program, on the benchmark's own
host clock: an XLA compile, or the load from the persistent cache on a hit;
none where the engine compiles inside its own calls."""


def read(ctx):
    return ctx["spans"].get("compile_s")
