"""Seconds to trace and lower the cell's program (``jax.jit(...).lower``),
on the benchmark's own host clock; none where the engine lowers inside
its own calls."""


def read(ctx):
    return ctx["spans"].get("trace_lower_s")
