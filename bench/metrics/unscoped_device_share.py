"""Share of the traced window's device time in ops that no FL round stage
claims, in %: device seconds of non-container ops that ``bench.scopes``
attributes to no ``fl.<stage>`` (per-trial init, loop plumbing, ops missing
from the program text) ÷ all non-container device seconds."""
from bench import scopes


def read(ctx):
    r = scopes.read(ctx)
    if r is None or r["total_s"] <= 0.0:
        return None
    return 100.0 * r["unscoped_s"] / r["total_s"]
