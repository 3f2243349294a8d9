"""``weighted_agg`` kernel's share of its roofline, in %: the least time of
the masked weighted means' required work (K×P f32 read, P written, 2KP
FLOPs — ``bench.work``; memory-bound) ÷ the summed device time of the
kernel's events in the traced window."""
from bench import work


def read(ctx):
    t = ctx["trace"].time_of("weighted_agg_kernel") if ctx["trace"] else 0.0
    if t <= 0.0:
        return None
    w = ctx["work"]
    return work.roofline_share(w["weighted_agg_flops"], w["weighted_agg_bytes"],
                               t, ctx["peak"])[0]
