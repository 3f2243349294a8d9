"""``label_hist`` kernel's share of its roofline, in %: the least time the
histograms' required work takes (labels and mask read, counts written —
``bench.work.label_hist_bytes``; memory-bound, its FLOPs are nil) ÷ the
summed device time of the kernel's events in the traced window."""
from bench import work


def read(ctx):
    t = ctx["trace"].time_of("label_hist_kernel") if ctx["trace"] else 0.0
    if t <= 0.0:
        return None
    return work.roofline_share(0, ctx["work"]["label_hist_bytes"], t,
                               ctx["peak"])[0]
