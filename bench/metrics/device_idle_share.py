"""Share of the traced window in which no operation ran on the device, in
%: 1 − (union of device op intervals ÷ window), the mean over the chips."""


def read(ctx):
    if not ctx["trace"]:
        return None
    return 100.0 * ctx["trace"].idle_share
