"""Device ms per trial-round in the FL round's ``fl.select`` stage — every
strategy's scores and mask, the validity gate, and the gather of the
selected clients' batches: the summed device time of the traced window's ops
that ``bench.scopes`` attributes to the stage ÷ the window's trial-rounds."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "select")
