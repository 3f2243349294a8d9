"""Device ms per trial-round in the FL round's ``fl.aggregate`` stage — the
masked weighted mean (the ``weighted_agg`` kernel), the server step and the
empty-selection guard: the summed device time of the traced window's ops
that ``bench.scopes`` attributes to the stage ÷ the window's trial-rounds."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "aggregate")
