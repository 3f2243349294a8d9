"""Device ms per trial-round in the FL round's ``fl.materialize`` stage — the
round's client data built from its label plan: the synthetic images, the
label histograms (the ``label_hist`` kernel) and the client batches: the
summed device time of the traced window's ops that ``bench.scopes``
attributes to the stage ÷ the window's trial-rounds."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "materialize")
