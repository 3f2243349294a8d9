"""Device ms per trial-round in the FL round's ``fl.eval`` stage — the new
global model's loss and accuracy on the held-out set: the summed device time
of the traced window's ops that ``bench.scopes`` attributes to the stage ÷
the window's trial-rounds."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "eval")
