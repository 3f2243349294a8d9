"""Device ms per trial-round in the FL round's ``fl.train`` stage — local
training of the selected clients: forward, backward and the optimizer step
(under fedsgd, one gradient each): the summed device time of the traced
window's ops that ``bench.scopes`` attributes to the stage ÷ the window's
trial-rounds."""
from bench import scopes


def read(ctx):
    return scopes.stage_ms(ctx, "train")
