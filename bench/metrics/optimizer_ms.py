"""Device time per step of the optimizer (clip, learning rate and update,
the program's ``optimizer`` scope), per chip, averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, lambda p: scopes.classify(p) == "optimizer")
