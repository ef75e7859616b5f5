"""Device time per step of the forward pass, per chip, averaged over the
chips: the ops under the program's ``fwd`` scope that are neither
transposed nor recomputed (``bench/scopes.py`` holds the rules)."""
from bench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, lambda p: scopes.classify(p) == "forward")
