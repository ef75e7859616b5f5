"""Device time per step of the gradient sync, per chip, averaged over the
chips: the ops of the ``sync`` class of ``bench/scopes.py`` (under the
program's ``grad_sync`` scope: packing, each bucket's collective schedule,
unpacking)."""
from bench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, lambda p: scopes.classify(p) == "sync")
