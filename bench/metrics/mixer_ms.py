"""Device time per step of the sequence mixers (``mixer/<kind>`` scopes),
forward, recompute and backward, per chip, averaged over the chips. Each
kind's share is printed on an earlier line."""
from bench import scopes


def read(ctx):
    value = scopes.per_step_ms(ctx, lambda p: scopes.mixer(p) is not None)
    if value is not None:
        kinds = {k: scopes.per_step_ms(ctx, lambda p, k=k: scopes.mixer(p) == k)
                 for k in scopes.MIXERS}
        ctx.log(f"mixer_ms: per kind {({k: v for k, v in kinds.items() if v is not None})!r}")
    return value
