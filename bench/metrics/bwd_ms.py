"""Device time per step of the backward pass, its recompute included, per
chip, averaged over the chips: the ops under ``transpose(jvp(fwd))``. The
recompute (``rematted_computation``) is printed on an earlier line."""
from bench import scopes


def read(ctx):
    value = scopes.per_step_ms(ctx, lambda p: scopes.classify(p) in scopes.BACKWARD)
    if value is not None:
        recompute = scopes.per_step_ms(ctx, lambda p: scopes.classify(p) == "recompute")
        ctx.log(f"bwd_ms: recompute {recompute!r} ms of {value!r}")
    return value
