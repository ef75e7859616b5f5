"""Device time per step of the expert layer (the ops under the program's
``moe`` scope, all passes), per chip, averaged over the chips. Its parts
``route``, ``dispatch``, ``experts``, ``combine`` and ``shared`` are
printed on an earlier line."""
from bench import layer_paths

PARTS = ("route", "dispatch", "experts", "combine", "shared")


def read(ctx):
    value = layer_paths.per_step_ms(ctx, lambda name, parts: "moe" in parts)
    if value is not None:
        split = {k: layer_paths.per_step_ms(ctx, lambda name, parts, k=k: layer_paths.follows(parts, "moe", k))
                 for k in PARTS}
        ctx.log(f"moe_ms: per part {({k: v for k, v in split.items() if v is not None})!r}")
    return value
