"""Device time per step of multi-head latent attention, forward, recompute
and backward, per chip, averaged over the chips: the ops under the
program's ``mixer/mla`` scope (projections, RoPE, layouts) and the splash
attention kernels, whose instructions carry no op_name and are found by
their names (``%splash_mha_*``; in this program only latent attention
launches them)."""
from bench import layer_paths


def read(ctx):
    return layer_paths.per_step_ms(
        ctx, lambda name, parts: layer_paths.follows(parts, "mixer", "mla") or name.startswith("%splash_mha"))
