"""Device time per step of the gradient sync, per chip, averaged over the
chips: the union of the intervals of the collective operations, found by
their XLA op names (the unrolled executor's collective-permute start and
done pairs, an all-reduce), and of the Pallas combine kernel where the
executor launches one, over the traced steps."""
import re

from bench import tracefile

COLLECTIVE = re.compile(r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all")
KERNEL = re.compile(r"merge_kernel|fused_combine")


def is_sync(name: str) -> bool:
    return bool(COLLECTIVE.search(name) or KERNEL.search(name))


def read(ctx):
    chips = sorted(ctx.trace["ops"])
    per_chip = [tracefile.op_ns(ctx.trace, c, is_sync, ctx.lo, ctx.hi) for c in chips]
    if not any(per_chip):
        return None
    ctx.log(f"grad_sync_ms: per chip per step {[t / 1e6 / ctx.steps for t in per_chip]!r}")
    return sum(per_chip) / len(per_chip) / 1e6 / ctx.steps
