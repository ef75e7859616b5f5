"""Share of the traced window in which no operation ran on a chip: one
minus the union of its "XLA Ops" intervals over the window, averaged over
the chips. The worst chip is printed on an earlier line."""
from bench import tracefile


def read(ctx):
    span = ctx.hi - ctx.lo
    idle = {c: 100.0 * (1.0 - tracefile.busy_ns(ctx.trace, c, ctx.lo, ctx.hi) / span)
            for c in sorted(ctx.trace["ops"])}
    if not idle:
        return None
    worst = max(idle, key=idle.get)
    ctx.log(f"device_idle_share: per chip {idle!r}; worst chip {worst}")
    return sum(idle.values()) / len(idle)
