"""Device time by any component of the program's scope paths.

``bench/scopes.py`` keeps only the scope components its pattern knows, so
a layer named inside them (``mixer/mla``, ``moe/route``) is stripped from
its stored paths. This module keeps each op's whole op_name instead: it
takes the compiled step's instruction-to-op_name map once per traced run,
through ``scopes``, and applies the same rules (innermost op, an op
without a class takes its container's path). A kernel whose instruction
carries no op_name at all (the splash attention kernels) is found by its
own name.
"""
from __future__ import annotations

from bench import scopes

KEY = "op_names"


def _per_chip_ns(ctx) -> dict:
    """{chip: {(instruction name, op_name): innermost ns}}, memoised in the
    trace."""
    trace = ctx.trace
    memo = trace.setdefault("_by_op_name", {})
    if (ctx.lo, ctx.hi) not in memo:
        if KEY not in trace:
            names = scopes._compiled_step_names(ctx.log) or {}
            trace[KEY] = {c: [names.get(n.lstrip("%"), "") for n, _, _ in ops]
                          for c, ops in trace["ops"].items()}
        out = {}
        for c, ops in trace["ops"].items():
            paths = scopes.inherited(ops, trace[KEY][c])
            per = out.setdefault(c, {})
            for i, ns in enumerate(scopes.innermost_ns(ops, ctx.lo, ctx.hi)):
                if ns:
                    key = (ops[i][0], paths[i])
                    per[key] = per.get(key, 0.0) + ns
        memo[(ctx.lo, ctx.hi)] = out
    return memo[(ctx.lo, ctx.hi)]


def per_step_ms(ctx, pick) -> float | None:
    """Device ms per step per chip, averaged over the chips, of the ops that
    ``pick(instruction name, path components)`` accepts (components as
    ``scopes.components`` gives them); None where it accepts none."""
    total, seen = 0.0, False
    per_chip = _per_chip_ns(ctx)
    for paths in per_chip.values():
        for (name, path), ns in paths.items():
            if pick(name, scopes.components(path)):
                total += ns
                seen = True
    if not seen:
        return None
    return total / len(per_chip) / ctx.steps / 1e6


def follows(parts: list, a: str, b: str) -> bool:
    """Whether component ``b`` comes right after ``a`` on a path."""
    return any(x == a and y == b for x, y in zip(parts, parts[1:]))
