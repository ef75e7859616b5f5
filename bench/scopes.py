"""Device time by the program's own layer names.

The program names its layers with ``jax.named_scope``: ``fwd`` around the
loss (its backward and recompute come out as ``transpose(jvp(fwd))`` and
``.../rematted_computation``), ``optimizer``, ``grad_sync`` (with ``pack``,
``bucket<i>`` and ``unpack`` inside the bucketed allreduce),
``mixer/<kind>``, ``mlp`` and ``head``. The names reach each compiled HLO
instruction's ``op_name`` metadata, which the profiler's "XLA Ops" events
do not carry. So the traced run maps instruction names to op_names from
the compiled step's text (``op_names``) and keeps the paths beside the
trace's ``ops`` under the key ``scopes``: one list per chip, parallel to
``ops[chip]``.

The rules live here, so every reader counts the same way:

- each nanosecond of a chip's busy time goes to the innermost op running
  (an op nested in a ``while``, ``conditional`` or ``call`` counts, its
  container does not, for that stretch);
- an op whose path has no class takes the path of the op that contains
  it (XLA makes and moves ops inside loop bodies without metadata, or with
  only its tail);
- a path's class is decided by its scope components, first match wins:
  ``grad_sync`` sync, ``optimizer`` optimizer, ``fwd`` under a
  ``transpose(...)`` backward (``recompute`` where
  ``rematted_computation`` is on the path too), ``fwd`` forward, else
  unattributed;
- separately, ``mixer/<kind>`` names a sequence mixer, whatever the pass.
"""
from __future__ import annotations

import json
import re

from bench import tracefile

KEY = "scopes"
CLASSES = ("sync", "optimizer", "backward", "recompute", "forward", "unattributed")
BACKWARD = ("backward", "recompute")
MIXERS = ("mlstm", "slstm", "mamba", "attn")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\bmetadata=\{[^}]*?\bop_name=\"((?:[^\"\\]|\\.)*)\"",
                    re.M)
_WRAPPED = re.compile(r"^([\w\-]+)\((.*)\)$")
_SCOPE = re.compile(r"^(fwd|optimizer|grad_sync|pack|unpack|bucket\d+|mixer|mlp|head|"
                    r"rematted_computation|" + "|".join(MIXERS) + r")$")


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} of every instruction of an HLO module's
    text that carries an op_name (names without the leading ``%``)."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def components(op_name: str) -> list:
    """The path's components with transform wrappers peeled:
    ``transpose(jvp(fwd))`` gives ``transpose(``, ``jvp(`` and ``fwd``."""
    out = []
    for c in op_name.split("/"):
        m = _WRAPPED.match(c)
        while m:
            out.append(m.group(1) + "(")
            c = m.group(2)
            m = _WRAPPED.match(c)
        out.append(c)
    return out


def scope_path(op_name: str) -> str:
    """The op_name stripped to its scope components and transforms, e.g.
    ``transpose(/jvp(/fwd/rematted_computation/mixer/mlstm``."""
    keep = [c for c in components(op_name)
            if _SCOPE.match(c) or c in ("transpose(", "jvp(")]
    return "/".join(keep)


def classify(path: str) -> str:
    """One of ``CLASSES`` for a scope path (or a raw op_name)."""
    parts = components(path)
    if "grad_sync" in parts:
        return "sync"
    if "optimizer" in parts:
        return "optimizer"
    if "transpose(" in parts and "fwd" in parts:
        return "recompute" if "rematted_computation" in parts else "backward"
    if "fwd" in parts:
        return "forward"
    return "unattributed"


def mixer(path: str) -> str | None:
    """The sequence mixer's kind on a path, or None."""
    parts = components(path)
    for a, b in zip(parts, parts[1:]):
        if a == "mixer" and b in MIXERS:
            return b
    return None


def attach(trace: dict, names: dict) -> None:
    """Store each op's scope path under ``trace[KEY]``, parallel to
    ``trace["ops"]``, from a {instruction name: op_name} map."""
    paths = {}
    for ops in trace["ops"].values():
        for n, _, _ in ops:
            if n not in paths:
                paths[n] = scope_path(names.get(n.lstrip("%"), ""))
    trace[KEY] = {c: [paths[n] for n, _, _ in ops] for c, ops in trace["ops"].items()}


def innermost_ns(ops: list, lo, hi) -> list:
    """Nanoseconds of [lo, hi] that each op of one chip's list ran as the
    innermost op: where intervals nest, the latest-starting open op takes
    the time. The sum is the chip's busy time (the union of the ops)."""
    out = [0.0] * len(ops)
    spans = [(max(s, lo), min(e, hi), i) for i, (_, s, e) in enumerate(ops)]
    spans = sorted((x for x in spans if x[1] > x[0]), key=lambda x: (x[0], -x[1]))
    stack, t = [], lo

    def advance(to):
        nonlocal t
        while stack:
            s, e, i = stack[-1]
            end = min(e, to)
            if end > t:
                out[i] += end - t
                t = end
            if e <= to:
                stack.pop()
            else:
                break
        t = max(t, to)

    for s, e, i in spans:
        advance(s)
        stack.append((s, e, i))
    advance(hi)
    return out


def inherited(ops: list, paths: list) -> list:
    """Each op's path, or where that has no class the path of the op that
    contains it: ops that XLA makes or moves inside a loop body carry no
    metadata, or only its tail, and belong to the loop's layer."""
    out = list(paths)
    unclassed = {p: classify(p) == "unattributed" for p in set(paths)}
    stack = []  # (end, index) of the ops open at the current start
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        s, e = ops[i][1], ops[i][2]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if unclassed[paths[i]] and stack and stack[-1][0] >= e:
            out[i] = out[stack[-1][1]]
        stack.append((e, i))
    return out


def by_path(trace: dict, chip: int, lo, hi) -> dict:
    """{scope path: innermost ns} of one chip, kept in the trace for the
    next reader."""
    memo = trace.setdefault("_by_path", {})
    if (chip, lo, hi) not in memo:
        ops, out = trace["ops"][chip], {}
        paths = inherited(ops, trace[KEY][chip])
        for i, ns in enumerate(innermost_ns(ops, lo, hi)):
            if ns:
                out[paths[i]] = out.get(paths[i], 0.0) + ns
        memo[(chip, lo, hi)] = out
    return memo[(chip, lo, hi)]


def by_class(trace: dict, chip: int, lo, hi) -> dict:
    """{class: innermost ns} of one chip, every class of ``CLASSES``."""
    out = dict.fromkeys(CLASSES, 0.0)
    for path, ns in by_path(trace, chip, lo, hi).items():
        out[classify(path)] += ns
    return out


def per_step_ms(ctx, pick) -> float | None:
    """Device ms per step per chip, averaged over the chips, of the ops
    whose path ``pick`` accepts; None where no op's path is accepted."""
    if not has_scopes(ctx):
        return None
    total, seen = 0.0, False
    for c in sorted(ctx.trace["ops"]):
        for path, ns in by_path(ctx.trace, c, ctx.lo, ctx.hi).items():
            if pick(path):
                total += ns
                seen = True
    if not seen:
        return None
    return total / len(ctx.trace["ops"]) / ctx.steps / 1e6


def has_scopes(ctx) -> bool:
    """Whether the trace's ops carry scope paths, finding them from the
    compiled step the first time (logging the summary once)."""
    trace = ctx.trace
    if KEY not in trace:
        attach(trace, _compiled_step_names(ctx.log) or {})
    found = any(classify(p) != "unattributed" for p in {p for ps in trace[KEY].values() for p in ps})
    if found and not trace.get("_summarised"):
        trace["_summarised"] = True
        _summary(ctx)
    return found


def _summary(ctx) -> None:
    """Earlier lines: unattributed share and the ten scope paths with the
    most device time."""
    trace, chips = ctx.trace, sorted(ctx.trace["ops"])
    paths, classes = {}, dict.fromkeys(CLASSES, 0.0)
    for c in chips:
        for p, ns in by_path(trace, c, ctx.lo, ctx.hi).items():
            paths[p] = paths.get(p, 0.0) + ns
        for k, ns in by_class(trace, c, ctx.lo, ctx.hi).items():
            classes[k] += ns
    busy = sum(classes.values())
    if not busy:
        return
    per = len(chips) * ctx.steps * 1e6
    ctx.log("scopes: ms per step per chip " + json.dumps({k: v / per for k, v in classes.items()})
            + f"; busy {busy / per!r}; unattributed share {100.0 * classes['unattributed'] / busy!r}%")
    top = sorted(paths.items(), key=lambda kv: -kv[1])[:10]
    ctx.log("scopes: top paths (ms per step per chip) "
            + json.dumps([[p or "(none)", ns / per] for p, ns in top]))


def _compiled_step_names(log) -> dict | None:
    """{instruction name: op_name} of the step the traced run ran, from its
    compiled text. The run's own command line names the cell and seed; the
    step is built and lowered again, and its executable comes from the
    compile cache: the one the run ran. JAX's cache leaves metadata out of
    its key, so that executable may have been compiled from a source that
    differs only in scope names; then the step is compiled once more with
    metadata in the key and the two texts are aligned (``align``). None,
    logged, where no names can be had."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    args, _ = ap.parse_known_args(sys.argv[1:])
    if args.workload is None or args.seed is None:
        log("scopes: no --workload/--seed on the command line; no scope names")
        return None
    import jax

    from bench import registry, train

    prog = None
    try:
        cell = registry.load_cell(args.workload)
        prog = train.Program(cell, jax.devices()[:cell.chips], args.seed)
        prog.init_state(args.seed)
        lowered = prog.step_fn.lower(prog.params, prog.opt, prog.batch(0))
        ran = lowered.compile().as_text()
        names = op_names(ran)
        if _scoped(names.values()) or not _SOURCE_SCOPE.search(lowered.as_text(debug_info=True)):
            return names
        log("scopes: the cached step carries no scope names; compiling it with metadata in the key")
        key = "jax_compilation_cache_include_metadata_in_key"
        before = getattr(jax.config, key)
        jax.config.update(key, True)
        jax.clear_caches()
        try:
            fresh = prog.step_fn.lower(prog.params, prog.opt, prog.batch(0)).compile().as_text()
        finally:
            jax.config.update(key, before)
        names = align(ran, fresh)
        if names is None:
            log("scopes: the two compiled texts differ beyond names and metadata; no scope names")
        return names
    except Exception:  # a reader reports nothing rather than fail the run
        import traceback

        log("scopes: could not read the compiled step's op names:\n" + traceback.format_exc())
        return None
    finally:
        if prog is not None:
            prog.free()


_SOURCE_SCOPE = re.compile(r"[/(]fwd[)/]")
_NAME = re.compile(r"%[\w.\-]+")
_TABLES = {"FileNames", "FunctionNames", "FileLocations", "StackFrames"}


def _scoped(op_names_) -> bool:
    return any("fwd" in components(n) for n in op_names_)


def _canonical(text: str) -> tuple:
    """(lines, names): the HLO text without metadata or source tables, and
    its instruction and computation names in order of first appearance."""
    lines, skip = [], False
    for line in text.splitlines():
        if line.strip() in _TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            lines.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    body = "\n".join(lines)
    order = list(dict.fromkeys(_NAME.findall(body)))
    index = {n: f"%_{i}" for i, n in enumerate(order)}
    return _NAME.sub(lambda m: index[m.group(0)], body), order


def align(ran: str, fresh: str) -> dict | None:
    """{instruction name in ``ran``: op_name of the same instruction in
    ``fresh``}, for two compiled texts of one program that differ only in
    names and metadata (metadata changes the numbering of names); None
    where they differ otherwise."""
    (a, names_a), (b, names_b) = _canonical(ran), _canonical(fresh)
    if a != b:
        return None
    meta = op_names(fresh)
    return {x.lstrip("%"): meta[y.lstrip("%")] for x, y in zip(names_a, names_b)
            if y.lstrip("%") in meta}


def load(path: str) -> dict:
    """A kept trace with its scope paths (``tracefile.load`` keeps only
    ``ops`` and ``spans``)."""
    trace = tracefile.load(path)
    with open(path) as f:
        raw = json.load(f)
    if KEY in raw:
        trace[KEY] = {int(k): v for k, v in raw[KEY].items()}
    return trace
