"""Reduction from a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to plain intervals first (``extract``): for each chip,
the (HLO instruction name, start_ns, end_ns) of every operation on its
"XLA Ops" line (a loop's op spans the ops nested in it), and for the host,
the benchmark's own spans (``bench.window`` brackets the counted steps). Everything after that is
arithmetic on intervals, kept here so that every PR computes the same
number the same way, and checked on a small recorded trace by
``bench/tests/test_tracefile.py``.
"""
from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def extract(trace_dir: str, chips: int) -> dict:
    """Intervals of one trace: ``{"ops": {chip: [[name, start, end], ...]},
    "spans": [[name, start, end], ...]}``, times in ns on the trace's clock.
    Only the first ``chips`` device planes are kept."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    ops, spans, seen = {}, [], []
    for plane in data.planes:
        seen.append(plane.name)
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = [[op_name(e.name), e.start_ns, e.start_ns + e.duration_ns]
                                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.start_ns + e.duration_ns])
    if len(ops) != chips:
        raise RuntimeError(f"found {OPS_LINE!r} lines for chips {sorted(ops)} of {chips}; planes {seen}")
    return {"ops": ops, "spans": spans}


def op_name(event: str) -> str:
    """The HLO instruction's name (``%while.12``) of a TPU op event, whose
    name is the whole instruction text."""
    return event.split(" = ", 1)[0]


def save(trace: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump({"ops": {str(k): v for k, v in trace["ops"].items()}, "spans": trace["spans"]}, f)


def load(path: str) -> dict:
    with open(path) as f:
        raw = json.load(f)
    return {"ops": {int(k): v for k, v in raw["ops"].items()}, "spans": raw["spans"]}


def window(trace: dict, span: str = "bench.window") -> tuple:
    """(start, end) of the host span that brackets the traced steps."""
    found = [(s, e) for n, s, e in trace["spans"] if n == span]
    if len(found) != 1:
        raise RuntimeError(f"expected one {span!r} span, found {len(found)}")
    return found[0]


def merged(intervals, lo, hi) -> list:
    """Union of intervals clipped to [lo, hi], as sorted disjoint pairs."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(trace: dict, chip: int, lo, hi) -> float:
    return float(sum(e - s for s, e in merged([(s, e) for _, s, e in trace["ops"][chip]], lo, hi)))


def idle_gaps(trace: dict, chip: int, lo, hi) -> list:
    """Idle stretches of one chip inside [lo, hi], as (start, end)."""
    busy = merged([(s, e) for _, s, e in trace["ops"][chip]], lo, hi)
    edges = [lo] + [x for pair in busy for x in pair] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]


def op_ns(trace: dict, chip: int, match, lo, hi) -> float:
    """Device time of the operations whose name ``match`` accepts, as the
    union of their intervals (a nested op is not counted twice)."""
    return float(sum(e - s for s, e in merged(
        [(s, e) for n, s, e in trace["ops"][chip] if match(n)], lo, hi)))


def op_events(trace: dict, chip: int, match, lo, hi) -> list:
    return [(n, s, e) for n, s, e in trace["ops"][chip] if match(n) and s >= lo and e <= hi]


def top_ops(trace: dict, lo, hi, k: int = 10) -> list:
    """The k operation names with the most device time, summed over chips
    and averaged per chip, in seconds."""
    chips = sorted(trace["ops"])
    total = {}
    for c in chips:
        for n, s, e in trace["ops"][c]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                total[n] = total.get(n, 0.0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / len(chips) / 1e9] for n, t in ranked]


def named_gaps(trace: dict, chip: int, lo, hi, k: int = 10) -> list:
    """The k longest idle gaps of one chip, each named by the benchmark's
    host span that overlaps it most ("none" where no span does)."""
    spans = [(n, s, e) for n, s, e in trace["spans"] if n != "bench.window"]
    out = []
    for s, e in sorted(idle_gaps(trace, chip, lo, hi), key=lambda g: g[0] - g[1])[:k]:
        best, cover = "none", 0
        for n, hs, he in spans:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = n, c
        out.append([best, (e - s) / 1e9])
    return out
