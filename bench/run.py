"""Run one benchmark cell once, on the chips it asks for.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. One process: it finds the chips (and exits
nonzero, printing no result, without them), keeps JAX's compilation cache
in ``.jax_cache`` of the checkout (or where ``JAX_COMPILATION_CACHE_DIR``
says), builds the cell from its data files, warms up, measures for
``--seconds`` (``--trace 0``) or traces a few steps (``--trace 1``), holds
the checked steps to the plain reference, and prints one JSON line last.
Earlier lines break ``setup_s`` down and count compiles in the window; the
numbers compared for `correct` are the last lines on stderr.

``--rehearse`` runs the same path on any backend at the configuration's
and traffic's ``rehearsal`` sizes, and prints neither the result line nor
any device metric.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu would log to a fixed path in /tmp
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path.pop(0)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import registry  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds and counts of JAX's compile events (tracing, lowering,
    backend compilation) and persistent-cache hits, from ``jax.monitoring``."""

    def __init__(self, jax):
        self.seconds, self.counts = {}, {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        self.seconds[name] = self.seconds.get(name, 0.0) + secs
        self.counts[name] = self.counts.get(name, 0) + 1

    def _event(self, name, **_):
        self.counts[name] = self.counts.get(name, 0) + 1

    def compiles(self) -> int:
        """Programs traced or compiled so far."""
        return sum(n for k, n in self.counts.items()
                   if k.endswith("jaxpr_trace_duration") or k.endswith("backend_compile_duration"))

    def summary(self) -> str:
        short = {k.rsplit("/", 1)[-1]: v for k, v in self.seconds.items()}
        hits = self.counts.get("/jax/compilation_cache/cache_hits", 0)
        misses = self.counts.get("/jax/compilation_cache/cache_misses", 0)
        return " ".join(f"{k}={v!r}" for k, v in sorted(short.items())) + \
            f" cache_hits={hits} cache_misses={misses}"


def find_devices(jax, chips: int, rehearse: bool):
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU found (jax sees platform {devs[0].platform!r}); "
                 "this benchmark measures a TPU only")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, jax sees {len(devs)}")
    return devs[:chips]


def enable_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def rehearsal(cell):
    """The cell at the sizes its data files give for a run off the chip,
    held to the limits set from readings at those sizes."""
    cell.config = {**cell.config, **cell.config.get("rehearsal", {})}
    cell.traffic = {**cell.traffic, **cell.traffic.get("rehearsal", {})}
    cell.limits = cell.limits["rehearsal"]
    return cell


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.metric_module(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced_window(prog, steps: int, chips: int, keep: str | None, device: bool):
    import jax

    from bench import tracefile

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # the benchmark's own spans are enough
    options.enable_hlo_proto = False
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    t0, times, losses = prog.pipelined(steps=steps)
    jax.profiler.stop_trace()
    trace = tracefile.extract(TRACE_DIR, chips) if device else None
    if keep and device:
        tracefile.save(trace, keep)
    else:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return trace, times, losses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", help="write the traced intervals to this JSON file and "
                    "keep the profiler's own files in .bench_trace")
    args = ap.parse_args(argv)

    cell = registry.load_cell(args.workload, ROOT)
    if args.rehearse:
        cell = rehearsal(cell)

    import jax
    import numpy as np

    from bench import check, train
    from bench.peaks import peaks_for

    devs = find_devices(jax, cell.chips, args.rehearse)
    kind = devs[0].device_kind
    peaks = None if args.rehearse else peaks_for(kind)
    cache = enable_cache(jax)
    clock = CompileClock(jax)
    marks = {"jax_init_s": time.perf_counter() - T0}
    log(f"device: platform={devs[0].platform} kind={kind!r} used={len(devs)} jax={jax.__version__}")
    log(f"compile cache: {cache}")

    t = cell.traffic
    b1 = t["optimizer"]["b1"]
    prog = train.Program(cell, devs, args.seed)
    t1 = time.perf_counter()
    prog.init_state(args.seed)
    jax.block_until_ready((prog.params, prog.opt))
    marks["weights_s"] = time.perf_counter() - t1
    t1 = time.perf_counter()
    observed = prog.checked_steps(t["checked_steps"], b1)
    marks["checked_steps_s"] = time.perf_counter() - t1
    t1 = time.perf_counter()
    prog.pipelined(steps=t["warmup_steps"])
    marks["warmup_steps_s"] = time.perf_counter() - t1
    compiles_before = clock.compiles()
    setup_s = time.perf_counter() - T0
    log("setup: " + " ".join(f"{k}={v!r}" for k, v in marks.items()) + f" total_s={setup_s!r}")
    log(f"setup: compile {clock.summary()}")

    flops = train.flops_per_step(cell, prog.n_params)
    if args.trace:
        trace, times, losses = traced_window(prog, t["trace_steps"], len(devs), args.keep_trace,
                                              device=not args.rehearse)
        window_s = None
    else:
        t0, times, losses = prog.pipelined(seconds=args.seconds)
        window_s = times[-1] - t0
        gaps = np.diff([t0] + times)
    in_window = clock.compiles() - compiles_before
    attempted = len(losses)
    failed = sum(not math.isfinite(x) for x in losses)
    log(f"window: steps={attempted} failed={failed} compiles_in_window={in_window} "
        f"first_loss={losses[0]!r} last_loss={losses[-1]!r}")
    memory = None
    if not args.rehearse:
        memory = max(d.memory_stats()["peak_bytes_in_use"] for d in devs)
    tokens_per_step = prog.tokens_per_step
    prog.free()
    del prog
    gc.collect()

    metrics, device = {}, {"platform": devs[0].platform, "kind": kind, "count": len(devs),
                           "memory_peak_bytes": memory}
    breakdown = None
    if args.trace and not args.rehearse:
        from bench import tracefile

        lo, hi = tracefile.window(trace)
        first = min(ev[0][1] for ev in trace["ops"].values() if ev)
        last = max(ev[-1][2] for ev in trace["ops"].values() if ev)
        log(f"trace: host window [{lo}, {hi}] ns; device ops from {first} to {last} ns")
        ctx = train.TraceContext(trace=trace, lo=lo, hi=hi, steps=len(times), chips=len(devs),
                                 flops_per_step=flops, peaks=peaks, log=log)
        busy = [tracefile.busy_ns(trace, c, lo, hi) for c in sorted(trace["ops"])]
        device["busy_s"] = float(np.mean(busy)) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        log(f"trace: window_s={device['window_s']!r} busy_s per chip={[b / 1e9 for b in busy]!r}")
        metrics = per_layer(cell, ctx)
        breakdown = {"device_ops": tracefile.top_ops(trace, lo, hi),
                     "idle_gaps": tracefile.named_gaps(trace, sorted(trace["ops"])[0], lo, hi)}
    elif not args.trace:
        p95 = float(np.percentile(gaps, 95)) * 1e3  # linear between order statistics
        log(f"window: seconds={window_s!r} step_ms median={float(np.median(gaps)) * 1e3!r} "
            f"p95={p95!r} max={float(gaps.max()) * 1e3!r}")
        e2e = {"train_tokens_per_s": tokens_per_step * attempted / window_s,
               "step_ms_p95": p95, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]} for m in cell.end_to_end}

    t1 = time.perf_counter()
    ref = train.reference_observed(cell, devs, args.seed)
    values = check.readings(observed, ref)
    correct, table = check.judge(values, cell.limits)
    correct = correct and failed == 0
    names = train.leaf_names(cell)
    log(f"check: reference took {time.perf_counter() - t1!r} s; losses program={observed.losses!r} "
        f"reference={ref.losses!r}")
    for n, (v, leaf) in values.items():
        where = "" if leaf is None else (f" step {leaf}" if n == "loss_gap" else f" leaf {names[leaf]}")
        log(f"check: {n}={v!r}{where}")
    for n, row in table.items():
        print(f"{n} {row['value']!r} limit {row['limit']!r}", file=sys.stderr, flush=True)

    if args.rehearse:
        log("REHEARSAL " + json.dumps({"correct": correct, "attempted": attempted, "failed": failed,
                                       "checks": table}))
        return 0
    result = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = table
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
