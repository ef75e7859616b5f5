"""Readings the limits of `correct` are set from, for one cell, in one
process on the cell's chips (never run by the benchmark itself):

    python bench/calibrate.py --workload <cell> --seeds 101 102 ... [--control 3]

For every seed: the program's checked steps (one compiled step, weights
made anew per seed) against the plain reference. On the first
``--control`` seeds, each of these put in the program's place against the
same reference: the control (the reference with fp8 matmuls), the
reference over only the first half of every batch, and, on more than one
chip, the program with its gradient exchange left out. A step that returns
its state unchanged reads 1 on grad_gap and update_gap by construction and
needs no run. Prints one JSON object of all readings last.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path.pop(0)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import registry  # noqa: E402
from bench.run import enable_cache, find_devices, log, rehearsal  # noqa: E402


def program_readings(cell, devs, seeds, faulty=False):
    from bench import check, train

    if faulty:
        import repro.train.train_step as ts

        ts.pallreduce_tree = lambda tree, *a, **k: tree
    b1 = cell.traffic["optimizer"]["b1"]
    prog = train.Program(cell, devs, seeds[0])
    out = {}
    for s in seeds:
        t = time.perf_counter()
        prog.init_state(s)
        out[s] = prog.checked_steps(cell.traffic["checked_steps"], b1)
        log(f"program seed {s}: losses {out[s].losses!r} ({time.perf_counter() - t!r} s)")
    prog.free()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload, ROOT)
    if args.rehearse:
        cell = rehearsal(cell)
    import jax

    from bench import check, train

    devs = find_devices(jax, cell.chips, args.rehearse)
    enable_cache(jax)
    names = train.leaf_names(cell)
    seeds, few = args.seeds, args.seeds[: args.control]
    runs = {"program": program_readings(cell, devs, seeds)}
    if len(devs) > 1:
        runs["no_exchange"] = program_readings(cell, devs, few, faulty=True)
    refs = {}
    for s in seeds:
        t = time.perf_counter()
        refs[s] = train.reference_observed(cell, devs, s)
        log(f"reference seed {s}: losses {refs[s].losses!r} ({time.perf_counter() - t!r} s)")
    runs["control_fp8"] = {s: train.reference_observed(cell, devs, s, fp8=True) for s in few}
    half = cell.traffic["global_batch"] // 2
    runs["half_batch"] = {s: train.reference_observed(cell, devs, s, rows=half) for s in few}

    table = {}
    for kind, by_seed in runs.items():
        table[kind] = {}
        for s, obs in by_seed.items():
            r = check.readings(obs, refs[s])
            table[kind][s] = {n: [v, None if i is None else (i if n == "loss_gap" else names[i])]
                              for n, (v, i) in r.items()}
            log(f"{kind} seed {s}: " + " ".join(f"{n}={v[0]!r} ({v[1]})" for n, v in table[kind][s].items()))
    summary = {kind: {n: [min(v[n][0] for v in t.values()), max(v[n][0] for v in t.values())]
                      for n in check.NAMES} for kind, t in table.items()}
    for kind, row in summary.items():
        log(f"summary {kind}: " + " ".join(f"{n} min={lo!r} max={hi!r}" for n, (lo, hi) in row.items()))
    result = {"cell": cell.name, "seeds": seeds, "readings": table, "summary": summary}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
