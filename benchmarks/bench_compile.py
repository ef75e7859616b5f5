"""Compile-cost benchmark — unrolled vs compiled executor program size.

The compiled schedule executor's claim is structural, so this suite measures
it rather than asserting it: for points across the tuner grid it traces and
lowers the SAME :class:`~repro.comm.CollectivePlan` through both executors
(``comm.executors.execute_collective`` unrolled vs ``execute_compiled``
fori_loop) and records jaxpr equation counts, HLO instruction counts, and
trace+lower wall time. Rows land in the schema-gated
``experiments/compile_table.json`` (``comm.tables.load_compile_table``);
:func:`repro.comm.tables.check_compile_flatness` is the CI compile-size
regression gate — the compiled executor's HLO instruction count must be
FLAT in ``num_chunks`` while the unrolled one grows monotonically.

Counts and lower times are host-side quantities (nothing executes), so
``--dryrun`` runs the same measurement on a smaller grid; entries are
branded ``dryrun`` all the same so downstream consumers know which grid
produced them.
"""
from __future__ import annotations

import json
import os

from repro.comm.tables import check_compile_flatness, load_compile_table

from .common import WorkerTimeoutError, run_worker

RANKS = [8, 16]
# (op, algo, M, num_chunks sweep) — chain-family points sweep the chunk
# count (the HLO-growth axis); ring-family points pin K == n by design
POINTS = [
    ("bcast", "pipelined_chain", 1 << 22, (4, 16, 64)),
    ("bcast", "bidir_chain", 1 << 22, (4, 16, 64)),
    ("allreduce", "fused_rsb", 1 << 22, (4, 16, 64)),
    ("allreduce", "ring_allreduce", 1 << 22, (None,)),
    ("allgather", "ring_allgather", 1 << 22, (None,)),
    ("reduce_scatter", "ring_reduce_scatter", 1 << 22, (None,)),
]

WORKER = """
import json, time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.analysis.jaxpr import iter_eqns
from repro.comm import plan_collective, apply_plan


def eqn_count(jaxpr):
    return sum(1 for _ in iter_eqns(jaxpr))


def hlo_count(text):
    return sum(1 for line in text.splitlines() if " = " in line)


def bench(n, points):
    mesh = jax.make_mesh((n,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    table = {}
    for op, algo, M, K in points:
        kw = {} if K is None else {"num_chunks": K}
        plan = plan_collective(op, M, n, algo=algo, **kw)
        lowered_sched = plan.lowered()
        elems = max(M // 4, 1)
        shape = (elems // n,) if op == "allgather" else (elems,)
        sds = jax.ShapeDtypeStruct(shape, jnp.float32)
        entry = {
            "M": M,
            "num_rounds": max(lowered_sched.num_rounds, 1),
            "lane_classes": max(lowered_sched.num_classes, 1),
        }
        for mode, flag in (("unrolled", False), ("compiled", True)):
            def g(b, flag=flag):
                return apply_plan(plan, b, "data", compiled=flag)
            f = jax.shard_map(g, mesh=mesh, in_specs=(P(),), out_specs=P(),
                              check_vma=False)
            entry[f"{mode}_jaxpr_eqns"] = max(
                eqn_count(jax.make_jaxpr(f)(sds).jaxpr), 1
            )
            t0 = time.perf_counter()
            low = jax.jit(f).lower(sds)
            entry[f"{mode}_lower_s"] = time.perf_counter() - t0
            entry[f"{mode}_hlo"] = max(hlo_count(low.as_text()), 1)
        table[f"n{n}/{op}/{algo}/K{plan.num_chunks}"] = entry
    return table
"""


def _point_worker(n, pt):
    return WORKER + f"""
print(json.dumps(bench({n}, {[pt]!r})))
"""


def rows(quick: bool = False, dryrun: bool = False, timeout: int = 560):
    ranks = RANKS[:1] if (quick or dryrun) else RANKS
    points = [
        (op, algo, M, ks[:2] if dryrun else ks) for op, algo, M, ks in POINTS
    ]
    table = {}
    timed_out = []
    for n in ranks:
        flat_points = [
            (op, algo, M, k) for op, algo, M, ks in points for k in ks
        ]
        worker = WORKER + f"""
print(json.dumps(bench({n}, {flat_points!r})))
"""
        try:
            table.update(run_worker(worker, devices=n, timeout=timeout, retries=1))
        except WorkerTimeoutError:
            # the whole-rank batch hung twice: re-run one worker PER POINT so
            # a single pathological point can't take the rest of the sweep
            # down with it — each point still gets the single retry
            for pt in flat_points:
                try:
                    table.update(
                        run_worker(
                            _point_worker(n, pt), devices=n,
                            timeout=timeout, retries=1,
                        )
                    )
                except WorkerTimeoutError:
                    op, algo, M, k = pt
                    timed_out.append((f"n{n}/{op}/{algo}/K{k or n}", M))
    if dryrun:
        for entry in table.values():
            entry["dryrun"] = True
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/compile_table.json", "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    table = load_compile_table("experiments/compile_table.json")  # schema gate
    check_compile_flatness(table)  # compile-size regression gate at source
    # timed-out points are recorded as explicit bench rows (derived.timeout),
    # NOT written into the schema-gated table — the gates only see measured
    # entries, and downstream consumers can see exactly which points are gone
    out = [
        {
            "name": f"compile/{key}",
            "us_per_call": float("nan"),
            "derived": {"timeout": True, "M": M},
        }
        for key, M in timed_out
    ]
    for key, e in sorted(table.items()):
        out.append(
            {
                "name": f"compile/{key}",
                "us_per_call": e["compiled_lower_s"] * 1e6,
                "derived": {
                    "unrolled_hlo": e["unrolled_hlo"],
                    "compiled_hlo": e["compiled_hlo"],
                    "unrolled_jaxpr_eqns": e["unrolled_jaxpr_eqns"],
                    "compiled_jaxpr_eqns": e["compiled_jaxpr_eqns"],
                    "unrolled_lower_ms": e["unrolled_lower_s"] * 1e3,
                    "compiled_lower_ms": e["compiled_lower_s"] * 1e3,
                    "num_rounds": e["num_rounds"],
                    "lane_classes": e["lane_classes"],
                },
            }
        )
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    for r in rows(quick=not args.full, dryrun=args.dryrun):
        print(r["name"], f"{r['us_per_call']:.1f}", json.dumps(r["derived"]))
