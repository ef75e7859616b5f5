"""In-kernel executor benchmark — one persistent launch per schedule replay.

The in-kernel executor's claim is structural, so this suite measures it
rather than asserting it: for points across the tuner grid it traces the
SAME :class:`~repro.comm.CollectivePlan` through the in-kernel executor
(``comm.executors.execute_inkernel``, one persistent Pallas launch) and the
compiled executor (``execute_compiled``, two launches per round), recording
the pallas launch count in the traced jaxpr, HLO instruction counts, and
per-round replay wall time. Rows land in the schema-gated
``experiments/inkernel_table.json`` (``comm.tables.load_inkernel_table``),
whose loader IS the regression gate: exactly ONE launch per replay, the
in-kernel round count equal to the compiled executor's, HLO flat in
``num_chunks``, and strictly below the compiled program at each group's
deepest point.

Counts and lower times are host-side quantities, but ``round_us`` executes
the replay, so ``--dryrun`` runs a smaller grid; entries are branded
``dryrun`` all the same so downstream consumers know which grid produced
them.
"""
from __future__ import annotations

import json
import os

from repro.comm.tables import load_inkernel_table

from .common import WorkerTimeoutError, run_worker

RANKS = [4, 8]
# (op, algo, M, num_chunks sweep) — chain-family points sweep the chunk
# count (the flatness axis); ring-family points pin K == n by design
POINTS = [
    ("bcast", "pipelined_chain", 1 << 16, (4, 8, 16)),
    ("bcast", "bidir_chain", 1 << 16, (4, 8, 16)),
    ("allreduce", "fused_rsb", 1 << 16, (4, 8, 16)),
    ("allreduce", "ring_allreduce", 1 << 16, (None,)),
    ("allgather", "ring_allgather", 1 << 16, (None,)),
    ("reduce_scatter", "ring_reduce_scatter", 1 << 16, (None,)),
]

WORKER = """
import json, time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.analysis.jaxpr import iter_eqns, pallas_eqns
from repro.comm import plan_collective, apply_plan


def eqn_count(jaxpr):
    return sum(1 for _ in iter_eqns(jaxpr))


def count_pallas(jaxpr):
    return len(pallas_eqns(jaxpr))


def hlo_count(text):
    return sum(1 for line in text.splitlines() if " = " in line)


def bench(n, points):
    mesh = jax.make_mesh((n,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    table = {}
    for op, algo, M, K in points:
        kw = {} if K is None else {"num_chunks": K}
        plan = plan_collective(op, M, n, algo=algo, **kw)
        lowered_sched = plan.lowered()
        rounds = max(lowered_sched.num_rounds, 1)
        elems = max(M // 4, 1)
        shape = (elems // n,) if op == "allgather" else (elems,)
        sds = jax.ShapeDtypeStruct(shape, jnp.float32)

        def g_ink(b):
            return apply_plan(plan, b, "data", inkernel=True)

        def g_cmp(b):
            return apply_plan(plan, b, "data", compiled=True)

        f_ink = jax.shard_map(g_ink, mesh=mesh, in_specs=(P(),), out_specs=P(),
                              check_vma=False)
        f_cmp = jax.shard_map(g_cmp, mesh=mesh, in_specs=(P(),), out_specs=P(),
                              check_vma=False)
        closed = jax.make_jaxpr(f_ink)(sds)
        t0 = time.perf_counter()
        low = jax.jit(f_ink).lower(sds)
        lower_s = time.perf_counter() - t0
        # the compiled executor walks the SAME lowered schedule object, so
        # its round count is recorded from its own plan lowering — the
        # loader gate rejects any drift between the two executors
        entry = {
            "M": M,
            "num_rounds": rounds,
            "compiled_rounds": max(plan.lowered().num_rounds, 1),
            "lane_classes": max(lowered_sched.num_classes, 1),
            "inkernel_launches": count_pallas(closed.jaxpr),
            "inkernel_jaxpr_eqns": max(eqn_count(closed.jaxpr), 1),
            "inkernel_lower_s": lower_s,
            "inkernel_hlo": max(hlo_count(low.as_text()), 1),
            "compiled_hlo": max(hlo_count(jax.jit(f_cmp).lower(sds).as_text()), 1),
        }
        x = jnp.zeros(shape, jnp.float32)
        fn = jax.jit(f_ink)
        fn(x).block_until_ready()
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            fn(x).block_until_ready()
        entry["round_us"] = (time.perf_counter() - t0) / reps / rounds * 1e6
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
    with open("experiments/inkernel_table.json", "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    # the loader IS the gate: single launch, matching round counts, flat and
    # compiled-beating HLO — reject the artifact at the source
    table = load_inkernel_table("experiments/inkernel_table.json")
    # timed-out points are recorded as explicit bench rows (derived.timeout),
    # NOT written into the schema-gated table — the gates only see measured
    # entries, and downstream consumers can see exactly which points are gone
    out = [
        {
            "name": f"inkernel/{key}",
            "us_per_call": float("nan"),
            "derived": {"timeout": True, "M": M},
        }
        for key, M in timed_out
    ]
    for key, e in sorted(table.items()):
        out.append(
            {
                "name": f"inkernel/{key}",
                "us_per_call": e["round_us"],
                "derived": {
                    "inkernel_launches": e["inkernel_launches"],
                    "inkernel_hlo": e["inkernel_hlo"],
                    "compiled_hlo": e["compiled_hlo"],
                    "inkernel_jaxpr_eqns": e["inkernel_jaxpr_eqns"],
                    "inkernel_lower_ms": e["inkernel_lower_s"] * 1e3,
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
