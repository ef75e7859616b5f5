"""Ragged-collective benchmark — allgatherv/alltoallv across skew regimes.

For a sweep of (rank count, size-vector pattern) points this suite plans
each ragged op through the skew-aware tuner (``comm.plan_collective`` with
``sizes=``), prices every candidate algorithm analytically, replays the
chosen schedule in the round-accurate simulator clock, and records the
schedule's wire-byte accounting. Rows land in the schema-gated
``experiments/ragged_table.json`` (``comm.tables.load_ragged_table`` —
the gate rebuilds every schedule from its size vector and rejects entries
whose wire bytes drift from the closed-form accounting).

The sweep spans the regimes the skew-aware cost model separates: uniform
vectors (bandwidth-bound, ring territory), one-hot skew (latency-bound,
doubling territory), zero-sized ranks, and incast alltoallv matrices
(store-and-forward ring territory). ``dryrun=True`` brands every entry —
the numbers are cost-model/simulator stand-ins, not measurements; the
non-dryrun mode additionally measures the SPMD entry points
(``pallgatherv``/``palltoallv``) on simulated host devices.
"""
from __future__ import annotations

import json
import os

from repro.comm.plan import expected_wire_bytes, plan_collective
from repro.comm.tables import load_ragged_table
from repro.core.cost_model import skew_ratio
from repro.core.tuner import Tuner

from .common import run_worker

RANKS = [4, 8]
ROW_BYTES = 4096  # bytes per ragged row (elems * itemsize)

# (pattern, per-rank row counts as a function of n)
GATHERV_PATTERNS = [
    ("uniform", lambda n: [8] * n),
    ("skewed", lambda n: [8 * (r + 1) for r in range(n)]),
    ("onehot", lambda n: [64] + [0] * (n - 1)),
    ("zero_rank", lambda n: [8] * (n - 1) + [0]),
]
A2AV_PATTERNS = [
    ("uniform", lambda n: [[4] * n for _ in range(n)]),
    ("incast", lambda n: [[16 if d == 0 else 1 for d in range(n)] for _ in range(n)]),
    ("zero_blocks", lambda n: [[(s + d) % 3 for d in range(n)] for s in range(n)]),
]

MEASURE_RAGGED = """
import time, json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.comm import pallgatherv, palltoallv

def measure(op, n, sizes, elems, reps=5):
    mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
    rng = np.random.RandomState(0)
    if op == "allgatherv":
        rows = max(max(sizes), 1)
        fn = lambda v: pallgatherv(v, "x", sizes=tuple(sizes))
    else:
        m = np.asarray(sizes).reshape(n, n)
        rows = max(int(m.sum(axis=1).max()), 1)
        fn = lambda v: palltoallv(v, "x", sizes=[list(r) for r in m])
    x = jnp.asarray(rng.randn(n * rows, elems).astype(np.float32))
    out_spec = P() if op == "allgatherv" else P("x")
    f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("x"),
                          out_specs=out_spec, check_vma=False))
    jax.block_until_ready(f(x))  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter(); jax.block_until_ready(f(x))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))
"""


def _flat(sizes):
    if sizes and isinstance(sizes[0], list):
        return [v for row in sizes for v in row]
    return list(sizes)


def rows(quick: bool = False, dryrun: bool = False):
    ranks = RANKS[:1] if quick else RANKS
    table = {}
    out = []
    for n in ranks:
        points = [("allgatherv", name, fn(n)) for name, fn in GATHERV_PATTERNS]
        points += [("alltoallv", name, fn(n)) for name, fn in A2AV_PATTERNS]
        for op, pattern, sizes in points:
            flat = _flat(sizes)
            total = sum(flat)
            M = total * ROW_BYTES
            auto = plan_collective(op, M, n, tuner=Tuner(), sizes=sizes)
            candidates = (
                ("ring_allgatherv", "doubling_allgatherv")
                if op == "allgatherv"
                else ("pairwise_alltoallv", "ring_alltoallv")
            )
            for algo in candidates:
                if algo == "doubling_allgatherv" and n & (n - 1):
                    continue
                plan = plan_collective(op, M, n, algo=algo, tuner=Tuner(), sizes=sizes)
                canonical = list(plan.sizes)
                entry = {
                    "sizes": canonical,
                    "row_bytes": ROW_BYTES,
                    "wire_bytes": plan.wire_bytes(),
                    "predicted_us": plan.predicted_s * 1e6,
                    "rounds": len(plan.schedule.rounds),
                    "auto_algo": auto.algo,
                    "skew": skew_ratio(canonical),
                }
                if dryrun:
                    entry["dryrun"] = True
                assert plan.wire_bytes() == expected_wire_bytes(
                    op, algo, M, n, sizes=tuple(canonical)
                ), f"wire accounting drift at {op}/{algo}/n{n}/{pattern}"
                table[f"{op}/{algo}/n{n}/{pattern}"] = entry
                derived = {
                    "pattern": pattern,
                    "skew": entry["skew"],
                    "wire_bytes": entry["wire_bytes"],
                    "rounds": entry["rounds"],
                    "chosen": auto.algo,
                    "timed_rounds_us": plan.timed_rounds_s() * 1e6,
                }
                if not dryrun and algo == auto.algo:
                    worker = MEASURE_RAGGED + f"""
res = {{"t": measure({op!r}, {n}, {flat!r}, {ROW_BYTES // 4})}}
print(json.dumps(res))
"""
                    res = run_worker(worker, devices=n)
                    derived["measured_us"] = res["t"] * 1e6
                out.append(
                    {
                        "name": f"ragged/{op}/n{n}/{pattern}/{algo}",
                        "us_per_call": entry["predicted_us"],
                        "derived": derived,
                    }
                )
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/ragged_table.json", "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    load_ragged_table("experiments/ragged_table.json")  # schema gate at source
    return out


if __name__ == "__main__":
    for r in rows(quick=True, dryrun=True):
        print(r["name"], r["us_per_call"], json.dumps(r["derived"]))
