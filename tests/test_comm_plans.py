"""Host-level tests for the repro.comm collective-plan subsystem.

Property invariants (ISSUE acceptance):
  * every op's schedule converges in the numpy simulator — all ranks hold
    the op's reference result — across pow2 AND non-pow2 rank counts;
  * bytes-on-wire from the schedule (CollectivePlan.wire_bytes) match the
    cost-model accounting (plan.expected_wire_bytes);
  * both path classes (intra / inter_pod) produce valid plans;
  * manual decisions carry a finite predicted_s (the old NaN bug);
  * the experiments/*.json loaders accept the committed artifacts and fail
    loudly on schema violations.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline fallback — see tests/_compat.py
    from _compat import given, settings, strategies as st

from repro.comm import (
    CollectivePlan,
    TableSchemaError,
    decide,
    expected_wire_bytes,
    load_bench,
    load_tuner_table,
    plan_collective,
    tuner_from_table,
)
from repro.comm import schedules as comm_schedules
from repro.core.schedules import Round, Transfer
from repro.core.simulator import simulate_collective
from repro.core.tuner import OPS, Tuner

REPO = os.path.join(os.path.dirname(__file__), "..")

# (op, algo, needs_pow2)
OP_ALGOS = [
    ("reduce", "binomial_reduce", False),
    ("reduce", "pipelined_reduce_chain", False),
    ("allreduce", "reduce_then_bcast", False),
    ("allreduce", "fused_rsb", False),
    ("allreduce", "ring_allreduce", False),
    ("allgather", "ring_allgather", False),
    ("allgather", "doubling_allgather", True),
    ("reduce_scatter", "ring_reduce_scatter", False),
]


def _reference(op: str, data: list[np.ndarray], root: int):
    if op == "bcast":
        return data[root]
    total = np.sum(data, axis=0)
    if op in ("reduce", "allreduce"):
        return total
    if op == "allgather":
        return np.stack([data[r][r] for r in range(len(data))])
    if op == "reduce_scatter":
        return total
    raise AssertionError(op)


def _check_ragged(plan: CollectivePlan, rng) -> None:
    """Ragged convergence on the global row frame: each rank starts with its
    own rows valid (zeros elsewhere) and must end holding every row it is
    owed — all rows for allgatherv, its incoming (s, r) blocks for
    alltoallv."""
    sched = plan.schedule
    n = sched.n
    sz = np.asarray(plan.sizes, dtype=np.int64)
    full = rng.randn(sched.num_chunks, 3)
    off = np.concatenate([[0], np.cumsum(sz)])
    owner = np.zeros(sched.num_chunks, dtype=np.int64)
    if plan.op == "allgatherv":
        owner = np.repeat(np.arange(n), sz)
    else:
        owner = np.repeat(np.arange(n * n) // n, sz)
    data = [np.where((owner == r)[:, None], full, 0.0) for r in range(n)]
    out = simulate_collective(sched, data)
    if plan.op == "allgatherv":
        for r in range(n):
            np.testing.assert_array_equal(out[r], full, err_msg=f"rank {r}")
    else:
        m = sz.reshape(n, n)
        for r in range(n):
            for s in range(n):
                b = s * n + r
                lo, hi = off[b], off[b + 1]
                np.testing.assert_array_equal(
                    out[r][lo:hi], full[lo:hi], err_msg=f"rank {r} block {s}->{r}"
                )


def _check_plan(plan: CollectivePlan, rng) -> None:
    if plan.op in ("allgatherv", "alltoallv"):
        return _check_ragged(plan, rng)
    sched = plan.schedule
    n, root = sched.n, sched.root
    data = [rng.randn(sched.num_chunks, 3) for _ in range(n)]
    out = simulate_collective(sched, data)
    ref = _reference(plan.op, data, root)
    if plan.op == "bcast":
        for r in range(n):
            np.testing.assert_allclose(out[r], ref, rtol=1e-9, err_msg=f"rank {r}")
    elif plan.op == "reduce":
        np.testing.assert_allclose(out[root], ref, rtol=1e-9)
    elif plan.op == "allreduce":
        for r in range(n):
            np.testing.assert_allclose(out[r], ref, rtol=1e-9, err_msg=f"rank {r}")
    elif plan.op == "allgather":
        for r in range(n):
            np.testing.assert_allclose(out[r], ref, rtol=1e-9, err_msg=f"rank {r}")
    elif plan.op == "reduce_scatter":
        for r in range(n):
            np.testing.assert_allclose(out[r][r], ref[r], rtol=1e-9, err_msg=f"rank {r}")


def _expected_bytes(plan: CollectivePlan) -> float:
    """Cost-model accounting, including the reduce_then_bcast composite."""
    if plan.algo == "reduce_then_bcast":
        K = plan.schedule.num_chunks
        chunk = math.ceil(plan.M / K)
        inner = plan.schedule.name.split("[", 1)[1].rstrip("]")
        reduce_part = (plan.n - 1) * K * chunk
        return reduce_part + expected_wire_bytes("bcast", inner, plan.M, plan.n, K)
    return expected_wire_bytes(plan.op, plan.algo, plan.M, plan.n, plan.num_chunks)


@settings(max_examples=150, deadline=None)
@given(
    case=st.sampled_from(OP_ALGOS),
    n=st.integers(2, 33),
    root_seed=st.integers(0, 1000),
    chunks=st.integers(1, 9),
    inter_pod=st.booleans(),
    size_exp=st.integers(6, 24),
)
def test_op_schedules_converge_and_account(case, n, root_seed, chunks, inter_pod, size_exp):
    op, algo, needs_pow2 = case
    if needs_pow2:
        n = 1 << max(n.bit_length() - 1, 1)
    root = root_seed % n
    M = 1 << size_exp
    kw = {"num_chunks": chunks} if algo in ("pipelined_reduce_chain", "fused_rsb") else {}
    plan = plan_collective(op, M, n, root=root, algo=algo, inter_pod=inter_pod, **kw)
    plan.schedule.validate_ranks()
    _check_plan(plan, np.random.RandomState(root_seed))
    assert plan.wire_bytes() == _expected_bytes(plan), (
        plan.algo, plan.n, plan.num_chunks, plan.wire_bytes(), _expected_bytes(plan)
    )
    assert math.isfinite(plan.predicted_s)


@settings(max_examples=60, deadline=None)
@given(n=st.integers(2, 40), chunks=st.integers(1, 16), root_seed=st.integers(0, 99))
def test_fused_rsb_round_count(n, chunks, root_seed):
    """fused_rsb matches its closed form: K + 2n - 3 rounds, 2K(n-1) chunk
    transfers (each chunk crosses every edge once per phase)."""
    sched = comm_schedules.fused_rsb(n, root_seed % n, num_chunks=chunks)
    assert sched.num_rounds == chunks + 2 * n - 3
    assert sched.wire_chunks() == 2 * chunks * (n - 1)


def test_auto_plans_for_every_op():
    """'auto' resolves every op at every path class, pow2 or not."""
    t = Tuner()
    for op in OPS:
        for n in (2, 5, 8, 24):
            for inter_pod in (False, True):
                for M in (256, 1 << 20, 64 << 20):
                    plan = plan_collective(op, M, n, tuner=t, inter_pod=inter_pod)
                    assert math.isfinite(plan.predicted_s), (op, n, M)
                    if plan.schedule is not None:
                        plan.schedule.validate_ranks()
                        _check_plan(plan, np.random.RandomState(0))


def test_allreduce_tuner_windows():
    t = Tuner()
    assert t.select(256, 16, op="allreduce").algo == "reduce_then_bcast"
    big = t.select(256 << 20, 256, op="allreduce")
    assert big.algo == "ring_allreduce"  # bandwidth-optimal at scale
    mid = t.select(16 << 20, 8, op="allreduce")
    assert mid.algo in ("fused_rsb", "ring_allreduce")
    # non-pow2 ranks still tune (ring/fused need no pow2)
    assert t.select(1 << 20, 12, op="allreduce").algo != "noop"
    # allgather: doubling only on pow2
    assert t.select(1 << 20, 8, op="allgather").algo == "doubling_allgather"
    assert t.select(1 << 20, 12, op="allgather").algo == "ring_allgather"


def test_per_op_empirical_override_and_roundtrip(tmp_path):
    t = Tuner()
    M, n = 1 << 20, 8
    t.record(M, n, "ring_allreduce", n, measured_s=1e-9, op="allreduce")
    hit = t.select(M, n, op="allreduce")
    assert hit.source == "empirical" and hit.algo == "ring_allreduce"
    # the bcast table is keyed separately — unaffected
    assert t.select(M, n).source == "analytic"
    p = str(tmp_path / "table.json")
    t.save(p)
    assert Tuner.load(p).select(M, n, op="allreduce").algo == "ring_allreduce"


def test_manual_decisions_have_finite_predictions():
    """The old core.bcast._decide returned predicted_s=NaN for manual algos;
    manual and auto must now be comparable in reports/benchmark JSON."""
    from repro.core.bcast import _decide

    for algo in ("chain", "binomial", "pipelined_chain", "bidir_chain", "scatter_allgather"):
        if algo == "scatter_allgather":
            d = _decide(1 << 20, 8, algo, None, None, False)
        else:
            d = _decide(1 << 20, 12, algo, None, None, False)
        assert math.isfinite(d.predicted_s), (algo, d)
        assert d.source == "manual"
    for op in ("reduce", "allreduce", "allgather", "reduce_scatter"):
        for algo in ("pipelined_reduce_chain", "fused_rsb", "ring_allgather", "ring_reduce_scatter"):
            try:
                d = decide(op, 1 << 20, 8, algo=algo)
            except KeyError:
                continue  # algo not applicable to this op
            assert math.isfinite(d.predicted_s), (op, algo, d)


def test_one_shot_op_compatibility():
    """An op/one-shot mismatch raises instead of silently running the wrong
    collective (xla_psum for reduce_scatter would return the full sum)."""
    with pytest.raises(ValueError, match="cannot implement"):
        decide("reduce_scatter", 1 << 20, 8, algo="xla_psum")
    with pytest.raises(ValueError, match="cannot implement"):
        decide("allreduce", 1 << 20, 8, algo="xla_allgather")
    assert decide("allreduce", 1 << 20, 8, algo="xla_psum").algo == "xla_psum"
    assert decide("reduce", 1 << 20, 8, algo="xla_psum").algo == "xla_psum"


def test_trainer_tuner_table_knob(tmp_path):
    """RunConfig.tuner_table loads a calibrated table into the explicit sync
    modes (the bench_allreduce -> trainer pipeline)."""
    from repro.configs.base import RunConfig

    t = Tuner()
    t.record(1 << 20, 8, "ring_allreduce", 8, 1e-9, op="allreduce")
    p = str(tmp_path / "table.json")
    t.save(p)
    run = RunConfig(sync_mode="tuned_allreduce", tuner_table=p)
    loaded = Tuner.load(run.tuner_table)
    assert loaded.select(1 << 20, 8, op="allreduce").source == "empirical"


def test_round_allows_disjoint_dst_ranges_only():
    # fused_rsb's pattern: same dst, disjoint chunks — legal
    Round((Transfer(0, 1, 0, 1, combine=True), Transfer(2, 1, 1, 1)))
    # overlapping ranges at one dst — rejected
    with pytest.raises(ValueError):
        Round((Transfer(0, 1, 0, 1), Transfer(2, 1, 0, 1)))


# ---------------------------- experiments/ loaders --------------------------


def test_committed_artifacts_validate():
    table = load_tuner_table(os.path.join(REPO, "experiments", "tuner_table.json"))
    rows = load_bench(os.path.join(REPO, "experiments", "bench.json"))
    assert table and rows
    tuner = tuner_from_table(os.path.join(REPO, "experiments", "tuner_table.json"))
    # the loaded table drives decisions: pick any committed entry and check
    # the tuner reproduces it as an empirical hit
    key, entry = next(iter(table.items()))
    path_cls, n_s, M_s = key.split("/")
    d = tuner.select(int(M_s[1:]), int(n_s[1:]), inter_pod=(path_cls == "inter"))
    assert d.source == "empirical" and d.algo == entry["algo"]


@pytest.mark.parametrize(
    "mutate, msg_part",
    [
        (lambda t: t.update({"bogus/n8/M256": {"algo": "binomial", "num_chunks": 1, "predicted_us": 1.0}}), "unknown key"),
        (lambda t: t.update({"intra/n12/M256": {"algo": "binomial", "num_chunks": 1, "predicted_us": 1.0}}), "power of two"),
        (lambda t: t.update({"intra/n8/M256": {"algo": "binomial", "num_chunks": 1, "predicted_us": 1.0, "surprise": 2}}), "unknown entry fields"),
        (lambda t: t.update({"intra/n8/M256": {"algo": "warp_drive", "num_chunks": 1, "predicted_us": 1.0}}), "unknown bcast algo"),
        (lambda t: t.update({"intra/n8/M256": {"algo": "binomial", "num_chunks": 1, "predicted_us": float("nan")}}), "finite"),
        (lambda t: t.update({"intra/n8/M256": {"algo": "binomial", "num_chunks": 1}}), "missing required"),
    ],
)
def test_table_loader_rejects_bad_schemas(tmp_path, mutate, msg_part):
    table = {"intra/n4/M1024": {"algo": "binomial", "num_chunks": 1, "predicted_us": 3.0}}
    mutate(table)
    p = tmp_path / "tuner_table.json"
    p.write_text(json.dumps(table))
    with pytest.raises(TableSchemaError, match=msg_part):
        load_tuner_table(str(p))


def test_bench_loader_rejects_bad_rows(tmp_path):
    p = tmp_path / "bench.json"
    p.write_text(json.dumps([{"name": "x", "us_per_call": 1.0, "derived": {}, "huh": 1}]))
    with pytest.raises(TableSchemaError, match="unknown fields"):
        load_bench(str(p))
    p.write_text(json.dumps({"not": "a list"}))
    with pytest.raises(TableSchemaError, match="array"):
        load_bench(str(p))


# ---------------------------------------------------------------------------
# host-side plan cache (comm.plan.plan_cached)
# ---------------------------------------------------------------------------


def test_plan_cache_hits_and_keying():
    from repro.comm import cache_stats, plan_cache_clear, plan_cached

    plan_cache_clear()
    t = Tuner()
    a = plan_cached("allreduce", 1 << 20, 8, tuner=t)
    b = plan_cached("allreduce", 1 << 20, 8, tuner=t)
    assert a is b  # identical point -> the SAME frozen plan object
    info = cache_stats()
    assert info["hits"] == 1 and info["misses"] == 1
    # any key component splits the entry
    assert plan_cached("allreduce", 1 << 20, 8, tuner=t, inter_pod=True) is not a
    assert plan_cached("allreduce", 1 << 20, 6, tuner=t) is not a
    assert plan_cached("reduce", 1 << 20, 8, tuner=t) is not a
    assert plan_cached("allreduce", 1 << 20, 8, tuner=t, algo="fused_rsb") is not a
    # two tuners with EQUAL content share entries (fingerprint keying, not id)
    assert plan_cached("allreduce", 1 << 20, 8, tuner=Tuner()) is a


def test_plan_cache_invalidated_by_tuner_record():
    """Satellite (ISSUE): Tuner.record of a new empirical row must change
    the cache-key fingerprint — stale plans are never replayed after
    calibration."""
    from repro.comm import plan_cache_clear, plan_cached

    plan_cache_clear()
    t = Tuner()
    M, n = 1 << 20, 8
    before = plan_cached("allreduce", M, n, tuner=t)
    assert before.decision.source == "analytic"
    fp0 = t.fingerprint()
    t.record(M, n, "ring_allreduce", n, 1e-4, op="allreduce")
    assert t.fingerprint() != fp0
    after = plan_cached("allreduce", M, n, tuner=t)
    assert after is not before
    assert after.decision.source == "empirical"
    assert after.algo == "ring_allreduce"
    # re-querying the calibrated point hits the new entry, not the stale one
    assert plan_cached("allreduce", M, n, tuner=t) is after
    # record_overlap (a depth-only row) must also invalidate
    fp1 = t.fingerprint()
    t.record_overlap(M, n, 3, op="allreduce")
    assert t.fingerprint() != fp1
    deeper = plan_cached("allreduce", M, n, tuner=t)
    assert deeper is not after and deeper.decision.overlap_depth == 3


def test_plan_cache_bounded():
    from repro.comm import cache_stats, plan_cache_clear, plan_cached
    from repro.comm.plan import _PLAN_CACHE_MAX

    plan_cache_clear()
    t = Tuner()
    for i in range(_PLAN_CACHE_MAX + 40):
        plan_cached("bcast", 1024 + i, 4, tuner=t)
    assert cache_stats()["size"] <= _PLAN_CACHE_MAX


def test_decision_fused_path_roundtrip(tmp_path):
    """The tuned fused-path flag rides the empirical table: record ->
    select -> save/load all preserve it, and apply_plan's routing honors it
    over the round-count policy."""
    from repro.comm.api import _use_compiled

    t = Tuner()
    t.record(1 << 20, 8, "fused_rsb", 16, 1e-4, op="allreduce",
             extras={"fused_path": True})
    dec = t.select(1 << 20, 8, op="allreduce")
    assert dec.fused_path is True
    p = tmp_path / "table.json"
    t.save(str(p))
    dec2 = Tuner.load(str(p)).select(1 << 20, 8, op="allreduce")
    assert dec2.fused_path is True

    plan = plan_collective("allreduce", 1 << 20, 8, tuner=t)
    assert plan.schedule.num_rounds <= 256  # policy alone would say unrolled
    assert _use_compiled(plan, fused=True, compiled=None)
    assert not _use_compiled(plan, fused=True, compiled=False)

    bad = {"hw": "tpu-v5e", "max_chunks": 64,
           "table": {"allreduce:8:20:0": {"algo": "fused_rsb", "num_chunks": 4,
                                          "measured_s": 1.0, "fused_path": "yes"}}}
    p.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="fused_path"):
        Tuner.load(str(p))
