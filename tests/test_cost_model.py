"""Analytic model sanity (Eqs. 1-6) + tuner behaviour."""
from __future__ import annotations

import math

import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline fallback — see tests/_compat.py
    from _compat import given, settings, strategies as st

from repro.core import cost_model as cm
from repro.core.tuner import Tuner

HW = cm.TPU_V5E
B = HW.link_bw


def test_regimes():
    """Paper Sec. V: trees win small messages, pipelined chain / scatter-
    allgather win large messages."""
    n = 16
    small, large = 1024, 256 << 20
    assert cm.cost("binomial", small, n) < cm.cost("chain", small, n)
    assert cm.cost("binomial", small, n) < cm.cost("pipelined_chain", small, n)
    assert cm.cost("pipelined_chain", large, n) < cm.cost("binomial", large, n)
    assert cm.cost("scatter_allgather", large, n) < cm.cost("binomial", large, n)
    # pipelined chain approaches the bandwidth bound M/B for large M
    t = cm.cost("pipelined_chain", large, n)
    assert t < 2.2 * large / B


def test_direct_worst_at_scale():
    for M in (1024, 1 << 20):
        assert cm.cost("direct", M, 32) > cm.cost("binomial", M, 32)


@settings(max_examples=60, deadline=None)
@given(M=st.integers(1 << 14, 1 << 28), n=st.integers(3, 64))
def test_optimal_chunk_is_near_optimal(M, n):
    """C* (continuous minimizer) is within 2x of the best DISCRETE chunking
    over a wide scan — ceil(M/C) quantization makes exact local optimality
    false, but the closed form must stay competitive."""
    c_star = cm.optimal_chunk_bytes(M, n, HW, B)
    t_star = cm.t_pipelined_chain(M, n, HW, B, C=c_star)
    best = min(
        cm.t_pipelined_chain(M, n, HW, B, C=max(M / k, 1.0))
        for k in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
    )
    assert t_star <= 2.0 * best


@settings(max_examples=40, deadline=None)
@given(M=st.integers(1, 1 << 24), n=st.integers(2, 128))
def test_monotone_in_message_size(M, n):
    for algo in ("chain", "binomial", "pipelined_chain", "scatter_allgather"):
        if algo == "scatter_allgather" and (n & (n - 1)):
            continue
        assert cm.cost(algo, M, n) <= cm.cost(algo, 2 * M, n) + 1e-12


def test_host_staging_tradeoff():
    """Eq. 6: staging only pays off when M/B_host is small vs the tree."""
    n = 16
    assert cm.cost("knomial_staged", 256 << 20, n) > cm.cost("pipelined_chain", 256 << 20, n)


def test_interpod_pricing():
    t_intra = cm.cost("pipelined_chain", 64 << 20, 16, inter_pod=False)
    t_inter = cm.cost("pipelined_chain", 64 << 20, 16, inter_pod=True)
    assert t_inter > 2 * t_intra  # interpod bw is 4x slower


# ---------------------------- tuner ----------------------------------------


def test_tuner_windows():
    t = Tuner()
    assert t.select(256, 16).algo in ("binomial", "knomial")
    big = t.select(256 << 20, 16)
    assert big.algo in ("pipelined_chain", "scatter_allgather", "bidir_chain")
    assert big.num_chunks > 1 or big.algo == "scatter_allgather"
    # non-power-of-two n: scatter_allgather must not be chosen
    assert t.select(256 << 20, 12).algo != "scatter_allgather"


def test_tuner_empirical_override(tmp_path):
    t = Tuner()
    M, n = 1 << 20, 8
    analytic = t.select(M, n)
    t.record(M, n, "chain", 1, measured_s=1e-9)  # fake: chain measured fastest
    hit = t.select(M, n)
    assert hit.source == "empirical" and hit.algo == "chain"
    assert analytic.algo != "chain" or analytic.source == "analytic"
    # persistence round-trip
    p = str(tmp_path / "table.json")
    t.save(p)
    t2 = Tuner.load(p)
    assert t2.select(M, n).algo == "chain"


# ------------------- executor-path pricing (PR 8) ---------------------------


def test_t_exec_path_ordering():
    """For any multi-round schedule the single persistent launch is priced
    strictly below the per-round compiled loop, which is strictly below the
    fully unrolled program."""
    for rounds, classes in [(3, 1), (10, 2), (29, 2)]:
        ink = cm.t_exec_path("inkernel", rounds, classes, HW)
        comp = cm.t_exec_path("compiled", rounds, classes, HW)
        unr = cm.t_exec_path("unrolled", rounds, classes, HW)
        assert 0 < ink < comp <= unr
        if classes > 1:
            assert comp < unr
    # a 0-round noop costs at most one boundary on any path
    assert cm.t_exec_path("compiled", 0, 1, HW) == 0.0
    with pytest.raises(ValueError):
        cm.t_exec_path("warp_specialized", 4, 1, HW)


def test_calibrate_t_launch_from_committed_table():
    """The committed compile table must calibrate to a positive per-round
    lowering cost, and the per-n-group medians must agree within ~2x —
    boundary cost is a property of the toolchain, not the rank count."""
    import os

    from repro.comm.tables import load_compile_table

    path = os.path.join(os.path.dirname(__file__), "..",
                        "experiments", "compile_table.json")
    table = load_compile_table(path)
    t = cm.calibrate_t_launch(table)
    assert t > 0
    per_n = {}
    for key in table:
        n_group = key.split("/")[0]
        per_n.setdefault(n_group, {})[key] = table[key]
    medians = {g: cm.calibrate_t_launch(sub) for g, sub in per_n.items()
               if len({k.rsplit("/K", 1)[0] for k in sub}) >= 1}
    vals = [v for v in medians.values() if v > 0]
    assert len(vals) >= 2, f"need >=2 n-groups with multi-K sweeps, got {medians}"
    assert max(vals) <= 2.0 * min(vals), medians


def test_calibrate_t_launch_rejects_flat_table():
    with pytest.raises(ValueError):
        cm.calibrate_t_launch(
            {"n8/bcast/chain/K4": {"num_rounds": 4, "unrolled_lower_s": 0.1}}
        )


def test_tuner_exec_path_roundtrip(tmp_path):
    """record(exec_path=...) -> select() surfaces it; persistence keeps it;
    load() rejects a rotted value."""
    import json

    t = Tuner()
    M, n = 1 << 20, 8
    t.record(M, n, "pipelined_chain", 8, measured_s=1e-9,
             extras={"exec_path": "inkernel"})
    hit = t.select(M, n)
    assert hit.source == "empirical" and hit.exec_path == "inkernel"
    p = str(tmp_path / "table.json")
    t.save(p)
    assert Tuner.load(p).select(M, n).exec_path == "inkernel"
    with pytest.raises(ValueError):
        # a winning measurement with a bogus tier must be rejected, not stored
        t.record(M, n, "chain", 1, measured_s=1e-12,
                 extras={"exec_path": "warp_specialized"})
    from repro.core.tuner import TunerTableError

    blob = json.load(open(p))
    for entry in blob["table"].values():
        if "exec_path" in entry:
            entry["exec_path"] = "warp_specialized"
    bad = str(tmp_path / "bad.json")
    json.dump(blob, open(bad, "w"))
    with pytest.raises(TunerTableError):
        Tuner.load(bad)


def test_tuner_calibrate_picks_best():
    t = Tuner()
    costs = {"binomial": 3.0, "chain": 1.0, "pipelined_chain": 2.0, "knomial": 4.0,
             "scatter_allgather": 5.0, "direct": 6.0, "bidir_chain": 2.5}

    def fake_measure(algo, M, n, k):
        return costs[algo]

    t.calibrate(fake_measure, sizes=[1 << 16], n=8)
    assert t.select(1 << 16, 8).algo == "chain"


def test_hardware_for_device_kind():
    """Constants are looked up by jax's device_kind; an unknown kind is an
    error, not another chip's constants."""
    assert cm.hardware_for("TPU v5 lite") is cm.TPU_V5E
    with pytest.raises(ValueError, match="no Hardware constants"):
        cm.hardware_for("cpu")
