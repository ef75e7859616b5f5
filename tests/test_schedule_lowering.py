"""Host-side tests for the schedule compiler (core.schedules.lower_schedule)
and the compile-cost artifact gate.

ISSUE acceptance: the lowering's dense round tables replay bit-identically
to the schedule-level numpy simulator for every op/algo across pow2 and
non-pow2 rank counts and chunk sweeps; lane partitions are hoisted (one
lowering per schedule, cached) with pinned lane counts for the multi-lane
schedules; the committed ``experiments/compile_table.json`` passes the
compile-size regression gate (compiled HLO flat in num_chunks, unrolled
growing, trace+lower cheaper at the grid's largest chunk points).
"""
from __future__ import annotations

import os

import numpy as np
import pytest

from repro.comm import schedules as comm_schedules
from repro.comm.schedules import build_op, fused_rsb, ring_allreduce_schedule
from repro.comm.tables import (
    TableSchemaError,
    check_compile_flatness,
    load_compile_table,
)
from repro.core.schedules import (
    bidirectional_chain,
    build,
    lane_partition,
    lower_schedule,
)
from repro.core.simulator import simulate_collective, simulate_lowered

REPO = os.path.join(os.path.dirname(__file__), "..")
RNG = np.random.RandomState(0)


def _schedules(n: int, K: int):
    yield build("pipelined_chain", n, 1 % n, num_chunks=K)
    yield build("bidir_chain", n, 0, num_chunks=K)
    yield fused_rsb(n, 0, K)
    yield build("binomial", n)
    yield build("chain", n)
    yield build("direct", n)
    yield ring_allreduce_schedule(n)
    yield build_op("allgather", "ring_allgather", n)
    yield build_op("reduce_scatter", "ring_reduce_scatter", n)
    yield build_op("reduce", "pipelined_reduce_chain", n, num_chunks=K)
    yield build_op("reduce", "binomial_reduce", n)
    if n & (n - 1) == 0 and n >= 4:
        yield build("scatter_allgather", n)
        yield build_op("allgather", "doubling_allgather", n)


@pytest.mark.parametrize("n", [2, 3, 4, 6, 8])
@pytest.mark.parametrize("K", [1, 4, 7])
def test_lowered_replay_matches_simulator(n, K):
    """simulate_lowered (the compiled executor's numpy twin) is bit-identical
    to simulate_collective on the original schedule — every algo, pow2 and
    non-pow2 n, divisible and awkward chunk counts."""
    for sched in _schedules(n, K):
        data = [RNG.randn(sched.num_chunks, 3) for _ in range(n)]
        want = simulate_collective(sched, data)
        got = simulate_lowered(lower_schedule(sched), data)
        for r in range(n):
            assert np.array_equal(want[r], got[r]), (sched.name, n, K, r)


def test_lowering_is_cached_per_schedule():
    """The O(T^2) lane partition runs once per schedule, not once per use:
    two equal schedules share one lowering object."""
    a = lower_schedule(fused_rsb(8, 0, 16))
    b = lower_schedule(fused_rsb(8, 0, 16))
    assert a is b


def test_lane_counts_pinned_bidir_and_fused_rsb():
    """Satellite: pinned lane counts for the multi-lane schedules. The bidir
    chain splits every steady-state round into exactly two direction lanes;
    fused_rsb runs a reduce lane + a bcast lane concurrently once the bcast
    phase wakes up."""
    n, K = 8, 16
    bidir = lower_schedule(bidirectional_chain(n, 0, K))
    counts = bidir.lane_counts()
    # fill rounds ramp up; the steady middle is 2 lanes (right + left chain)
    assert max(counts) == 2
    assert counts[K // 2] == 2
    assert bidir.num_classes == 2

    fr = lower_schedule(fused_rsb(n, 0, K))
    counts = fr.lane_counts()
    # first rounds are reduce-only (1 lane); once chunk 0 is fully reduced
    # (round n-1) the bcast chain joins: exactly 2 lanes mid-schedule
    assert counts[0] == 1
    assert counts[n] == 2
    assert max(counts) == 2
    assert fr.num_classes == 2
    # one class carries the (combining) reduce lane, the other the
    # (overwriting) bcast lane — combine flags are per ROUND per class
    assert fr.classes[0].combine.any() and not fr.classes[1].combine.any()

    # single-lane schedules stay single-class; ring_allreduce's two phases
    # (combining reduce-scatter rounds, then overwriting allgather rounds)
    # share ONE class thanks to the per-round combine flag
    assert lower_schedule(build("pipelined_chain", n, 0, num_chunks=K)).num_classes == 1
    assert lower_schedule(build_op("allgather", "ring_allgather", n)).num_classes == 1
    ring_ar = lower_schedule(ring_allreduce_schedule(n))
    assert ring_ar.num_classes == 1
    assert ring_ar.classes[0].combine[: n - 1].all()
    assert not ring_ar.classes[0].combine[n - 1:].any()


def test_lowering_wire_accounting():
    """Exact wire accounting matches the schedule; the ring family —
    ring_allreduce included, its two phases on one class — is zero-waste
    under the compiled replay (its constant permutation is fully active
    every round), chains are not (fill/drain garbage)."""
    ring = lower_schedule(build_op("allgather", "ring_allgather", 8))
    assert ring.wire_chunks_exact() == ring.wire_chunks_compiled()
    assert ring.zero_waste
    assert lower_schedule(ring_allreduce_schedule(8)).zero_waste

    sched = build("pipelined_chain", 8, 0, num_chunks=16)
    low = lower_schedule(sched)
    assert low.wire_chunks_exact() == sched.wire_chunks()
    assert low.wire_chunks_compiled() > low.wire_chunks_exact()
    assert not low.zero_waste


def test_lane_partition_invariants():
    """Within a lane: each rank a source at most once, a destination at most
    once, one combine flag — for every round of every lowered schedule."""
    for sched in (fused_rsb(6, 2, 9), bidirectional_chain(7, 3, 5),
                  ring_allreduce_schedule(6)):
        for rnd in sched.rounds:
            for lane in lane_partition(rnd.transfers):
                srcs = [t.src for t in lane]
                dsts = [t.dst for t in lane]
                assert len(set(srcs)) == len(srcs)
                assert len(set(dsts)) == len(dsts)
                assert len({t.combine for t in lane}) == 1


def test_reduce_then_bcast_lowering_parity():
    """The composite allreduce (barrier reduce + tuned bcast rounds, varying
    chunk_count across phases) lowers correctly too: block-height clipping
    plus the lo/hi windows keep the replay exact."""
    for n in (3, 4, 6):
        bcast = build("pipelined_chain", n, 0, num_chunks=5)
        sched = comm_schedules.reduce_then_bcast(n, 0, bcast)
        data = [RNG.randn(sched.num_chunks, 2) for _ in range(n)]
        want = simulate_collective(sched, data)
        got = simulate_lowered(lower_schedule(sched), data)
        for r in range(n):
            assert np.array_equal(want[r], got[r]), (n, r)


# ---------------------------------------------------------------------------
# compile-cost artifact: committed table passes the regression gate
# ---------------------------------------------------------------------------


def test_committed_compile_table_passes_gate():
    table = load_compile_table(os.path.join(REPO, "experiments", "compile_table.json"))
    gated = check_compile_flatness(table)
    assert gated >= 2  # at least two (op, algo) groups swept over num_chunks


def test_committed_compile_table_shows_lowering_win():
    """ISSUE acceptance: at the tuner grid's largest chunk points the
    compiled executor's trace+lower wall time beats the unrolled one (the
    committed artifact's values are frozen, so this asserts the shape of the
    result, not CI-machine timing)."""
    table = load_compile_table(os.path.join(REPO, "experiments", "compile_table.json"))
    groups: dict[tuple, list] = {}
    for key, e in table.items():
        n, op, algo, K = key.split("/")
        groups.setdefault((n, op, algo), []).append((int(K[1:]), e))
    wins = 0
    for _g, pts in groups.items():
        if len(pts) < 2:
            continue
        _K, biggest = max(pts)
        assert biggest["compiled_lower_s"] < biggest["unrolled_lower_s"], _g
        assert biggest["compiled_hlo"] < biggest["unrolled_hlo"], _g
        assert biggest["compiled_jaxpr_eqns"] < biggest["unrolled_jaxpr_eqns"], _g
        wins += 1
    assert wins >= 2


def test_compile_table_loader_rejects_rot(tmp_path):
    import json

    good = {
        "n8/bcast/pipelined_chain/K4": {
            "unrolled_hlo": 100, "compiled_hlo": 50,
            "unrolled_jaxpr_eqns": 60, "compiled_jaxpr_eqns": 20,
            "unrolled_lower_s": 0.1, "compiled_lower_s": 0.05,
            "num_rounds": 10, "lane_classes": 1,
        }
    }
    p = tmp_path / "t.json"
    p.write_text(json.dumps(good))
    assert load_compile_table(str(p))

    for mutate in (
        lambda t: t.__setitem__("bogus-key", next(iter(t.values()))),
        lambda t: next(iter(t.values())).__setitem__("compiled_hlo", -1),
        lambda t: next(iter(t.values())).__setitem__("unrolled_lower_s", float("nan")),
        lambda t: next(iter(t.values())).pop("num_rounds"),
        lambda t: next(iter(t.values())).__setitem__("surprise", 1),
    ):
        bad = json.loads(json.dumps(good))
        mutate(bad)
        p.write_text(json.dumps(bad))
        with pytest.raises(TableSchemaError):
            load_compile_table(str(p))

    # the flatness gate itself: a compiled count that grows with K must fail
    grown = json.loads(json.dumps(good))
    e2 = json.loads(json.dumps(good["n8/bcast/pipelined_chain/K4"]))
    e2["compiled_hlo"] = 500
    e2["unrolled_hlo"] = 400
    grown["n8/bcast/pipelined_chain/K16"] = e2
    with pytest.raises(TableSchemaError):
        check_compile_flatness(grown)


# ---------------------------------------------------------------------------
# in-kernel executor: single-launch replay parity + artifact gate
# ---------------------------------------------------------------------------


def _shared_from(data):
    return np.stack([np.asarray(d, np.float32) for d in data])


@pytest.mark.parametrize("n", [2, 3, 4, 6, 8])
@pytest.mark.parametrize("K", [1, 4, 7])
def test_inkernel_replay_matches_simulator(n, K):
    """The persistent single-launch kernel (interpret-mode emulation), its
    numpy oracle, and the lowered simulator agree bit-for-bit on the shared
    buffer — every algo, pow2 and non-pow2 n, divisible and awkward chunk
    counts."""
    import jax.numpy as jnp

    from repro.core.schedules import pack_tables
    from repro.kernels.inkernel_collective import inkernel_replay_shared
    from repro.kernels.ref import inkernel_shared_ref

    for sched in _schedules(n, K):
        low = lower_schedule(sched)
        data = [RNG.randn(sched.num_chunks, 3).astype(np.float32) for _ in range(n)]
        want = simulate_lowered(low, data)
        oracle = inkernel_shared_ref(pack_tables(low), _shared_from(data))
        got = np.asarray(inkernel_replay_shared(low, jnp.asarray(_shared_from(data))))
        for r in range(n):
            assert np.array_equal(want[r], oracle[r]), (sched.name, n, K, r)
            assert np.array_equal(want[r], got[r]), (sched.name, n, K, r)


@pytest.mark.parametrize(
    "op,algo,sizes",
    [
        ("allgatherv", "ring_allgatherv", (3, 0, 2, 0)),
        ("allgatherv", "doubling_allgatherv", (0, 4, 1, 2)),
        ("alltoallv", "pairwise_alltoallv",
         (0, 1, 2, 0, 3, 0, 0, 1, 1, 0, 0, 2, 2, 1, 0, 0)),
        ("alltoallv", "ring_alltoallv",
         (1, 0, 0, 2, 0, 0, 1, 0, 2, 1, 0, 0, 0, 0, 3, 1)),
    ],
)
def test_inkernel_replay_matches_simulator_ragged(op, algo, sizes):
    """Ragged parity including zero-sized ranks: the in-kernel replay of the
    allgatherv/alltoallv schedules is bit-identical to the simulator."""
    import jax.numpy as jnp

    from repro.core.schedules import pack_tables
    from repro.kernels.inkernel_collective import inkernel_replay_shared
    from repro.kernels.ref import inkernel_shared_ref

    n = 4
    sched = build_op(op, algo, n, 0, sizes=sizes)
    low = lower_schedule(sched)
    data = [RNG.randn(sched.num_chunks, 2).astype(np.float32) for _ in range(n)]
    want = simulate_lowered(low, data)
    oracle = inkernel_shared_ref(pack_tables(low), _shared_from(data))
    got = np.asarray(inkernel_replay_shared(low, jnp.asarray(_shared_from(data))))
    for r in range(n):
        assert np.array_equal(want[r], oracle[r]), (op, algo, r)
        assert np.array_equal(want[r], got[r]), (op, algo, r)


def test_inkernel_single_launch_and_flat_jaxpr():
    """ISSUE acceptance, structural half: ONE pallas_call per schedule replay
    and a traced program whose size is independent of both chunk count and
    round count."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr import pallas_eqns
    from repro.kernels.inkernel_collective import inkernel_replay_shared

    sizes = {}
    for K in (4, 16, 64):
        low = lower_schedule(build("pipelined_chain", 4, 0, num_chunks=K))
        shared = jnp.zeros((4, K, 8), jnp.float32)
        closed = jax.make_jaxpr(
            lambda s, low=low: inkernel_replay_shared(low, s)
        )(shared)
        assert len(pallas_eqns(closed.jaxpr)) == 1, K
        sizes[K] = len(closed.jaxpr.eqns)
    assert len(set(sizes.values())) == 1, sizes


def test_committed_inkernel_table_passes_gate():
    """ISSUE acceptance, artifact half: the committed table shows exactly one
    launch per replay, HLO flat in K and strictly below the compiled
    executor's at each group's deepest point — all enforced by the loader."""
    from repro.comm.tables import load_inkernel_table

    table = load_inkernel_table(
        os.path.join(REPO, "experiments", "inkernel_table.json")
    )
    assert all(e["inkernel_launches"] == 1 for e in table.values())
    multi_k = {}
    for key in table:
        n, op, algo, _K = key.split("/")
        multi_k[(n, op, algo)] = multi_k.get((n, op, algo), 0) + 1
    assert sum(1 for v in multi_k.values() if v >= 2) >= 2


def test_inkernel_table_loader_rejects_rot(tmp_path):
    import json

    from repro.comm.tables import load_inkernel_table

    good = {
        "n4/bcast/pipelined_chain/K4": {
            "inkernel_launches": 1, "inkernel_hlo": 170, "compiled_hlo": 210,
            "num_rounds": 6, "compiled_rounds": 6, "round_us": 50.0,
        },
        "n4/bcast/pipelined_chain/K16": {
            "inkernel_launches": 1, "inkernel_hlo": 172, "compiled_hlo": 211,
            "num_rounds": 18, "compiled_rounds": 18, "round_us": 20.0,
        },
    }
    p = tmp_path / "t.json"
    p.write_text(json.dumps(good))
    assert load_inkernel_table(str(p))

    def k16(t):
        return t["n4/bcast/pipelined_chain/K16"]

    for mutate in (
        # a second launch: the whole point of the executor regressed
        lambda t: k16(t).__setitem__("inkernel_launches", 2),
        # executor round-count drift
        lambda t: k16(t).__setitem__("compiled_rounds", 19),
        # HLO no longer flat in K
        lambda t: k16(t).__setitem__("inkernel_hlo", 400),
        # not smaller than the compiled program at the deepest K
        lambda t: k16(t).__setitem__("inkernel_hlo", 211),
        lambda t: t.__setitem__("bogus-key", dict(k16(t))),
        lambda t: k16(t).__setitem__("round_us", float("nan")),
        lambda t: k16(t).pop("num_rounds"),
        lambda t: k16(t).__setitem__("surprise", 1),
    ):
        bad = json.loads(json.dumps(good))
        mutate(bad)
        p.write_text(json.dumps(bad))
        with pytest.raises(TableSchemaError):
            load_inkernel_table(str(p))

    # a table with no multi-K sweep at all is not a gateable artifact
    single = {"n4/bcast/pipelined_chain/K4": good["n4/bcast/pipelined_chain/K4"]}
    p.write_text(json.dumps(single))
    with pytest.raises(TableSchemaError):
        load_inkernel_table(str(p))
