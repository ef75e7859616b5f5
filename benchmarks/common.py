"""Benchmark infrastructure: subprocess workers with N simulated devices.

Benchmarks print ``name,us_per_call,derived`` CSV rows (one per paper
table/figure entry). Measured numbers are CPU-host timings of the REAL
shard_map collectives (relative behaviour); 'derived' carries the analytic
TPU-v5e prediction from the paper's cost models so both views are recorded.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class WorkerTimeoutError(RuntimeError):
    """A bench subprocess exceeded its wall-clock budget on every attempt.

    Raised instead of the raw ``subprocess.TimeoutExpired`` so suites can
    catch it and record the point as timed out (``derived.timeout=true``)
    rather than dropping it silently or crashing the whole sweep."""


def run_worker(code: str, devices: int, timeout: int = 560, retries: int = 0) -> dict:
    """Run ``code`` with ``devices`` simulated host devices, pinned to the
    CPU backend so the worker never contends for an accelerator."""
    pre = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        f"import sys; sys.path.insert(0, {SRC!r})\n"
    )
    last = None
    for attempt in range(retries + 1):
        try:
            proc = subprocess.run(
                [sys.executable, "-c", pre + code],
                capture_output=True,
                text=True,
                timeout=timeout,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
        except subprocess.TimeoutExpired as e:
            # a hung worker gets one more honest shot (transient host load);
            # a reproducible hang surfaces as the typed error below
            last = e
            continue
        if proc.returncode != 0:
            raise RuntimeError(f"bench worker failed:\n{proc.stderr[-3000:]}")
        # last line is the JSON payload
        return json.loads(proc.stdout.strip().splitlines()[-1])
    raise WorkerTimeoutError(
        f"bench worker timed out after {timeout}s on {retries + 1} attempt(s) "
        f"(devices={devices})"
    ) from last


MEASURE_SNIPPET = """
import time, json
import numpy as np, jax, jax.numpy as jnp
from repro.core import bcast_stacked

def measure(algo, M, n, reps=5):
    elems = max(M // 4, 1)
    mesh = jax.make_mesh((n,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    xs = jnp.asarray(np.random.RandomState(0).randn(n, elems).astype(np.float32))
    def run():
        return bcast_stacked(xs, mesh, "data", root=0, algo=algo)
    out = run(); out.block_until_ready()   # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter(); run().block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))
"""
