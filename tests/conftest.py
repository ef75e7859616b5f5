"""Shared test infrastructure.

IMPORTANT: this process keeps the default single CPU device (the dry-run's
512-device override is NOT set here — per the assignment, smoke tests and
benches must see 1 device). Multi-device collective behaviour is tested in
subprocesses via ``run_distributed``.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, SRC)


def run_distributed(code: str, devices: int = 8, timeout: int = 560,
                    env: dict | None = None) -> str:
    """Run ``code`` in a subprocess with N simulated host devices (the CPU
    device-count override: ``XLA_FLAGS=--xla_force_host_platform_device_count``
    is set before any jax import, so collectives and sharding see a real
    multi-device platform without accelerators or network access).

    The snippet must print 'PASS' as its last line on success. The child
    is pinned to the CPU backend: its devices are simulated on the host, and
    it must never contend for an accelerator the parent may hold.
    ``env``: extra environment overrides for the subprocess.
    """
    preamble = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        "import sys\n"
        f"sys.path.insert(0, {SRC!r})\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", preamble + code],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=os.path.dirname(SRC),
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
    )
    if proc.returncode != 0 or "PASS" not in proc.stdout:
        raise AssertionError(
            f"distributed snippet failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout[-4000:]}\n--- stderr ---\n{proc.stderr[-4000:]}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def dist():
    return run_distributed
