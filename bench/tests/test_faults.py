"""`correct` comes out false when the timed path is broken underneath.

Each case drives a whole rehearsal run (every step of ``bench/run.py``
but the look for a chip, at the data files' rehearsal sizes, on the CPU)
with one fault planted in the program (a step that returns its state
unchanged; half of every batch left out, the mean taken over the rest),
and reads the run's verdict against the cell's limits for that size. The
control, the plain reference in fp8, must fail them too. A one-chip cell
has no exchange between chips to leave out."""
import json
import os
import subprocess
import sys

import pytest

from bench import registry

ROOT = registry.ROOT

PLANT = r'''
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from repro.train import trainer as trainer_mod

fault = {fault!r}
build = trainer_mod.Trainer._build

def planted(self):
    build(self)
    real = self._step_fn
    if fault == "unchanged":
        self._step_fn = jax.jit(lambda p, o, b: (p, o, real(p, o, b)[2]))
    elif fault == "half_batch":
        self._step_fn = jax.jit(lambda p, o, b: real(p, o, jax.tree.map(lambda x: x[: x.shape[0] // 2], b)))

trainer_mod.Trainer._build = planted
from bench import run
sys.exit(run.main(["--workload", {cell!r}, "--seed", "424242", "--seconds", "0.5",
                   "--trace", "0", "--rehearse"]))
'''


def _verdict(cell: str, fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = PLANT.format(root=ROOT, src=os.path.join(ROOT, "src"), fault=fault, cell=cell)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [x for x in p.stdout.splitlines() if x.startswith("REHEARSAL ")][-1]
    return json.loads(line[len("REHEARSAL "):])


CASES = [
    ("xlstm350m.train1", "none"),
    ("xlstm350m.train1", "unchanged"),
    ("xlstm350m.train1", "half_batch"),
    ("hymba1p5b.train1", "none"),
    ("hymba1p5b.train1", "unchanged"),
    ("hymba1p5b.train1", "half_batch"),
]


@pytest.mark.parametrize("cell, fault", CASES)
def test_planted_fault_is_not_correct(cell, fault):
    verdict = _verdict(cell, fault)
    assert verdict["correct"] is (fault == "none"), verdict["checks"]


@pytest.mark.parametrize("cell", ["xlstm350m.train1", "hymba1p5b.train1"])
def test_control_fp8_is_not_correct(cell):
    import jax

    from bench import check, train
    from bench.run import rehearsal

    c = rehearsal(registry.load_cell(cell))
    devs = jax.devices()[:1]
    fails = 0
    for seed in (7, 8, 9):
        ref = train.reference_observed(c, devs, seed)
        control = train.reference_observed(c, devs, seed, fp8=True)
        ok, table = check.judge(check.readings(control, ref), c.limits)
        fails += not ok
    assert fails == 3
