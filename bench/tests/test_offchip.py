"""The benchmark's arithmetic and discovery, on the CPU: FLOP functions,
the peaks table, finding new cells by their files, and refusing to run
without a TPU."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import registry
from bench.peaks import peaks_for
from bench.reference.common import n_params

ROOT = registry.ROOT


def _flops(cell_name):
    cell = registry.load_cell(cell_name)
    ref = cell.reference()
    shapes = jax.eval_shape(lambda k: ref.init(cell.config, k), jax.random.PRNGKey(0))
    n = n_params(shapes)
    return cell, ref, n


def test_xlstm_flops_matmul_term_is_six_n():
    cell, ref, n = _flops("xlstm350m.train1")
    assert n == 290_927_700                      # 290.9M, embedding tied
    no_mix = dict(cell.config, block_pattern=["slstm"] * 8)
    # with no mLSTM block the only term left is 6 N per token
    assert ref.train_flops_per_token(no_mix, 2048, n) == 6 * n
    # 21 mLSTM layers x 4 heads x (4 L hd + 4 hd^2) x 3 at L=128, hd=512
    mix = 3 * 21 * 4 * (4 * 128 * 512 + 4 * 512 * 512)
    assert ref.train_flops_per_token(cell.config, 2048, n) == 6 * n + mix


def test_hymba_flops():
    cell, ref, n = _flops("hymba1p5b.train1")
    assert n == 518_324_816
    # causal window 1024 over 2048 positions: 768.25 keys per query on average
    assert ref.attended_keys(2048, 1024) == 768.25
    attn = 4 * 25 * 64 * 768.25
    ssm = 4 * 3200 * 16
    assert ref.train_flops_per_token(cell.config, 2048, n) == 3 * (2 * n + 8 * (attn + ssm))


def test_peaks_known_and_unknown():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def _digest(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_config_and_metric_are_new_files(tmp_path):
    """A configuration, a traffic mix, limits, a cell and a per-layer metric
    added as files (plus their BENCHMARK.json entries) are found by name;
    no file the benchmark already had changes."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path / "bench")

    cfg = json.load(open(tmp_path / "bench/configs/xlstm-350m.json"))
    cfg.update(name="xlstm-tiny", num_layers=8, d_model=128)
    json.dump(cfg, open(tmp_path / "bench/configs/xlstm-tiny.json", "w"))
    traffic = json.load(open(tmp_path / "bench/traffic/train_b8_s2048.json"))
    traffic.update(global_batch=4, seq=256)
    json.dump(traffic, open(tmp_path / "bench/traffic/train_b4_s256.json", "w"))
    json.dump({"loss_gap": 0.1, "grad_gap": 0.1, "update_gap": 0.1},
              open(tmp_path / "bench/limits/xlstmtiny.train1.json", "w"))
    (tmp_path / "bench/metrics/steps_traced.py").write_text("def read(ctx):\n    return float(ctx.steps)\n")

    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    bench["configs"].append({"name": "xlstm-tiny", "source": "https://arxiv.org/abs/2405.04517",
                             "file": "bench/configs/xlstm-tiny.json", "reduced": ["num_layers", "d_model"],
                             "why": "test"})
    bench["workloads"].append({"name": "xlstmtiny.train1", "config": "xlstm-tiny",
                               "traffic": "train_b4_s256", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps", "better": "higher",
                               "source": "device_trace", "layer": "device",
                               "moves": "train_tokens_per_s", "workloads": ["xlstmtiny.train1"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))

    cell = registry.load_cell("xlstmtiny.train1", str(tmp_path))
    assert cell.config["d_model"] == 128 and cell.traffic["seq"] == 256
    assert cell.limits["grad_gap"] == 0.1
    names = [m["name"] for m in cell.per_layer]
    assert "steps_traced" in names and "grad_sync_ms" not in names
    assert cell.metric_module("steps_traced").read(type("C", (), {"steps": 3})()) == 3.0
    assert cell.reference().train_flops_per_token(cell.config, 256, 1) > 0
    old = registry.load_cell("xlstm350m.train1", str(tmp_path))
    assert "steps_traced" not in [m["name"] for m in old.per_layer]
    after = _digest(tmp_path / "bench")
    assert all(after[k] == v for k, v in before.items())


def test_every_cell_has_its_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = registry.load_cell(w["name"])
        assert cell.chips == cell.traffic["data_parallel"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for m in cell.per_layer:
            assert hasattr(cell.metric_module(m["name"]), "read")


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "xlstm350m.train1",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_only_benchmark_files_exit_nonzero(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "xlstm350m.train1",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
