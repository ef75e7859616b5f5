"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch minitron-8b-smoke \
        --steps 100 --batch 8 --seq 128 --sync-mode param_bcast

Any assigned architecture id (or its '-smoke' reduced variant) is accepted.
``--sync-mode param_bcast`` runs the paper's reduce-to-root + tuned-broadcast
data-parallel synchronization; ``grad_allreduce`` is the GSPMD baseline.
"""
from __future__ import annotations

import argparse

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.train.trainer import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgdm", "lion"])
    ap.add_argument("--sync-mode", default="grad_allreduce",
                    choices=["grad_allreduce", "param_bcast"])
    ap.add_argument("--bcast-algo", default="auto")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--data", default=None, help="packed int32 token .npy file")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    run = RunConfig(
        learning_rate=args.lr,
        warmup_steps=args.warmup,
        total_steps=args.steps,
        optimizer=args.optimizer,
        sync_mode=args.sync_mode,
        bcast_algo=args.bcast_algo,
        num_microbatches=args.microbatches,
        seed=args.seed,
    )
    mesh = make_local_mesh(args.model_parallel)
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} sync={run.sync_mode}")
    tr = Trainer(cfg, run, mesh=mesh, data_path=args.data, ckpt_dir=args.ckpt_dir)
    tr.train(
        batch=args.batch,
        seq=args.seq,
        steps=args.steps,
        log_every=args.log_every,
        ckpt_every=args.ckpt_every,
    )


if __name__ == "__main__":
    main()
