"""Serving launcher: load (or init) weights, distribute them with the tuned
broadcast, and run batched greedy generation.

    PYTHONPATH=src python -m repro.launch.serve --arch xlstm-350m-smoke \
        --batch 4 --prompt-len 16 --steps 16 [--ckpt-dir DIR]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serve.engine import Engine
from repro.train import checkpoint as ck


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--temperature", type=float, default=0.0, help="0 = greedy")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    model = Model(cfg)
    if args.ckpt_dir:
        step = ck.latest_step(args.ckpt_dir)
        assert step is not None, f"no checkpoint under {args.ckpt_dir}"
        params = ck.restore_checkpoint(args.ckpt_dir, step, model.param_shapes())
        print(f"restored step {step} from {args.ckpt_dir}")
    else:
        params = model.init(jax.random.PRNGKey(args.seed))
        print("no checkpoint given; serving random-init weights")

    engine = Engine(cfg, params, max_len=args.prompt_len + args.steps)
    rng = np.random.RandomState(args.seed)
    batch = {
        "tokens": jnp.asarray(
            rng.randint(0, cfg.vocab_size - 1, (args.batch, args.prompt_len))
        )
    }
    if cfg.frontend == "vision":
        batch["embeds"] = jnp.asarray(
            rng.randn(args.batch, cfg.prefix_len, cfg.d_model), jnp.bfloat16
        )
    if cfg.arch_type == "encdec":
        batch["embeds"] = jnp.asarray(
            rng.randn(args.batch, cfg.frontend_len, cfg.d_model), jnp.bfloat16
        )
    res = engine.generate(
        batch,
        steps=args.steps,
        greedy=(args.temperature == 0.0),
        temperature=max(args.temperature, 1e-6),
        seed=args.seed,
    )
    print(f"arch={cfg.name} batch={args.batch} prefill={args.prompt_len} decode={args.steps}")
    for b in range(args.batch):
        print(f"req{b}: {res.tokens[b].tolist()} (mean logprob {res.logprobs[b].mean():.3f})")


if __name__ == "__main__":
    main()
