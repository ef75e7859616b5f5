"""Model facade: init / loss / prefill / decode + dry-run input specs."""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, ShapeSpec
from .layers import cross_entropy_loss
from .transformer import apply_lm, init_decode_cache, init_lm, update_router_bias

__all__ = ["Model"]


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---- params -----------------------------------------------------------

    def init(self, key) -> Any:
        return init_lm(key, self.cfg)

    def param_shapes(self) -> Any:
        """Abstract parameter tree (no allocation) — dry-run / sharding."""
        return jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), self.cfg))

    # ---- forward ----------------------------------------------------------

    def forward(self, params, batch, *, remat: bool = False):
        logits, aux, _ = self._train_forward(params, batch, remat)
        return logits, aux

    def _train_forward(self, params, batch, remat: bool):
        logits, _, aux, load = apply_lm(
            params,
            self.cfg,
            tokens=batch["tokens"],
            embeds=batch.get("embeds"),
            mode="train",
            remat=remat,
        )
        return logits, aux, load

    def loss(self, params, batch, *, remat: bool = False):
        """(nll + aux, metrics). A model with dropless MoE layers also
        reports ``expert_load``, (MoE layers, router experts) token counts:
        what ``update_router_bias`` moves the routing bias by."""
        logits, aux, load = self._train_forward(params, batch, remat)
        with jax.named_scope("head"):
            labels = jnp.minimum(batch["labels"], self.cfg.padded_vocab - 1)
            nll = cross_entropy_loss(logits, labels, batch.get("loss_mask"))
        metrics = {"nll": nll, "aux": aux}
        if load is not None:
            metrics["expert_load"] = load
        return nll + aux, metrics

    def update_router_bias(self, new_params, params, metrics):
        """The step's last act: the routing bias moved by the step's load;
        ``new_params`` unchanged for a model without dropless MoE layers."""
        if "expert_load" not in metrics:
            return new_params
        return update_router_bias(new_params, params, metrics["expert_load"], self.cfg)

    # ---- serving ----------------------------------------------------------

    def prefill(self, params, batch, *, max_len: int):
        if self.cfg.frontend == "vision":
            max_len = max_len + self.cfg.prefix_len  # cache holds the prefix too
        logits, caches, _, _ = apply_lm(
            params,
            self.cfg,
            tokens=batch["tokens"],
            embeds=batch.get("embeds"),
            mode="prefill",
            max_len=max_len,
        )
        return logits, caches

    def decode_step(self, params, tokens, caches, cur_pos):
        """tokens (B,1) int32; cur_pos scalar int32 (absolute position of the
        new token). Returns (logits (B,1,V), new_caches)."""
        logits, caches, _, _ = apply_lm(
            params,
            self.cfg,
            tokens=tokens,
            mode="decode",
            caches=caches,
            cur_pos=jnp.asarray(cur_pos, jnp.int32),
        )
        return logits, caches

    def init_cache(self, batch: int, max_len: int):
        return init_decode_cache(self.cfg, batch, max_len)

    def cache_specs(self, batch: int, max_len: int):
        """ShapeDtypeStructs for the decode cache (no allocation)."""
        return jax.eval_shape(partial(init_decode_cache, self.cfg, batch, max_len))

    # ---- dry-run input specs (ShapeDtypeStruct stand-ins) -------------------

    def input_specs(self, shape: ShapeSpec) -> dict:
        """Abstract inputs for a given assigned input shape.

        The modality frontends are STUBS per the assignment: for VLM/audio
        archs the specs contain precomputed patch/frame embeddings of the
        right shape instead of pixels/waveforms.
        """
        cfg = self.cfg
        B, T = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = jnp.dtype(cfg.dtype)
        sds = jax.ShapeDtypeStruct

        if shape.mode in ("train", "prefill"):
            specs: dict[str, Any] = {}
            if cfg.frontend == "vision":
                t_text = T - cfg.prefix_len
                specs["tokens"] = sds((B, t_text), i32)
                specs["embeds"] = sds((B, cfg.prefix_len, cfg.d_model), dt)
                if shape.mode == "train":
                    specs["labels"] = sds((B, t_text), i32)
            elif cfg.arch_type == "encdec":
                specs["tokens"] = sds((B, T), i32)
                specs["embeds"] = sds((B, cfg.frontend_len, cfg.d_model), dt)
                if shape.mode == "train":
                    specs["labels"] = sds((B, T), i32)
            else:
                specs["tokens"] = sds((B, T), i32)
                if shape.mode == "train":
                    specs["labels"] = sds((B, T), i32)
            return specs

        # decode: one new token against a seq_len-deep cache
        return {
            "tokens": sds((B, 1), i32),
            "caches": self.cache_specs(B, T),
            "cur_pos": sds((), i32),
        }

    # ---- sample concrete batch (smoke tests / examples) ---------------------

    def sample_batch(self, shape: ShapeSpec, seed: int = 0) -> dict:
        rng = np.random.RandomState(seed)
        specs = self.input_specs(shape)

        def make(s):
            if np.issubdtype(s.dtype, np.integer):
                return jnp.asarray(
                    rng.randint(0, max(self.cfg.vocab_size - 1, 2), size=s.shape), s.dtype
                )
            return jnp.asarray(rng.randn(*s.shape).astype(np.float32), s.dtype)

        out = {}
        for k, v in specs.items():
            if k == "caches":
                out[k] = self.init_cache(shape.global_batch, shape.seq_len)
            elif k == "cur_pos":
                out[k] = jnp.asarray(shape.seq_len - 1, jnp.int32)
            else:
                out[k] = jax.tree.map(make, v)
        return out
