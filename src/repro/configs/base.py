"""Config system: model architecture + input shapes + run settings."""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

__all__ = ["ModelConfig", "ShapeSpec", "INPUT_SHAPES", "RunConfig"]

# Keys of a published Hugging Face config.json, each with the value that the
# program's own fields imply. A configuration file may hold them beside
# those fields, so that it states the published model key for key; they
# are checked when a ModelConfig is made, then dropped, so nothing reads
# them and ``dataclasses.replace`` does not carry them. A key mapped to
# None only states the published model.
PUBLISHED_KEYS = {
    "hidden_size": lambda c: c.d_model,
    "num_hidden_layers": lambda c: c.num_layers,
    "num_attention_heads": lambda c: c.num_heads,
    "num_key_value_heads": lambda c: c.num_kv_heads,
    "moe_intermediate_size": lambda c: c.d_ff,
    "n_routed_experts": lambda c: c.num_experts,
    "num_experts_per_tok": lambda c: c.experts_per_token,
    "n_shared_experts": lambda c: c.num_shared_experts,
    "rms_norm_eps": lambda c: c.norm_eps,
    "tie_word_embeddings": lambda c: c.tie_embeddings,
    "hidden_act": lambda c: c.act,
    "attention_bias": lambda c: c.qkv_bias,
    "moe_layer_freq": lambda c: 1,              # every layer after the dense ones
    "num_nextn_predict_layers": lambda c: 0,    # no multi-token prediction layers
    "scoring_func": lambda c: "sigmoid" if c.dropless else "softmax",
    "norm_topk_prob": lambda c: True,           # chosen weights always normalised
    "seq_aux": lambda c: c.dropless,            # the balance loss of dropless routing
    "n_group": lambda c: 1,                     # no grouped routing
    "topk_group": lambda c: 1,
    "q_lora_rank": lambda c: None,              # no query compression
    "model_type": None,
    "max_position_embeddings": None,
    "ep_size": None,
}


def _published():
    return dataclasses.field(default=None, repr=False, compare=False)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description. One instance per assigned architecture
    (see src/repro/configs/<id>.py); ``reduced()`` derives the CPU smoke
    variant of the same family."""

    name: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int                       # MLP width; the expert width in MoE layers
    vocab_size: int
    head_dim: Optional[int] = None

    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    moe_group_size: int = 512       # GShard dispatch group length
    router_aux_coef: float = 0.01
    # expert-dispatch transport: 'einsum' (dense one-hot; GSPMD infers the
    # all-to-all) or 'alltoallv' (explicit repro.comm.palltoallv expert
    # parallelism — needs an axis_name threaded to moe_ffn)
    moe_dispatch: str = "einsum"
    # 'noaux_tc' is DeepSeek-V3's routing: top-k of sigmoid(logits) plus a
    # per-expert bias that the step moves by load, not by gradient; the
    # chosen scores, unbiased, normalised and scaled by
    # routed_scaling_factor. Dropless: the layer holds ``num_experts``
    # experts of the router's ``router_experts`` (0 = all of them) and
    # computes its own experts' part of the result. router_aux_coef is then
    # the sequence-wise balance loss's alpha.
    topk_method: str = "greedy"     # greedy (GShard, capacity) | noaux_tc (dropless)
    routed_scaling_factor: float = 1.0
    router_experts: int = 0
    # leading layers with a dense MLP of width intermediate_size
    first_k_dense_replace: int = 0
    intermediate_size: int = 0

    # --- multi-head latent attention (kv_lora_rank > 0) ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- attention ---
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    # per-layer window pattern, repeated over depth; None entry = global attn.
    # e.g. gemma3: (1024, 1024, 1024, 1024, 1024, None)  -> 5 local : 1 global
    attn_pattern: Tuple[Optional[int], ...] = (None,)

    # --- block pattern (ssm / hybrid); entries: 'attn'|'moe'|'mlstm'|'slstm'|'hybrid'
    block_pattern: Optional[Tuple[str, ...]] = None
    ssm_state: int = 0              # mamba state dim N
    ssm_expand: int = 2             # mamba/mlstm inner expansion
    ssm_conv: int = 4               # mamba short-conv width
    ssm_chunk: int = 128            # chunkwise-scan chunk length

    # --- structure ---
    arch_type: str = "decoder"      # decoder | encdec
    encoder_layers: int = 0
    frontend: Optional[str] = None  # 'audio' | 'vision' (STUB embeddings)
    frontend_len: int = 0           # frames / patches supplied by the stub
    prefix_len: int = 0             # bidirectional prefix (VLM prefix-LM)
    tie_embeddings: bool = True
    embed_scale: bool = True        # multiply embeddings by sqrt(d_model)
    act: str = "silu"               # mlp nonlinearity: silu (swiglu) | gelu
    norm_eps: float = 1e-6
    vocab_pad_to: int = 256
    dtype: str = "bfloat16"
    # decode KV-cache dtype. Production override for archs whose MHA cache
    # would exceed HBM at the assigned decode shapes (qwen1.5-32b @ 32k x 128
    # needs float8_e5m2 to fit a single v5e pod — see EXPERIMENTS.md Dry-run).
    kv_cache_dtype: str = "bfloat16"

    # --- citation ---
    source: str = ""

    # --- keys of a published Hugging Face config.json (PUBLISHED_KEYS) ---
    hidden_size: Optional[int] = _published()
    num_hidden_layers: Optional[int] = _published()
    num_attention_heads: Optional[int] = _published()
    num_key_value_heads: Optional[int] = _published()
    moe_intermediate_size: Optional[int] = _published()
    n_routed_experts: Optional[int] = _published()
    num_experts_per_tok: Optional[int] = _published()
    n_shared_experts: Optional[int] = _published()
    rms_norm_eps: Optional[float] = _published()
    tie_word_embeddings: Optional[bool] = _published()
    hidden_act: Optional[str] = _published()
    attention_bias: Optional[bool] = _published()
    model_type: Optional[str] = _published()
    max_position_embeddings: Optional[int] = _published()
    ep_size: Optional[int] = _published()
    moe_layer_freq: Optional[int] = _published()
    num_nextn_predict_layers: Optional[int] = _published()
    scoring_func: Optional[str] = _published()
    norm_topk_prob: Optional[bool] = _published()
    seq_aux: Optional[bool] = _published()
    n_group: Optional[int] = _published()
    topk_group: Optional[int] = _published()
    q_lora_rank: Optional[int] = _published()

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.block_pattern is None:
            kind = "moe" if self.num_experts else "attn"
            object.__setattr__(self, "block_pattern", (kind,))
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be divisible by num_kv_heads")
        if self.topk_method not in ("greedy", "noaux_tc"):
            raise ValueError(f"unsupported topk_method {self.topk_method!r}")
        if self.router_experts and self.router_experts < self.num_experts:
            raise ValueError("router_experts must be at least num_experts (the experts held)")
        self._check_published_keys()

    def _check_published_keys(self):
        for key, implied in PUBLISHED_KEYS.items():
            value = getattr(self, key)
            if implied is not None and value is not None and value != implied(self):
                raise ValueError(f"{self.name}: {key}={value!r} disagrees with the program's "
                                 f"{implied(self)!r}")
            object.__setattr__(self, key, None)

    # ---- derived ----

    @property
    def mla(self) -> bool:
        """Multi-head latent attention in place of GQA."""
        return self.kv_lora_rank > 0

    @property
    def dropless(self) -> bool:
        """DeepSeek-V3 routing over ``router_width`` experts, dropless."""
        return self.topk_method == "noaux_tc"

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return ((self.vocab_size + p - 1) // p) * p

    @property
    def pattern_period(self) -> int:
        return max(len(self.block_pattern), len(self.attn_pattern))

    def layer_kinds(self) -> list[str]:
        """Kind of every layer: the leading dense layers ('attn'), then the
        block pattern repeated."""
        bp, k = self.block_pattern, self.first_k_dense_replace
        return ["attn"] * k + [bp[i % len(bp)] for i in range(self.num_layers - k)]

    def layer_windows(self) -> list[Optional[int]]:
        ap = self.attn_pattern
        return [ap[i % len(ap)] for i in range(self.num_layers)]

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k decode: layers are SSM / windowed
        attention, allowing a MINORITY of global layers (gemma3's 5:1
        local:global long-context design — decode against a global cache is
        linear per token; the windowed majority bounds the cache growth)."""
        kinds = self.layer_kinds()
        wins = self.layer_windows()
        n_global = 0
        n_attn = 0
        for k, w in zip(kinds, wins):
            if k in ("mlstm", "slstm"):
                continue
            n_attn += 1
            if w is None:
                n_global += 1
        if n_attn == 0:
            return True
        if n_global == 0:
            return True
        return n_global / n_attn <= 0.34 and len(self.attn_pattern) > 1

    # ---- parameter counting (for 6*N*D model-FLOPs accounting) ----

    def param_count(self, active_only: bool = False) -> int:
        d, hd = self.d_model, self.head_dim
        H, KV = self.num_heads, self.num_kv_heads
        total = self.padded_vocab * d  # embed
        if not self.tie_embeddings:
            total += self.padded_vocab * d
        kinds = self.layer_kinds()

        def attn_params():
            if self.mla:
                r, nope, rp, vd = (self.kv_lora_rank, self.qk_nope_head_dim,
                                   self.qk_rope_head_dim, self.v_head_dim)
                return d * H * (nope + rp) + d * (r + rp) + r + r * H * (nope + vd) + H * vd * d
            p = d * H * hd + 2 * d * KV * hd + H * hd * d
            if self.qkv_bias:
                p += H * hd + 2 * KV * hd
            return p

        def mlp_params(f):
            return 3 * d * f if self.act in ("silu", "geglu") else 2 * d * f

        def ssm_params():
            di = self.ssm_expand * d
            if self.ssm_state:  # mamba
                return d * di * 2 + di * self.ssm_conv + di * (2 * self.ssm_state + 2) + di * d
            # mlstm: q,k,v,o over inner dim + gates
            return d * di * 4 + 2 * d * H + di * d

        for i, kind in enumerate(kinds):
            if kind == "attn":
                width = self.intermediate_size if i < self.first_k_dense_replace else self.d_ff
                total += attn_params() + mlp_params(width)
            elif kind == "moe":
                if active_only:  # the experts held, at uniform load
                    e = self.experts_per_token * self.num_experts / self.router_width
                else:
                    e = self.num_experts
                total += attn_params() + (e + self.num_shared_experts) * mlp_params(self.d_ff)
                total += d * self.router_width  # router
                if self.dropless:
                    total += self.router_width  # its correction bias
            elif kind == "mlstm":
                total += ssm_params()
            elif kind == "slstm":
                total += 4 * d * d + 4 * d * H  # i,f,z,o projections + gates
            elif kind == "hybrid":
                total += attn_params() + ssm_params() + mlp_params(self.d_ff)
            total += 2 * d  # norms
        if self.arch_type == "encdec":
            # encoder layers + decoder cross-attention
            enc = self.encoder_layers * (attn_params() + mlp_params(self.d_ff) + 2 * d)
            cross = self.num_layers * (attn_params() + d)
            total += enc + cross
        return int(total)

    def reduced(self) -> "ModelConfig":
        """CPU smoke variant of the same family: 2 pattern periods of layers
        (the leading dense ones among them), d_model <= 512, <= 4 experts (of
        a router <= 8 wide where the layer holds a share)."""
        period = self.pattern_period
        n_layers = min(self.num_layers, 2 * period)
        d = min(self.d_model, 256)
        hd = 32
        kv = min(self.num_kv_heads, 2)
        heads = max(kv, min(self.num_heads, 4))
        heads = (heads // kv) * kv
        enc = min(self.encoder_layers, 2)
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            num_layers=n_layers,
            d_model=d,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=hd,
            d_ff=min(self.d_ff, 512) if self.d_ff else self.d_ff,
            vocab_size=min(self.vocab_size, 1024),
            num_experts=min(self.num_experts, 4),
            router_experts=min(self.router_experts, 8),
            experts_per_token=min(self.experts_per_token, 2),
            intermediate_size=min(self.intermediate_size, 512),
            kv_lora_rank=min(self.kv_lora_rank, 64),
            qk_nope_head_dim=min(self.qk_nope_head_dim, 32),
            qk_rope_head_dim=min(self.qk_rope_head_dim, 16),
            v_head_dim=min(self.v_head_dim, 32),
            num_shared_experts=min(self.num_shared_experts, 1),
            moe_group_size=64,
            encoder_layers=enc,
            frontend_len=min(self.frontend_len, 16) if self.frontend_len else 0,
            prefix_len=min(self.prefix_len, 16) if self.prefix_len else 0,
            attn_pattern=tuple(
                (min(w, 64) if w is not None else None) for w in self.attn_pattern
            ),
            ssm_chunk=16,
            vocab_pad_to=64,
        )


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned input shape."""

    name: str
    seq_len: int
    global_batch: int
    mode: str  # 'train' | 'prefill' | 'decode'


INPUT_SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Training/serving run settings."""

    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 1000
    optimizer: str = "adamw"
    # data-parallel sync mode: 'grad_allreduce' (modern baseline, GSPMD
    # inserts the collective), 'param_bcast' (the paper's CA-CNTK pattern:
    # reduce-to-root + tuned bcast through core.bcast), 'tuned_allreduce'
    # (the repro.comm plan layer: bucketed, hierarchical, per-op tuned
    # allreduce — reduce_then_bcast/fused_rsb/ring windows), or
    # 'overlap_allreduce' (same plans, bucket-streamed through the overlap
    # engine: backward-order dispatch inside a tuned in-flight window —
    # identical params up to float summation order)
    sync_mode: str = "grad_allreduce"
    bcast_algo: str = "auto"
    # allreduce algorithm for sync_mode='tuned_allreduce'/'overlap_allreduce':
    # 'auto' consults the per-op tuner; or pin 'reduce_then_bcast' |
    # 'fused_rsb' | 'ring_allreduce' | 'xla_psum'
    allreduce_algo: str = "auto"
    # path to a calibrated empirical table (Tuner.save format; a REAL-device
    # run of benchmarks/bench_allreduce.py writes a loadable
    # experiments/allreduce_table.json). None = analytic decisions. Applies
    # to all explicit sync modes. NOTE: the committed copy of that artifact
    # is regenerated by CI in --dryrun mode and branded as such — Tuner.load
    # refuses dryrun tables, so point this at a table from a device run.
    tuner_table: Optional[str] = None
    # collective executor for the repro.comm sync modes: True pins the
    # compiled fori_loop replay (O(1)-HLO schedule executor), False the
    # exact unrolled replay, None (default) the tuned round-count policy
    # (Decision.fused_path / comm.api.apply_plan)
    compiled_collectives: Optional[bool] = None
    # in-flight bucket window for sync_mode='overlap_allreduce': None tunes
    # it (tuner table overlap_depth, else cost_model.optimal_overlap_depth)
    overlap_depth: Optional[int] = None
    # backward-pass seconds the overlap engine may hide collectives behind
    # (0.0 = depth tuning assumes staging-bound, still streams buckets)
    overlap_compute_s: float = 0.0
    # second comm stream for sync_mode='overlap_allreduce': broadcast the
    # UPDATED params right after optimizer.update as a lower-priority
    # 'weight_prefetch' stream entry DAG-ordered after 'grad_sync'
    # (comm.streams link scheduler). Params are replicated, so the bcast
    # is value-identical — it pre-stages next step's weights on the wire
    # schedule without changing any result bit.
    prefetch_stream: bool = False
    # wire format for sync_mode='compressed_allreduce' ('bf16'|'fp8'|'int8'):
    # gradients cross every hop quantized to 1 byte/element + per-256-block
    # f32 scales; the error-feedback residual (carried in opt_state['ef'])
    # re-injects each step's quantization error into the next step's
    # gradient, so the compressed run tracks the full-precision trajectory.
    # 'bf16' is the full-precision passthrough (bit-identical to
    # tuned_allreduce).
    wire_format: str = "bf16"
    bcast_bucket_bytes: int = 4 << 20
    num_microbatches: int = 1
    remat: bool = True
    seed: int = 0
