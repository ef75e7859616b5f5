"""Pallas TPU kernels for the perf-critical hot-spots:

  chunked_copy     — pipelined HBM->VMEM->HBM staging copy (the paper's
                     CUDA-kernel-copy analogue, used by the staged bcast path)
  combine_update   — fused add-or-select block merge for the compiled
                     schedule executor (one VMEM pass per replay round)
  param_update     — fused model-average / scaled-add epilogue for bcast sync
  flash_attention  — blocked online-softmax attention with block skipping
  mamba_scan       — Mamba's selective scan, forward and backward, with the
                     state in VMEM (a custom VJP over two kernels; any shape,
                     split by hand over a multi-device mesh)

Each kernel ships ops.py (jit'd wrapper, interpret on CPU / Mosaic on TPU)
and ref.py (pure-jnp oracle used by the test sweeps).
"""
from . import ops, ref
from .combine_update import fused_combine, fused_combine_update
from .ops import chunked_copy, flash_attention, mamba_scan, mix, scaled_add

__all__ = [
    "ops",
    "ref",
    "chunked_copy",
    "fused_combine",
    "fused_combine_update",
    "flash_attention",
    "mamba_scan",
    "mix",
    "scaled_add",
]
