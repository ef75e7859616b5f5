"""The Pallas ``interpret`` flag, resolved from the backend."""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["on_tpu", "resolve_interpret"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Single source of truth for the Pallas ``interpret`` flag.

    ``None`` means "whatever the backend needs": the interpreter off-TPU,
    real Mosaic lowering on TPU. Every kernel call site must resolve through
    here — a CPU-backend trace must never embed a literal ``interpret=False``
    (it would try to Mosaic-lower on a backend that can't).
    """
    return (not on_tpu()) if interpret is None else bool(interpret)
