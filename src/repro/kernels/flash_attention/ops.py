"""Public jit'd entry points for the flash-attention kernel."""

from __future__ import annotations

import jax

from repro.kernels import default_interpret

from .kernel import flash_attention_pallas
from .ref import attention_ref

__all__ = ["flash_attention", "attention_ref"]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Blockwise attention, (B, H, S, D) x (B, Hkv, S, D)^2 -> (B, H, S, D).

    Pallas kernel on TPU; ``interpret=True`` (Python emulation) on CPU;
    any other backend raises.
    """
    if interpret is None:
        interpret = default_interpret()
    return flash_attention_pallas(
        q, k, v,
        causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
