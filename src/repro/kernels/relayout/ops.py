"""Public jit'd entry points for the relayout kernel.

On CPU the Pallas kernel runs in ``interpret=True``; on TPU it compiles
to Mosaic; any other backend raises. ``relayout`` auto-selects;
benchmarks and tests can force either path.
"""

from __future__ import annotations

import jax

from repro.kernels import default_interpret

from .kernel import relayout_pallas
from .ref import dense_to_blocked, blocked_to_dense, parse_layout, relayout_ref

__all__ = [
    "relayout",
    "relayout_str",
    "relayout_ref",
    "parse_layout",
    "dense_to_blocked",
    "blocked_to_dense",
]


def relayout(
    x: jax.Array,
    shape: tuple[int, int],
    src_block: tuple[int, int],
    dst_block: tuple[int, int],
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked-layout transform (see :mod:`.kernel`)."""
    if interpret is None:
        interpret = default_interpret()
    return relayout_pallas(x, shape, src_block, dst_block, interpret=interpret)


def relayout_str(
    x: jax.Array,
    shape: tuple[int, int],
    src_layout: str,
    dst_layout: str,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Same, with the paper's layout strings (e.g. ``"MNM16N8"``)."""
    return relayout(
        x, shape, parse_layout(src_layout), parse_layout(dst_layout),
        interpret=interpret,
    )
