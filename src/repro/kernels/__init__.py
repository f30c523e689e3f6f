"""Pallas TPU kernels for the compute hot spots (validated in
interpret mode on CPU; Mosaic-compiled on TPU):

* :mod:`.relayout`        — DSE blocked-layout transform (paper P1/P2).
* :mod:`.flash_attention` — blockwise attention (prefill hot spot),
  causal + sliding-window, GQA via index maps.
"""

import jax


def default_interpret() -> bool:
    """Pallas interpret mode for the current backend: on for CPU (tests
    and rehearsals), off for TPU (Mosaic). Any other backend has no
    lowering for these kernels and raises instead of emulating them."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas lowering for backend {backend!r}; the kernels run "
        "compiled on TPU or in interpret mode on CPU"
    )
