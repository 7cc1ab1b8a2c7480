from __future__ import annotations

import jax

from .. import interpret_mode
from .linked_cbr_pool import cbr_avgpool as _kernel_impl
from .ref import cbr_avgpool_ref


@jax.jit
def cbr_avgpool(x, w, b):
    """Linked conv1x1+ReLU+avgpool2x2.  An odd map runs the reference in
    interpret mode and raises when compiled for a TPU."""
    N, H, W, C = x.shape
    if w.ndim == 4:  # (1,1,C,OC) conv weight layout
        w = w[0, 0]
    if H % 2 or W % 2:
        if not interpret_mode():
            raise ValueError(f"cbr_avgpool cannot tile a TPU: the {H}x{W} "
                             "map does not split into 2x2 squares")
        return cbr_avgpool_ref(x, w, b)
    return _kernel_impl(x, w, b, interpret=interpret_mode())
