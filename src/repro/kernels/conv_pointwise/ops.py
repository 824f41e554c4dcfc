"""Jitted public wrapper for the fused pointwise conv kernel.
``interpret=True`` runs it through the Pallas interpreter (any backend);
the default compiles it to Mosaic for a TPU."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import conv1x1_pallas


@partial(jax.jit, static_argnames=("relu", "block_rows", "interpret"))
def conv1x1_fused(x, w, b=None, *, relu: bool = True, block_rows: int = 256,
                  interpret: bool = False):
    return conv1x1_pallas(x, w, b, relu=relu, block_rows=block_rows,
                          interpret=interpret)
