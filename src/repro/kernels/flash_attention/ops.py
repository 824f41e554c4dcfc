"""Jitted public wrapper for the flash-attention kernel.
``interpret=True`` runs it through the Pallas interpreter (any backend);
the default compiles it to Mosaic for a TPU."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool = False):
    return flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk,
                                  interpret=interpret)
