"""Jitted public wrapper for the decode-attention kernel."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import decode_attention_pallas


@partial(jax.jit, static_argnames=("bs", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *, bs: int = 256,
                     interpret: bool = False):
    return decode_attention_pallas(q, k_cache, v_cache, lengths, bs=bs,
                                   interpret=interpret)
