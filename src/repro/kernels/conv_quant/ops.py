"""Jitted public wrappers for the fused int8 kernels, mirroring the q-op
semantics signatures (``qconv2d``/``qdwconv2d``): SAME padding by default,
``hpad`` overriding the height pads for Pex slices, weights in the graph's
``(k, k, Cin, Cout)`` / ``(k, k, Cin, 1)`` layouts.  ``interpret`` is the
caller's decision, made once for the device the program runs on
(``mcu.compile_schedule`` resolves it): ``False`` compiles the kernels to
Mosaic for a TPU; ``True`` runs them through the Pallas interpreter, which on
a CPU lowers them to int32 dot_generals."""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.partition import same_pads

from .kernel import (AddParams, qadd_pallas, qconv1x1_add_pallas,
                     qconv1x1_pallas, qconv_add_pallas, qconv_pallas,
                     qdwconv_pallas)


def _pads(n: int, k: int, stride: int) -> Tuple[int, int]:
    _, beg, end = same_pads(n, k, stride)
    return beg, end


@partial(jax.jit, static_argnames=("stride", "mult", "zp_in", "zp_out",
                                   "lo", "hpad", "wpad", "block_rows",
                                   "interpret"))
def qconv_fused(x, w, *, stride: int, mult: float, zp_in: int, zp_out: int,
                lo: Optional[int] = None,
                hpad: Optional[Tuple[int, int]] = None,
                wpad: Optional[Tuple[int, int]] = None,
                block_rows: Optional[int] = None,
                interpret: bool = False):
    """Fused-kernel drop-in for ``qconv2d`` — bit-identical outputs.
    ``lo`` is the lower clamp (None = ``zp_out``, the fused ReLU; -128 for
    a linear layer).  ``wpad`` overrides the width pads for 2-D tile
    clones (None = SAME)."""
    k = w.shape[0]
    if (k == 1 and stride == 1 and hpad in (None, (0, 0))
            and wpad in (None, (0, 0))):
        return qconv1x1_pallas(
            x, jnp.reshape(w, w.shape[2:]), mult=mult, zp_in=zp_in,
            zp_out=zp_out, lo=lo, block_rows=block_rows or 256,
            interpret=interpret)
    hp = _pads(x.shape[0], k, stride) if hpad is None else tuple(hpad)
    wp = _pads(x.shape[1], k, stride) if wpad is None else tuple(wpad)
    return qconv_pallas(x, w, stride=stride, mult=mult, zp_in=zp_in,
                        zp_out=zp_out, lo=lo, hpad=hp, wpad=wp,
                        block_rows=block_rows or 32, interpret=interpret)


@partial(jax.jit, static_argnames=("stride", "mult", "zp_in", "zp_out",
                                   "add_params", "hpad", "wpad",
                                   "block_rows", "interpret"))
def qconv_add_fused(x, w, r, *, stride: int, mult: float, zp_in: int,
                    zp_out: int, add_params: AddParams,
                    hpad: Optional[Tuple[int, int]] = None,
                    wpad: Optional[Tuple[int, int]] = None,
                    block_rows: Optional[int] = None,
                    interpret: bool = False):
    """Fused drop-in for a ``qconv2d -> qadd`` chain (residual ``r`` is the
    add's second leg): one kernel pass, bit-identical outputs.
    ``add_params = (mult_a, mult_b, zp_a, zp_b, zp_out)`` in the qadd
    argument order, where leg *a* is the conv's output."""
    k = w.shape[0]
    if (k == 1 and stride == 1 and hpad in (None, (0, 0))
            and wpad in (None, (0, 0))):
        return qconv1x1_add_pallas(
            x, jnp.reshape(w, w.shape[2:]), r, mult=mult, zp_in=zp_in,
            zp_out=zp_out, add_params=tuple(add_params),
            block_rows=block_rows or 256, interpret=interpret)
    hp = _pads(x.shape[0], k, stride) if hpad is None else tuple(hpad)
    wp = _pads(x.shape[1], k, stride) if wpad is None else tuple(wpad)
    return qconv_add_pallas(x, w, r, stride=stride, mult=mult, zp_in=zp_in,
                            zp_out=zp_out, add_params=tuple(add_params),
                            hpad=hp, wpad=wp, block_rows=block_rows or 32,
                            interpret=interpret)


@partial(jax.jit, static_argnames=("stride", "mult", "zp_in", "zp_out",
                                   "lo", "hpad", "wpad", "block_rows",
                                   "interpret"))
def qdwconv_fused(x, w, *, stride: int, mult: float, zp_in: int, zp_out: int,
                  lo: Optional[int] = None,
                  hpad: Optional[Tuple[int, int]] = None,
                  wpad: Optional[Tuple[int, int]] = None,
                  block_rows: Optional[int] = None,
                  interpret: bool = False):
    """Fused-kernel drop-in for ``qdwconv2d`` — bit-identical outputs;
    ``lo`` as in ``qconv_fused``."""
    k = w.shape[0]
    hp = _pads(x.shape[0], k, stride) if hpad is None else tuple(hpad)
    wp = _pads(x.shape[1], k, stride) if wpad is None else tuple(wpad)
    wc = jnp.reshape(w, (k, w.shape[1], x.shape[-1]))   # (k,k,Cin,1)->(k,k,C)
    return qdwconv_pallas(x, wc, stride=stride, mult=mult, zp_in=zp_in,
                          zp_out=zp_out, lo=lo, hpad=hp, wpad=wp,
                          block_rows=block_rows or 32, interpret=interpret)


@partial(jax.jit, static_argnames=("add_params", "interpret"))
def qadd_fused(a, b, *, add_params: AddParams, interpret: bool = False):
    """Pallas drop-in for ``qadd`` — bit-identical outputs.
    ``add_params = (mult_a, mult_b, zp_a, zp_b, zp_out)`` in the qadd
    argument order."""
    return qadd_pallas(a, b, add_params=tuple(add_params),
                       interpret=interpret)
