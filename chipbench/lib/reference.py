"""The int8 arithmetic every model's reference shares, and the comparison
that decides ``correct``.

A model's reference (its layers, weight naming, calibration, quantization
and int8 forward) lives in ``models/<model>.py``, loaded by the
configuration's ``model`` key (``harness.load_model``).  This file holds
what any int8 model shares, in plain NumPy and ``jax.numpy`` with nothing
imported from the program under test:

* ``QP`` and ``activation_qp``: asymmetric per-tensor int8 activations
  whose range includes 0; ``quantize``: float images to int8 at the edge;
* ``quantize_weight``: symmetric per-tensor weights in ``[-w, w]``,
  ``w = 2**(bits-1) - 1`` (``bits=4`` is the control);
* ``same_pads``: TensorFlow's SAME padding;
* ``requantize``: an int32 accumulator to int8 by one float32 multiply,
  round half to even, and a clamp (a fused ReLU clamps at the output zero
  point);
* ``logits``: a model's int8 forward over quantized images, in blocks;
* ``compare``: the exact comparison of served logits with the reference's.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

QMIN, QMAX = -128, 127


@dataclasses.dataclass(frozen=True)
class QP:
    scale: float
    zp: int


def activation_qp(lo: float, hi: float) -> QP:
    lo, hi = min(0.0, lo), max(0.0, hi)
    scale = (hi - lo) / (QMAX - QMIN) or 1.0
    zp = int(round(QMIN - lo / scale))
    return QP(scale, max(QMIN, min(QMAX, zp)))


def quantize(x: np.ndarray, qp: QP) -> np.ndarray:
    """Float values to int8 with ``qp``, as the edge quantizer does."""
    q = np.round(np.asarray(x, np.float32) / np.float32(qp.scale))
    return np.clip(q + qp.zp, QMIN, QMAX).astype(np.int8)


def quantize_weight(w: np.ndarray, bits: int) -> Tuple[np.ndarray, float]:
    """Symmetric per-tensor weights: the integers (held as int8) and the
    scale."""
    wmax = 2 ** (bits - 1) - 1
    sw = float(max(np.abs(w).max(), 1e-8)) / wmax
    wq = np.clip(np.round(w / np.float32(sw)), -wmax, wmax)
    return wq.astype(np.int8), sw


def same_pads(n: int, k: int, stride: int):
    """(begin, end) of TensorFlow's SAME padding along one axis."""
    out = -(-n // stride)
    total = max((out - 1) * stride + k - n, 0)
    return total // 2, total - total // 2


def requantize(acc, mult: float, zp: int, lo: int):
    y = jnp.round(acc.astype(jnp.float32) * jnp.float32(mult)) + zp
    return jnp.clip(y, lo, QMAX).astype(jnp.int8)


def logits(forward: Callable, images_q: np.ndarray, *,
           block: int = 32) -> np.ndarray:
    """int8 logits ``(N, classes)`` of quantized images by a model's
    jitted ``forward``, ``block`` images per call so that the int32
    activations fit beside the program."""
    out = []
    for i in range(0, len(images_q), block):
        part = images_q[i:i + block]
        pad = block - len(part)
        if pad:                    # one compiled shape for every block
            part = np.concatenate([part, np.zeros((pad,) + part.shape[1:],
                                                  part.dtype)])
        y = np.asarray(forward(part)).reshape(block, -1)
        out.append(y[:block - pad])
    return np.concatenate(out)


def compare(got: Sequence[Optional[np.ndarray]], want: np.ndarray
            ) -> Dict[str, int]:
    """Exact comparison of served int8 logits with the reference's.

    ``got[i]`` is the i-th answer (``None`` when none came); ``want[i]``
    the reference's logits for the same image.  Returns the numbers that
    ``correct`` is decided on, each of which has the limit 0."""
    missing = sum(g is None for g in got)
    gaps = [int(np.max(np.abs(np.asarray(g, np.int32).reshape(-1)
                              - want[i].astype(np.int32))))
            for i, g in enumerate(got) if g is not None]
    return {"missing_answers": missing,
            "wrong_answers": sum(gap > 0 for gap in gaps),
            "max_logit_gap_lsb": max(gaps, default=0)}
