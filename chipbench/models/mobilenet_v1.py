"""Plain int8 MobileNet-v1: the model of a configuration whose ``model`` is
``mobilenet_v1``, and the one place the benchmark names it.

``program_graph`` is the only call into the program under test, and it
imports inside the function.  The rest is the reference that decides
``correct``, written from the configuration alone in straightforward
``jax.numpy``: no schedule, no arena, no Pallas, nothing of the program.
It rebuilds the model the configuration states:

* float weights drawn per tensor name (``weight_init`` in the
  configuration: a NumPy generator seeded with the CRC-32 of the name,
  standard normals times a scale);
* post-training quantization (``ptq``): activation ranges observed by one
  float forward pass over a calibration image, asymmetric int8 activations
  whose range includes 0, symmetric per-tensor int8 weights, pooling
  passing its input's parameters through;
* int8 inference: int32 accumulation of ``(x - zp_in) * w`` with SAME
  padding, one float32 requantizing multiply, round half to even, the
  fused ReLU as a lower clamp at the output zero point.

The calibration pass runs eagerly, one primitive at a time, on the default
device at its default matmul precision, and sums the head's products in a
fixed pairwise order: the activation scales are a function of that float
arithmetic, and the configuration states it so that the scales are exact.

``weight_bits=4`` gives the control: the same network with int4 weights.
The int8 arithmetic that any model shares is ``lib/reference.py``.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from lib import reference as R

_DN = ("NHWC", "HWIO", "NHWC")


def program_graph(cfg: dict):
    """The program's own MobileNet-v1 graph at the configuration's width
    and resolution: what ``deploy.build`` is given."""
    from repro.graphs import mobilenet_v1_graph
    return mobilenet_v1_graph(cfg["alpha"], cfg["resolution"])


@dataclasses.dataclass(frozen=True)
class Layer:
    kind: str            # conv | dwconv | avgpool | fc
    weight: str          # weight tensor name ("" for avgpool)
    k: int
    stride: int
    cin: int
    cout: int
    h_out: int           # square outputs: height == width

    @property
    def macs(self) -> int:
        """Multiply-accumulates of one image through this layer."""
        hw = self.h_out * self.h_out
        if self.kind == "conv":
            return hw * self.cout * self.k * self.k * self.cin
        if self.kind == "dwconv":
            return hw * self.cout * self.k * self.k
        if self.kind == "fc":
            return self.cin * self.cout
        return 0


def layers(cfg: dict) -> List[Layer]:
    """MobileNet-v1's layers at the configuration's width and resolution,
    with each weight's name as the configuration's naming rule gives it:
    operators are counted from 1 in order, and a weight is named after the
    operator's kind prefix and number."""
    alpha, h = cfg["alpha"], cfg["resolution"]
    names = cfg["weight_init"]["names"]
    out: List[Layer] = []

    def add(kind, k, stride, cin, cout):
        nonlocal h
        h_out = -(-h // stride)
        n = len(out) + 1
        out.append(Layer(kind, names[kind].format(n=n) if kind in names
                         else "", k, stride, cin, cout, h_out))
        h = h_out

    c = cfg["input_channels"]
    stem = int(cfg["stem_channels"] * alpha)
    add("conv", 3, 2, c, stem)
    c = stem
    for stride, cout in cfg["blocks"]:
        add("dwconv", 3, stride, c, c)
        add("conv", 1, 1, c, int(cout * alpha))
        c = int(cout * alpha)
    add("avgpool", h, h, c, c)
    add("fc", 1, 1, c, cfg["num_classes"])
    return out


def model_macs(cfg: dict) -> int:
    """Multiply-accumulates of one image through the unrewritten graph."""
    return sum(layer.macs for layer in layers(cfg))


def float_weight(cfg: dict, layer: Layer) -> np.ndarray:
    init = cfg["weight_init"]
    if layer.kind == "conv":
        shape = (layer.k, layer.k, layer.cin, layer.cout)
    elif layer.kind == "dwconv":
        shape = (layer.k, layer.k, layer.cin, 1)
    else:
        shape = (layer.cin, layer.cout)
    rng = np.random.default_rng(zlib.crc32(layer.weight.encode()))
    return (rng.standard_normal(shape) * init["scale"]).astype(np.float32)


def calibration_image(cfg: dict) -> np.ndarray:
    r = cfg["resolution"]
    rng = np.random.default_rng(cfg["ptq"]["calibration_seed"])
    return rng.standard_normal((r, r, cfg["input_channels"])
                               ).astype(np.float32)


# ------------------------------------------------------------- calibration
def _float_layer(layer: Layer, x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """One layer of the float network on one (H, W, C) image, eagerly."""
    if layer.kind in ("conv", "dwconv"):
        p = R.same_pads(x.shape[0], layer.k, layer.stride)
        kw = {}
        if layer.kind == "dwconv":
            w = jnp.reshape(jnp.transpose(w, (0, 1, 3, 2)),
                            (layer.k, layer.k, 1, layer.cin))
            kw = dict(feature_group_count=layer.cin)
        y = lax.conv_general_dilated(
            x[None], w, window_strides=(layer.stride, layer.stride),
            padding=[p, p], dimension_numbers=_DN, **kw)[0]
        return np.asarray(jnp.maximum(y, 0.0))
    if layer.kind == "avgpool":
        return np.asarray(jnp.mean(x, axis=(0, 1), keepdims=True))
    # fc: the products, then a fixed pairwise tree of adds
    p = jnp.reshape(x, (-1, 1)) * w
    while p.shape[0] > 1:
        h = p.shape[0] // 2
        top = p[:h] + p[h:2 * h]
        p = top if p.shape[0] % 2 == 0 else jnp.concatenate(
            [top, p[2 * h:]], axis=0)
    return np.asarray(p[0])[None, None, :]


@dataclasses.dataclass
class QuantModel:
    """Everything the int8 forward needs, derived from the configuration."""

    layers: List[Layer]
    act: List[R.QP]               # [input, output of layer 0, 1, ...]
    weights: List[Optional[np.ndarray]]
    mults: List[Optional[float]]

    def quantize_input(self, images: np.ndarray) -> np.ndarray:
        return R.quantize(images, self.act[0])


def quantize_model(cfg: dict, *, weight_bits: int = 8) -> QuantModel:
    """Calibrate on the configuration's image, then quantize."""
    ls = layers(cfg)
    fw = [float_weight(cfg, layer) if layer.weight else None for layer in ls]
    x = calibration_image(cfg)
    ranges = [(float(np.min(x)), float(np.max(x)))]
    for layer, w in zip(ls, fw):
        x = _float_layer(layer, x, w)
        ranges.append((float(np.min(x)), float(np.max(x))))
    act = [R.activation_qp(*r) for r in ranges]
    for i, layer in enumerate(ls):
        if layer.kind == "avgpool":     # pooling keeps its input's params
            act[i + 1] = act[i]
    weights: List[Optional[np.ndarray]] = []
    mults: List[Optional[float]] = []
    for i, w in enumerate(fw):
        if w is None:
            weights.append(None)
            mults.append(None)
            continue
        wq, sw = R.quantize_weight(w, weight_bits)
        weights.append(wq)
        mults.append(act[i].scale * sw / act[i + 1].scale)
    return QuantModel(ls, act, weights, mults)


# ------------------------------------------------------------ int8 forward
def int8_forward(qm: QuantModel):
    """``(N, H, W, C) int8 -> (N, 1, 1, classes) int8``, jitted."""
    ls, act, ws, mults = qm.layers, qm.act, qm.weights, qm.mults

    def fwd(x):
        for i, layer in enumerate(ls):
            qin, qout = act[i], act[i + 1]
            if layer.kind in ("conv", "dwconv"):
                w = jnp.asarray(ws[i], jnp.int32)
                kw = {}
                if layer.kind == "dwconv":
                    w = jnp.reshape(jnp.transpose(w, (0, 1, 3, 2)),
                                    (layer.k, layer.k, 1, layer.cin))
                    kw = dict(feature_group_count=layer.cin)
                p = R.same_pads(x.shape[1], layer.k, layer.stride)
                acc = lax.conv_general_dilated(
                    x.astype(jnp.int32) - qin.zp, w,
                    window_strides=(layer.stride, layer.stride),
                    padding=[p, p], dimension_numbers=_DN,
                    preferred_element_type=jnp.int32, **kw)
                x = R.requantize(acc, mults[i], qout.zp, lo=qout.zp)
            elif layer.kind == "avgpool":
                m = jnp.mean(x.astype(jnp.float32), axis=(1, 2),
                             keepdims=True)
                x = jnp.clip(jnp.round(m), R.QMIN, R.QMAX).astype(jnp.int8)
            else:
                xi = jnp.reshape(x.astype(jnp.int32) - qin.zp,
                                 (x.shape[0], -1, 1))
                acc = jnp.sum(xi * jnp.asarray(ws[i], jnp.int32), axis=1)
                x = R.requantize(acc, mults[i], qout.zp,
                                 lo=R.QMIN)[:, None, None]
        return x

    return jax.jit(fwd)
