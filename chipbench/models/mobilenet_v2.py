"""Plain int8 MobileNet-v2: the model of a configuration whose ``model`` is
``mobilenet_v2``.

``program_graph`` is the only call into the program under test, and it
imports inside the function.  The rest is the reference that decides
``correct``, written from the configuration alone in straightforward
``jax.numpy``: no schedule, no arena, no Pallas, nothing of the program.
It rebuilds the network the configuration states (Sandler et al. 2018,
arXiv:1801.04381, Table 2):

* a 3x3/2 stem, then inverted residual blocks ``(t, c, n, s)``: a 1x1
  expansion to ``t`` times the input channels (none where ``t`` is 1), a
  3x3 depthwise conv of stride ``s`` (1 after a stage's first block), both
  with ReLU6, and a linear 1x1 projection to ``c``; the block's input is
  added to the projection where the stride is 1 and the channels match;
  then a 1x1 conv with ReLU6, a global average pool and the classifier;
* channel counts rounded as ``channel_rounding`` says;
* float weights drawn per tensor name (``weight_init``: a NumPy generator
  seeded with the CRC-32 of the name, standard normals times a scale),
  the operators counted from 1 in order, residual adds included;
* post-training quantization (``ptq``): ranges observed by one float pass
  over the calibration image through the activations themselves (so a
  ReLU6 range ends at 6 or below), asymmetric int8 activations whose
  range includes 0, symmetric per-tensor int8 weights, pooling passing
  its input's parameters through;
* int8 inference: int32 accumulation of ``(x - zp_in) * w`` with SAME
  padding, one float32 requantizing multiply, round half to even, then
  the activation's clamp as TFLite computes it: ReLU6 to ``[zp, min(127,
  zp + round(6 / s))]``, a linear layer to ``[-128, 127]``; the residual
  add in fixed point: each input's multiplier ``s_in / s_out`` rounded to
  16 fractional bits, int32 sums, and an integer division by 2**16
  rounded half to even.

``weight_bits=4`` gives the control: the same network with int4 weights.
The int8 arithmetic that any model shares is ``lib/reference.py``.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from lib import reference as R

_DN = ("NHWC", "HWIO", "NHWC")


def program_graph(cfg: dict):
    """The program's own MobileNet-v2 graph at the configuration's width,
    resolution and classes: what ``deploy.build`` is given."""
    from repro.graphs import mobilenet_v2_graph
    return mobilenet_v2_graph(cfg["alpha"], cfg["resolution"],
                              cfg["num_classes"])


@dataclasses.dataclass(frozen=True)
class Layer:
    kind: str            # conv | dwconv | add | avgpool | fc
    weight: str          # weight tensor name ("" for add, avgpool)
    k: int
    stride: int
    cin: int
    cout: int
    h_out: int           # square outputs: height == width
    act: str             # relu6 | none
    src: Tuple[int, ...]  # activations read: 0 the image, i + 1 layer i's

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


def channels(cfg: dict, c: float) -> int:
    """``c`` rounded as ``channel_rounding`` states (TF-slim's rule)."""
    d = cfg["channel_rounding"]["divisor"]
    new = max(d, int(c + d / 2) // d * d)
    if new < 0.9 * c:
        new += d
    return new


def layers(cfg: dict) -> List[Layer]:
    alpha, h = cfg["alpha"], cfg["resolution"]
    names = cfg["weight_init"]["names"]
    out: List[Layer] = []

    def add(kind, k, stride, cin, cout, act, src):
        nonlocal h
        h_out = -(-h // stride)
        n = len(out) + 1
        out.append(Layer(kind, names[kind].format(n=n) if kind in names
                         else "", k, stride, cin, cout, h_out, act, src))
        h = h_out
        return n                       # index of the new activation

    c = channels(cfg, cfg["stem_channels"] * alpha)
    x = add("conv", 3, 2, cfg["input_channels"], c, "relu6", (0,))
    for t, cb, n, s in cfg["blocks"]:
        cout = channels(cfg, cb * alpha)
        for i in range(n):
            stride = s if i == 0 else 1
            y = x
            if t != 1:
                y = add("conv", 1, 1, c, c * t, "relu6", (y,))
            y = add("dwconv", 3, stride, c * t, c * t, "relu6", (y,))
            y = add("conv", 1, 1, c * t, cout, "none", (y,))
            if stride == 1 and c == cout:
                y = add("add", 1, 1, cout, cout, "none", (y, x))
            x, c = y, cout
    last = cfg["last_channels"]
    last = max(last, channels(cfg, last * alpha))
    x = add("conv", 1, 1, c, last, "relu6", (x,))
    x = add("avgpool", h, h, last, last, "none", (x,))
    add("fc", 1, 1, last, cfg["num_classes"], "none", (x,))
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
def _conv_weight(layer: Layer, w):
    """HWIO weights and the group count of ``lax.conv_general_dilated``."""
    if layer.kind == "dwconv":
        return (jnp.reshape(jnp.transpose(w, (0, 1, 3, 2)),
                            (layer.k, layer.k, 1, layer.cin)),
                dict(feature_group_count=layer.cin))
    return w, {}


def _float_layer(layer: Layer, xs, w) -> np.ndarray:
    """One layer of the float network on one (H, W, C) image, eagerly."""
    x = xs[0]
    if layer.kind in ("conv", "dwconv"):
        p = R.same_pads(x.shape[0], layer.k, layer.stride)
        w, kw = _conv_weight(layer, w)
        y = lax.conv_general_dilated(
            x[None], w, window_strides=(layer.stride, layer.stride),
            padding=[p, p], dimension_numbers=_DN, **kw)[0]
        return np.asarray(jnp.clip(y, 0.0, 6.0) if layer.act == "relu6"
                          else y)
    if layer.kind == "add":
        return np.asarray(xs[0] + xs[1])
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
    xs = [calibration_image(cfg)]
    for layer, w in zip(ls, fw):
        xs.append(_float_layer(layer, [xs[j] for j in layer.src], w))
    act = [R.activation_qp(float(np.min(x)), float(np.max(x))) for x in xs]
    for i, layer in enumerate(ls):
        if layer.kind == "avgpool":     # pooling keeps its input's params
            act[i + 1] = act[layer.src[0]]
    weights: List[Optional[np.ndarray]] = []
    mults: List[Optional[float]] = []
    for i, (layer, w) in enumerate(zip(ls, fw)):
        if w is None:
            weights.append(None)
            mults.append(None)
            continue
        wq, sw = R.quantize_weight(w, weight_bits)
        weights.append(wq)
        mults.append(act[layer.src[0]].scale * sw / act[i + 1].scale)
    return QuantModel(ls, act, weights, mults)


# ------------------------------------------------------------ int8 forward
ADD_SHIFT = 16          # fractional bits of the residual add's multipliers


def _int8_add(a, b, qa: R.QP, qb: R.QP, qout: R.QP):
    one = 1 << ADD_SHIFT
    ma = round(qa.scale / qout.scale * one)     # Python: half to even
    mb = round(qb.scale / qout.scale * one)
    acc = ((a.astype(jnp.int32) - qa.zp) * ma
           + (b.astype(jnp.int32) - qb.zp) * mb)
    q = jnp.floor_divide(acc, one)
    r = acc - q * one
    up = (r > one // 2) | ((r == one // 2) & (q % 2 == 1))
    return jnp.clip(q + up + qout.zp, R.QMIN, R.QMAX).astype(jnp.int8)


def _relu6_hi(qout: R.QP) -> int:
    return min(R.QMAX, qout.zp + int(round(6.0 / qout.scale)))


def int8_forward(qm: QuantModel):
    """``(N, H, W, C) int8 -> (N, 1, 1, classes) int8``, jitted."""
    ls, act, ws, mults = qm.layers, qm.act, qm.weights, qm.mults

    def fwd(x):
        xs: Dict[int, jax.Array] = {0: x}
        for i, layer in enumerate(ls):
            qin, qout = act[layer.src[0]], act[i + 1]
            x = xs[layer.src[0]]
            if layer.kind in ("conv", "dwconv"):
                w, kw = _conv_weight(layer, jnp.asarray(ws[i], jnp.int32))
                p = R.same_pads(x.shape[1], layer.k, layer.stride)
                acc = lax.conv_general_dilated(
                    x.astype(jnp.int32) - qin.zp, w,
                    window_strides=(layer.stride, layer.stride),
                    padding=[p, p], dimension_numbers=_DN,
                    preferred_element_type=jnp.int32, **kw)
                if layer.act == "relu6":
                    y = R.requantize(acc, mults[i], qout.zp, lo=qout.zp)
                    y = jnp.minimum(y, jnp.int8(_relu6_hi(qout)))
                else:
                    y = R.requantize(acc, mults[i], qout.zp, lo=R.QMIN)
            elif layer.kind == "add":
                j = layer.src[1]
                y = _int8_add(x, xs[j], qin, act[j], qout)
            elif layer.kind == "avgpool":
                m = jnp.mean(x.astype(jnp.float32), axis=(1, 2),
                             keepdims=True)
                y = jnp.clip(jnp.round(m), R.QMIN, R.QMAX).astype(jnp.int8)
            else:
                xi = jnp.reshape(x.astype(jnp.int32) - qin.zp,
                                 (x.shape[0], -1, 1))
                acc = jnp.sum(xi * jnp.asarray(ws[i], jnp.int32), axis=1)
                y = R.requantize(acc, mults[i], qout.zp,
                                 lo=R.QMIN)[:, None, None]
            xs[i + 1] = y
        return xs[len(ls)]

    return jax.jit(fwd)
