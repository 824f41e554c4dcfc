"""Executable semantics for CNN graph operators (used by the
micro-interpreter simulator).  Weights are deterministic per-op constants
kept in ``Operator.attrs`` — they model NOR-Flash residency (paper §2.2:
parameters are immutable static data, only activations occupy SRAM), so they
are *not* tensors of the scheduling graph.

This module is also where operators are classified for **partial execution**
(Pex-style spatial slicing, ``core/partition.py``):

* sliceable — elementwise (``add``), depthwise/regular convolution and
  spatial max-pooling: their output rows map to a bounded window of input
  rows under SAME padding, so a slice can be computed from a halo'd input
  window with explicit edge padding, bit-identically to the full op;
* not sliceable — global ``avgpool`` (its 1×1 output needs every input
  row), ``fc`` (ditto), and ``concat`` (channel-wise join of whole maps).

Each builder attaches a ``SliceSpec`` for the sliceable kinds; the spec's
``make_fn`` rebuilds the op with explicit height padding for a slice.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp
from jax import lax

from repro.core.graph import Graph, Operator
from repro.core.partition import PEX_ATTR, SliceSpec, same_pads


def _weight(name: str, shape: Tuple[int, ...], scale: float = 0.1):
    # crc32, not hash(): str hashes are salted per process, which made
    # every process draw different weights
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    return (rng.standard_normal(shape) * scale).astype(np.float32)


def conv_out_hw(h: int, w: int, stride: int) -> Tuple[int, int]:
    return math.ceil(h / stride), math.ceil(w / stride)


def _pads(n: int, k: int, stride: int) -> Tuple[int, int]:
    _, beg, end = same_pads(n, k, stride)
    return beg, end


# ----------------------------------------------------- slice-spec factories
def _windowed_slice_fn(kernel_name: str, attr_names: Tuple[str, ...],
                       kw_names: Tuple[str, ...] = ()):
    """make_fn factory for windowed kernels: reads the kernel's extra args
    from op.attrs (``kw_names`` by keyword, where the op has them) and
    rebuilds it with explicit height padding — and, for 2-D tile clones,
    explicit width padding.  1-D callers pass two pads and get the legacy
    closure (no ``wpad`` argument at all), so the row-ring path traces
    byte-identical jaxprs."""
    def make(op: Operator, pad_top: int, pad_bottom: int,
             pad_left: Optional[int] = None, pad_right: Optional[int] = None):
        kernel = globals()[kernel_name]
        args = tuple(op.attrs[a] for a in attr_names)
        kw = {a: op.attrs[a] for a in kw_names if a in op.attrs}

        if pad_left is None:
            def fn(x, kernel=kernel, args=args, hpad=(pad_top, pad_bottom)):
                return kernel(x, *args, hpad=hpad, **kw)
        else:
            def fn(x, kernel=kernel, args=args, hpad=(pad_top, pad_bottom),
                   wpad=(pad_left, pad_right)):
                return kernel(x, *args, hpad=hpad, wpad=wpad, **kw)
        return fn
    return make


def _elementwise_slice_fn(op: Operator, pad_top: int, pad_bottom: int,
                          pad_left: int = 0, pad_right: int = 0):
    assert pad_top == 0 and pad_bottom == 0
    assert pad_left in (0, None) and pad_right in (0, None)
    return op.fn


_QCONV_ATTRS = ("weight_q", "stride", "mult", "zp_in", "zp_out")


def pex_spec(kind: str, out_shape: Tuple[int, int, int], cin: int,
             k: int = 1, stride: int = 1) -> Optional[SliceSpec]:
    """The partial-execution classification of a CNN operator kind.  The
    int8 kinds (``q*``) slice exactly like their float counterparts: the
    row map only depends on kernel/stride, and requantization is per-tensor
    so every slice applies the same (scale, zero-point)."""
    oh, ow, cout = out_shape
    if kind == "conv":
        return SliceSpec(k, stride, (0,),
                         _windowed_slice_fn("conv2d", ("weight", "stride"),
                                            ("act",)),
                         macs_per_row=ow * cout * k * k * cin)
    if kind == "dwconv":
        return SliceSpec(k, stride, (0,),
                         _windowed_slice_fn("dwconv2d", ("weight", "stride"),
                                            ("act",)),
                         macs_per_row=ow * cout * k * k)
    if kind == "maxpool":
        return SliceSpec(k, stride, (0,),
                         _windowed_slice_fn("maxpool2d", ("k", "stride")),
                         macs_per_row=ow * cout * k * k)
    if kind == "add":
        return SliceSpec(1, 1, None, _elementwise_slice_fn,
                         macs_per_row=ow * cout)
    if kind == "qconv":
        return SliceSpec(k, stride, (0,),
                         _windowed_slice_fn("qconv2d", _QCONV_ATTRS,
                                            ("lo",)),
                         macs_per_row=ow * cout * k * k * cin)
    if kind == "qdwconv":
        return SliceSpec(k, stride, (0,),
                         _windowed_slice_fn("qdwconv2d", _QCONV_ATTRS,
                                            ("lo",)),
                         macs_per_row=ow * cout * k * k)
    if kind == "qmaxpool":
        return SliceSpec(k, stride, (0,),
                         _windowed_slice_fn("qmaxpool2d", ("k", "stride")),
                         macs_per_row=ow * cout * k * k)
    if kind == "qadd":
        return SliceSpec(1, 1, None, _elementwise_slice_fn,
                         macs_per_row=ow * cout)
    return None    # concat / avgpool / fc: not spatially sliceable


# Each builder registers a tensor + operator on the graph and returns the
# output tensor name.  The builder models the *float* network, so tensors
# are float32 and sizes are honest bytes (4 * H * W * C); the post-training
# int8 path (``graphs/quantize.py``) rewrites the graph with int8 tensors
# at 1 byte per element — the byte-for-byte composition of quantization
# with reordering/Pex the paper calls "orthogonal".
F32 = 4   # bytes per float32 element


class CNNBuilder:
    def __init__(self, graph: Graph):
        self.g = graph
        self.shapes: Dict[str, Tuple[int, int, int]] = {}
        self._n = 0

    def _next(self, prefix: str) -> str:
        self._n += 1
        return f"{prefix}{self._n}"

    def input(self, name: str, h: int, w: int, c: int) -> str:
        self.g.add_tensor(name, F32 * h * w * c, (h, w, c), dtype="float32")
        self.shapes[name] = (h, w, c)
        return name

    def _emit(self, kind: str, inputs: Sequence[str], out_shape, fn,
              cin: int = 0, **attrs):
        name = self._next(kind)
        out = f"{name}_out"
        h, w, c = out_shape
        self.g.add_tensor(out, F32 * h * w * c, out_shape, dtype="float32")
        self.shapes[out] = out_shape
        spec = pex_spec(kind, out_shape, cin, attrs.get("k", 1),
                        attrs.get("stride", 1))
        if spec is not None:
            attrs[PEX_ATTR] = spec
        self.g.add_operator(name, list(inputs), out, kind=kind, fn=fn, **attrs)
        return out

    def conv(self, x: str, cout: int, k: int = 1, stride: int = 1,
             act: str = "relu") -> str:
        """``act`` is the fused activation (``ACTS``): ReLU, ReLU6, or
        none for a linear layer such as a bottleneck projection."""
        _check_act(act)
        h, w, cin = self.shapes[x]
        oh, ow = conv_out_hw(h, w, stride)
        wname = f"conv{self._n + 1}_w"
        wgt = _weight(wname, (k, k, cin, cout))

        def fn(a, w=wgt, stride=stride, act=act):
            return conv2d(a, w, stride, act=act)

        return self._emit("conv", [x], (oh, ow, cout), fn, cin=cin,
                          weight_bytes=wgt.nbytes, weight=wgt, k=k,
                          stride=stride, act=act)

    def dwconv(self, x: str, k: int = 3, stride: int = 1,
               act: str = "relu") -> str:
        _check_act(act)
        h, w, cin = self.shapes[x]
        oh, ow = conv_out_hw(h, w, stride)
        wname = f"dw{self._n + 1}_w"
        wgt = _weight(wname, (k, k, cin, 1))

        def fn(a, w=wgt, stride=stride, act=act):
            return dwconv2d(a, w, stride, act=act)

        return self._emit("dwconv", [x], (oh, ow, cin), fn, cin=cin,
                          weight_bytes=wgt.nbytes, weight=wgt, k=k,
                          stride=stride, act=act)

    def maxpool(self, x: str, k: int = 2, stride: int = 2) -> str:
        h, w, c = self.shapes[x]
        oh, ow = conv_out_hw(h, w, stride)

        def fn(a, k=k, stride=stride):
            return maxpool2d(a, k, stride)

        return self._emit("maxpool", [x], (oh, ow, c), fn, cin=c,
                          k=k, stride=stride)

    def concat(self, xs: Sequence[str]) -> str:
        shapes = [self.shapes[x] for x in xs]
        h, w = shapes[0][0], shapes[0][1]
        c = sum(s[2] for s in shapes)

        def fn(*arrays):
            return jnp.concatenate(arrays, axis=-1)

        return self._emit("concat", xs, (h, w, c), fn)

    def add(self, a: str, b: str) -> str:
        def fn(x, y):
            return x + y

        cin = self.shapes[a][2]
        return self._emit("add", [a, b], self.shapes[a], fn, cin=cin)

    def avgpool(self, x: str) -> str:
        h, w, c = self.shapes[x]

        def fn(a):
            return jnp.mean(a, axis=(0, 1), keepdims=True)

        return self._emit("avgpool", [x], (1, 1, c), fn)

    def fc(self, x: str, nout: int) -> str:
        h, w, c = self.shapes[x]
        wgt = _weight(f"fc{self._n + 1}_w", (h * w * c, nout))

        def fn(a, w=wgt):
            # products, then a fixed pairwise tree of adds — not a dot or a
            # fused mul+reduce, whose accumulation order (and FMA
            # contraction) XLA CPU picks per context: eager, jitted and
            # vmapped runs must round identically for the compiled
            # executor's bit-identity contract with this eager reference.
            p = lax.optimization_barrier(jnp.reshape(a, (-1, 1)) * w)
            return _tree_sum(p)[None, None, :]

        return self._emit("fc", [x], (1, 1, nout), fn, weight=wgt,
                          weight_bytes=wgt.nbytes)


def _tree_sum(p):
    """Sum over axis 0 as a fixed pairwise tree of elementwise adds."""
    while p.shape[0] > 1:
        h = p.shape[0] // 2
        top = p[:h] + p[h:2 * h]
        p = top if p.shape[0] % 2 == 0 else jnp.concatenate(
            [top, p[2 * h:]], axis=0)
    return p[0]


# Fused activations of conv/dwconv: ReLU, ReLU6 (MobileNet-v2's expansion
# and depthwise layers), and none (its linear bottleneck projections).
ACTS = ("relu", "relu6", "none")


def _check_act(act: str) -> None:
    if act not in ACTS:
        raise ValueError(f"activation {act!r} is not one of {ACTS}")


def _activate(y, act: str):
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "relu6":
        return jnp.clip(y, 0.0, 6.0)
    _check_act(act)
    return y


def conv2d(x, w, stride: int, hpad: Optional[Tuple[int, int]] = None,
           wpad: Optional[Tuple[int, int]] = None, act: str = "relu"):
    """x: (H,W,Cin) f32; w: (k,k,Cin,Cout); SAME padding; then ``act``.

    ``hpad`` overrides the height padding with an explicit (top, bottom)
    pair — partial execution uses this to run a slice whose interior edges
    get their halo rows from the input window instead of zero padding.
    ``wpad`` is the width-axis twin, used by 2-D tile clones whose column
    windows carry their own halos.  SAME is reproduced exactly when either
    is None.
    """
    k = w.shape[0]
    hp = _pads(x.shape[0], k, stride) if hpad is None else tuple(hpad)
    wp = _pads(x.shape[1], w.shape[1], stride) if wpad is None else tuple(wpad)
    y = lax.conv_general_dilated(
        x[None], w, window_strides=(stride, stride), padding=[hp, wp],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    return _activate(y, act)


def dwconv2d(x, w, stride: int, hpad: Optional[Tuple[int, int]] = None,
             wpad: Optional[Tuple[int, int]] = None, act: str = "relu"):
    cin = x.shape[-1]
    k = w.shape[0]
    hp = _pads(x.shape[0], k, stride) if hpad is None else tuple(hpad)
    wp = _pads(x.shape[1], w.shape[1], stride) if wpad is None else tuple(wpad)
    y = lax.conv_general_dilated(
        x[None], jnp.reshape(jnp.transpose(w, (0, 1, 3, 2)), (w.shape[0], w.shape[1], 1, cin)),
        window_strides=(stride, stride), padding=[hp, wp],
        feature_group_count=cin,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    return _activate(y, act)


def maxpool2d(x, k: int, stride: int,
              hpad: Optional[Tuple[int, int]] = None,
              wpad: Optional[Tuple[int, int]] = None):
    """SAME max-pooling over (H, W); padding rows take the -inf identity, so
    explicit-pad slices are bit-identical to the full op."""
    hp = _pads(x.shape[0], k, stride) if hpad is None else tuple(hpad)
    wp = _pads(x.shape[1], k, stride) if wpad is None else tuple(wpad)
    return lax.reduce_window(x, -jnp.inf, lax.max, (k, k, 1),
                             (stride, stride, 1), (hp, wp, (0, 0)))


def model_weight_bytes(graph: Graph) -> int:
    return sum(op.attrs.get("weight_bytes", 0) for op in graph.operators)


# ------------------------------------------------------ int8 (quantized) ops
# Per-tensor affine quantization (TFLite-Micro convention): real = scale *
# (q - zero_point), q int8 in [-128, 127].  Convolutions subtract the input
# zero-point, accumulate in int32 (exact), then requantize through a single
# float32 multiplier ``mult = s_in * s_w / s_out`` with round-half-even —
# every step is deterministic, so the compiled executor's int8 outputs are
# bit-identical to the interpreter's, slice-by-slice (the same contract the
# f32 path keeps).  SAME padding in the quantized domain pads with the input
# zero-point, which the (x - zp) -> pad-with-0 formulation gives for free,
# so Pex slices of int8 ops stay bit-identical too.
INT8_MIN, INT8_MAX = -128, 127


def requantize(acc, mult: float, zp_out: int, lo: int = INT8_MIN):
    """int32 accumulator -> int8 at the output (scale, zero_point).  ``lo``
    is the lower clamp: ``zp_out`` for fused relu (real 0), -128 otherwise.

    There is no upper clamp but 127.  A ReLU6 output is calibrated through
    its clip at 6, so its range is [0, m] with m <= 6: ``s_out = m / 255``
    and ``zp_out = -128``, and real 6 sits at ``zp_out + round(6 / s_out)
    >= 127``.  The int8 ReLU6 is therefore the clamp ``[zp_out, 127]``,
    the range TFLite's activation rule gives it (``activation_lo``)."""
    y = jnp.round(acc.astype(jnp.float32) * jnp.float32(mult)) + zp_out
    return jnp.clip(y, lo, INT8_MAX).astype(jnp.int8)


def quantize_array(x, scale: float, zp: int):
    """f32 -> int8 at (scale, zp).  Also the semantics of ``quant`` ops in
    mixed-precision graphs."""
    q = jnp.round(x.astype(jnp.float32) / jnp.float32(scale)) + zp
    return jnp.clip(q, INT8_MIN, INT8_MAX).astype(jnp.int8)


def dequantize_array(q, scale: float, zp: int):
    """int8 -> f32; the semantics of ``dequant`` ops."""
    return (q.astype(jnp.float32) - zp) * jnp.float32(scale)


def activation_lo(act: str, zp_out: int) -> int:
    """The int8 lower clamp of a fused activation: ``zp_out`` (real 0) for
    ReLU and ReLU6, -128 for a linear layer (see ``requantize``)."""
    _check_act(act)
    return INT8_MIN if act == "none" else zp_out


def qconv2d(x, w, stride: int, mult: float, zp_in: int, zp_out: int,
            hpad: Optional[Tuple[int, int]] = None,
            wpad: Optional[Tuple[int, int]] = None,
            lo: Optional[int] = None):
    """x: (H,W,Cin) int8; w: (k,k,Cin,Cout) int8; SAME padding; lower clamp
    ``lo`` (default ``zp_out``: fused relu; ``activation_lo``).
    ``hpad``/``wpad`` as in ``conv2d``."""
    k = w.shape[0]
    hp = _pads(x.shape[0], k, stride) if hpad is None else tuple(hpad)
    wp = _pads(x.shape[1], w.shape[1], stride) if wpad is None else tuple(wpad)
    xi = x.astype(jnp.int32) - zp_in       # pad rows become 0 == zp_in
    acc = lax.conv_general_dilated(
        xi[None], jnp.asarray(w, jnp.int32), window_strides=(stride, stride),
        padding=[hp, wp], dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    return requantize(acc, mult, zp_out, lo=zp_out if lo is None else lo)


def qdwconv2d(x, w, stride: int, mult: float, zp_in: int, zp_out: int,
              hpad: Optional[Tuple[int, int]] = None,
              wpad: Optional[Tuple[int, int]] = None,
              lo: Optional[int] = None):
    cin = x.shape[-1]
    k = w.shape[0]
    hp = _pads(x.shape[0], k, stride) if hpad is None else tuple(hpad)
    wp = _pads(x.shape[1], w.shape[1], stride) if wpad is None else tuple(wpad)
    xi = x.astype(jnp.int32) - zp_in
    wi = jnp.reshape(jnp.transpose(jnp.asarray(w, jnp.int32), (0, 1, 3, 2)),
                     (w.shape[0], w.shape[1], 1, cin))
    acc = lax.conv_general_dilated(
        xi[None], wi, window_strides=(stride, stride), padding=[hp, wp],
        feature_group_count=cin,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    return requantize(acc, mult, zp_out, lo=zp_out if lo is None else lo)


def qmaxpool2d(x, k: int, stride: int,
               hpad: Optional[Tuple[int, int]] = None,
               wpad: Optional[Tuple[int, int]] = None):
    """Max-pooling is order-preserving, so scale/zero-point pass through;
    padding takes the int8 identity -128 (mirrors the f32 -inf)."""
    hp = _pads(x.shape[0], k, stride) if hpad is None else tuple(hpad)
    wp = _pads(x.shape[1], k, stride) if wpad is None else tuple(wpad)
    return lax.reduce_window(x, np.int8(INT8_MIN), lax.max, (k, k, 1),
                             (stride, stride, 1), (hp, wp, (0, 0)))


# qadd runs in fixed point: the two rescale multipliers are quantized to
# QADD_SHIFT fractional bits at trace time and the whole op is int32
# arithmetic + an integer round-half-even.  A float formulation
# (``round((a-zp_a)*mult_a + (b-zp_b)*mult_b)``) is NOT bit-stable across
# execution contexts: XLA CPU codegen contracts the mul->add into an FMA
# under jit (optimization_barrier/bitcast do not survive codegen), so the
# eager interpreter and the jitted compiled executor disagreed by +-1 on
# exact-half ties.  Integer ops cannot be contracted, so this sequence is
# bit-identical everywhere — eager, jit, and inside Pallas kernels (the
# fused conv->add kernel replays it literally).
QADD_SHIFT = 16


def _round_half_even_rshift(acc, shift: int):
    """Round-half-even of ``acc / 2**shift`` in pure integer arithmetic
    (``acc`` any signed int array; arithmetic right shift floors)."""
    base = acc >> shift
    rem = acc - (base << shift)          # in [0, 2**shift)
    half = 1 << (shift - 1)
    return jnp.where(rem > half, base + 1,
                     jnp.where(rem < half, base, base + (base & 1)))


def qadd(a, b, mult_a: float, mult_b: float, zp_a: int, zp_b: int,
         zp_out: int):
    ma = int(round(float(mult_a) * (1 << QADD_SHIFT)))
    mb = int(round(float(mult_b) * (1 << QADD_SHIFT)))
    assert abs(ma) + abs(mb) <= (1 << 23), "qadd multipliers too large"
    acc = ((a.astype(jnp.int32) - zp_a) * ma
           + (b.astype(jnp.int32) - zp_b) * mb)
    y = _round_half_even_rshift(acc, QADD_SHIFT) + zp_out
    return jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int8)


def qavgpool(x):
    """Global average in the quantized domain (scale/zp pass through: the
    mean of q-values represents the mean of reals at the same params)."""
    m = jnp.mean(x.astype(jnp.float32), axis=(0, 1), keepdims=True)
    return jnp.clip(jnp.round(m), INT8_MIN, INT8_MAX).astype(jnp.int8)


def qfc(x, w, mult: float, zp_in: int, zp_out: int):
    """int8 fully-connected; mul+reduce in int32 for the same
    context-insensitivity reason as the f32 ``fc`` (and exactness)."""
    xi = jnp.reshape(x.astype(jnp.int32) - zp_in, (-1, 1))
    acc = jnp.sum(xi * jnp.asarray(w, jnp.int32), axis=0)[None, None, :]
    return requantize(acc, mult, zp_out)


def qconcat(*xs, mults: Sequence[float], zps: Sequence[int], zp_out: int):
    """Channel concat with per-input requantization to the output params."""
    parts = []
    for x, m, zp in zip(xs, mults, zps):
        y = jnp.round((x.astype(jnp.float32) - zp) * jnp.float32(m)) + zp_out
        parts.append(jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int8))
    return jnp.concatenate(parts, axis=-1)


# ----------------------------------------- receptive-field redistribution
# 2-D tiled cascades pay halo recompute along BOTH spatial axes, and the
# bill scales with the receptive field of the early (high-resolution) ops.
# MCUNetV2's "receptive field redistribution" shifts kernel reach from the
# expensive early stage to the cheap late stage: shrink an early kernel to
# its center tap (a flagged MODEL EDIT — accuracy must be re-validated by
# retraining, which is out of scope here) and grow a late kernel by
# zero-embedding (function-preserving: a zero tap contributes exactly 0 to
# the int32/f32 accumulation, so outputs stay bit-identical while the
# planner sees — and prices — the larger reach).  ``cascade_graph(...,
# rf_redistribute=(shrink_op, grow_op))`` applies the pair before planning.
_RF_KINDS = ("conv", "dwconv", "qconv", "qdwconv")


def _rf_op(graph: Graph, op_name: str) -> Operator:
    for op in graph.operators:
        if op.name == op_name:
            if op.kind not in _RF_KINDS:
                raise ValueError(
                    f"receptive-field edit needs a conv kind, {op_name!r} "
                    f"is {op.kind!r}")
            return op
    raise KeyError(op_name)


def _rf_rebuild(graph: Graph, op: Operator, new_w: Optional[np.ndarray],
                new_k: int, rf_edit: str) -> Graph:
    """Copy of ``graph`` with ``op`` rebuilt at kernel size ``new_k``:
    weights/attrs/fn/SliceSpec all refreshed so the planner's halo maps and
    the executable semantics agree on the new reach."""
    wkey = "weight_q" if op.kind.startswith("q") else "weight"
    attrs = {a: v for a, v in op.attrs.items() if a != PEX_ATTR}
    old_k = attrs["k"]
    stride = attrs["stride"]
    attrs["k"] = new_k
    attrs["rf_edit"] = rf_edit
    if new_w is not None:
        attrs[wkey] = new_w
        attrs["weight_bytes"] = new_w.nbytes
    elif "weight_bytes" in attrs:
        # scheduling-only graphs carry no weights: scale flash accounting
        attrs["weight_bytes"] = (attrs["weight_bytes"] * new_k * new_k
                                 // (old_k * old_k))
    out_shape = tuple(graph.tensors[op.output].shape)
    in_shape = graph.tensors[op.inputs[0]].shape
    cin = in_shape[-1] if in_shape else 1
    spec = pex_spec(op.kind, out_shape, cin, new_k, stride)
    if spec is not None:
        attrs[PEX_ATTR] = spec
    fn = None
    if new_w is not None and op.fn is not None:
        if op.kind == "conv":
            def fn(a, w=new_w, s=stride, act=attrs.get("act", "relu")):
                return conv2d(a, w, s, act=act)
        elif op.kind == "dwconv":
            def fn(a, w=new_w, s=stride, act=attrs.get("act", "relu")):
                return dwconv2d(a, w, s, act=act)
        else:
            kern = qconv2d if op.kind == "qconv" else qdwconv2d
            def fn(a, kern=kern, w=new_w, at=dict(attrs)):
                return kern(a, w, at["stride"], at["mult"], at["zp_in"],
                            at["zp_out"], lo=at.get("lo"))
    new = Graph()
    for tname, t in graph.tensors.items():
        new.add_tensor(tname, t.size, t.shape, t.dtype)
    for o in graph.operators:
        if o.name == op.name:
            new.add_operator(o.name, list(o.inputs), o.output, kind=o.kind,
                             fn=fn, **attrs)
        else:
            new.add_operator(o.name, list(o.inputs), o.output, kind=o.kind,
                             fn=o.fn, **o.attrs)
    new.set_outputs(graph.outputs)
    return new


def grow_kernel(graph: Graph, op_name: str,
                new_k: Optional[int] = None) -> Graph:
    """Zero-embed ``op_name``'s kernel into a ``new_k``×``new_k`` one
    (default k+2).  Function-preserving — bit-identical outputs: the
    embedded taps read exactly the rows/cols the original taps read (the
    embed offset equals the SAME pad growth), and the new zero taps
    contribute exactly 0 to the accumulation."""
    op = _rf_op(graph, op_name)
    k, stride = op.attrs["k"], op.attrs["stride"]
    new_k = k + 2 if new_k is None else new_k
    if new_k < k:
        raise ValueError(f"grow_kernel: new_k {new_k} < k {k}")
    h_in, w_in = graph.tensors[op.inputs[0]].shape[:2]
    eh = same_pads(h_in, new_k, stride)[1] - same_pads(h_in, k, stride)[1]
    ew = same_pads(w_in, new_k, stride)[1] - same_pads(w_in, k, stride)[1]
    assert 0 <= eh <= new_k - k and 0 <= ew <= new_k - k, (eh, ew, k, new_k)
    wkey = "weight_q" if op.kind.startswith("q") else "weight"
    old_w = op.attrs.get(wkey)
    new_w = None
    if old_w is not None:
        new_w = np.zeros((new_k, new_k) + old_w.shape[2:], old_w.dtype)
        new_w[eh:eh + k, ew:ew + k] = old_w
    return _rf_rebuild(graph, op, new_w, new_k, "grow")


def shrink_kernel(graph: Graph, op_name: str) -> Graph:
    """Shrink ``op_name``'s kernel to its center tap (k -> 1).  A flagged
    MODEL EDIT (``attrs['rf_edit'] == 'shrink'``): outputs change, reach
    drops to 1, and the planner's halo/extra-MACs bill shrinks with it.
    Pairs with ``grow_kernel`` on a later op to conserve network reach."""
    op = _rf_op(graph, op_name)
    k, stride = op.attrs["k"], op.attrs["stride"]
    if k == 1:
        return graph
    h_in, w_in = graph.tensors[op.inputs[0]].shape[:2]
    # the tap that reads input row i*stride — what a 1x1 SAME kernel reads
    pb_h = same_pads(h_in, k, stride)[1]
    pb_w = same_pads(w_in, k, stride)[1]
    assert 0 <= pb_h < k and 0 <= pb_w < k, (pb_h, pb_w, k)
    wkey = "weight_q" if op.kind.startswith("q") else "weight"
    old_w = op.attrs.get(wkey)
    new_w = None
    if old_w is not None:
        new_w = np.ascontiguousarray(old_w[pb_h:pb_h + 1, pb_w:pb_w + 1])
    return _rf_rebuild(graph, op, new_w, 1, "shrink")


def redistribute_receptive_field(graph: Graph, shrink: str, grow: str,
                                 grow_k: Optional[int] = None) -> Graph:
    """The MCUNetV2-style planner option: move kernel reach from an early
    op (``shrink`` -> center tap) to a later one (``grow`` zero-embedded to
    ``grow_k``, default its k plus the reach the shrink dropped).  The
    result carries ``rf_edit`` flags on both ops; the grow leg alone is
    bit-identical, the pair is a model edit gated behind explicit opt-in."""
    s_op = _rf_op(graph, shrink)
    g_op = _rf_op(graph, grow)
    if grow_k is None:
        grow_k = g_op.attrs["k"] + max(0, s_op.attrs["k"] - 1)
    out = shrink_kernel(graph, shrink)
    return grow_kernel(out, grow, grow_k)


# ------------------------------------------------- compiled-executor lowering
# Rules for the compiled arena executor (mcu/compile.py) live next to the
# semantics they mirror.  Each rule rebuilds the op's computation from attrs
# (weight/k/stride, plus the explicit pads a partial-execution clone carries
# in ``pex_pads``), tracing the SAME jnp/lax calls the simulator fns run —
# so compiled outputs stay bit-identical to the interpreter.  The pointwise
# conv optionally routes through the Pallas fused conv+bias+relu kernel
# (different accumulation order: fast, not bit-stable — opt-in).  The
# quantized convs route through the fused int8 kernels under
# ``kernels/conv_quant/`` when ``use_pallas=True`` — those ARE bit-identical
# (int32 accumulation is exact and order-independent; see the kernel module
# docstring), so ``use_pallas`` costs no precision on int8 graphs.
from repro.mcu.compile import register_lowering


@register_lowering("conv")
def _lower_conv(ctx, op: Operator, x):
    w, stride = op.attrs["weight"], op.attrs["stride"]
    act = op.attrs.get("act", "relu")
    if (ctx.use_pallas and op.attrs.get("k", 1) == 1 and stride == 1
            and x.ndim == 3):
        from repro.kernels import conv1x1_fused
        y = conv1x1_fused(x, jnp.asarray(w)[0, 0], relu=act == "relu",
                          interpret=ctx.interpret)
        return _activate(y, act) if act == "relu6" else y
    return conv2d(x, w, stride, hpad=op.attrs.get("pex_pads"),
                  wpad=op.attrs.get("pex_wpads"), act=act)


@register_lowering("dwconv")
def _lower_dwconv(ctx, op: Operator, x):
    return dwconv2d(x, op.attrs["weight"], op.attrs["stride"],
                    hpad=op.attrs.get("pex_pads"),
                    wpad=op.attrs.get("pex_wpads"),
                    act=op.attrs.get("act", "relu"))


@register_lowering("maxpool")
def _lower_maxpool(ctx, op: Operator, x):
    return maxpool2d(x, op.attrs["k"], op.attrs["stride"],
                     hpad=op.attrs.get("pex_pads"),
                     wpad=op.attrs.get("pex_wpads"))


@register_lowering("add")
def _lower_add(ctx, op: Operator, x, y):
    return x + y


@register_lowering("qconv")
def _lower_qconv(ctx, op: Operator, x):
    a = op.attrs
    hpad, wpad = a.get("pex_pads"), a.get("pex_wpads")
    if ctx.use_pallas and x.ndim == 3:
        from repro.kernels import qconv_fused
        return qconv_fused(x, jnp.asarray(a["weight_q"]), stride=a["stride"],
                           mult=a["mult"], zp_in=a["zp_in"],
                           zp_out=a["zp_out"], lo=a.get("lo"),
                           hpad=None if hpad is None else tuple(hpad),
                           wpad=None if wpad is None else tuple(wpad),
                           interpret=ctx.interpret)
    return qconv2d(x, a["weight_q"], a["stride"], a["mult"], a["zp_in"],
                   a["zp_out"], hpad=hpad, wpad=wpad, lo=a.get("lo"))


@register_lowering("qdwconv")
def _lower_qdwconv(ctx, op: Operator, x):
    a = op.attrs
    hpad, wpad = a.get("pex_pads"), a.get("pex_wpads")
    if ctx.use_pallas and x.ndim == 3:
        from repro.kernels import qdwconv_fused
        return qdwconv_fused(x, jnp.asarray(a["weight_q"]),
                             stride=a["stride"], mult=a["mult"],
                             zp_in=a["zp_in"], zp_out=a["zp_out"],
                             lo=a.get("lo"),
                             hpad=None if hpad is None else tuple(hpad),
                             wpad=None if wpad is None else tuple(wpad),
                             interpret=ctx.interpret)
    return qdwconv2d(x, a["weight_q"], a["stride"], a["mult"], a["zp_in"],
                     a["zp_out"], hpad=hpad, wpad=wpad, lo=a.get("lo"))


@register_lowering("qmaxpool")
def _lower_qmaxpool(ctx, op: Operator, x):
    return qmaxpool2d(x, op.attrs["k"], op.attrs["stride"],
                      hpad=op.attrs.get("pex_pads"),
                      wpad=op.attrs.get("pex_wpads"))


@register_lowering("qadd")
def _lower_qadd(ctx, op: Operator, x, y):
    a = op.attrs
    if ctx.use_pallas:
        from repro.kernels import qadd_fused
        return qadd_fused(x, y, add_params=(a["mult_a"], a["mult_b"],
                                            a["zp_a"], a["zp_b"],
                                            a["zp_out"]),
                          interpret=ctx.interpret)
    return qadd(x, y, a["mult_a"], a["mult_b"], a["zp_a"], a["zp_b"],
                a["zp_out"])


@register_lowering("qavgpool")
def _lower_qavgpool(ctx, op: Operator, x):
    return qavgpool(x)


@register_lowering("qfc")
def _lower_qfc(ctx, op: Operator, x):
    a = op.attrs
    return qfc(x, a["weight_q"], a["mult"], a["zp_in"], a["zp_out"])


@register_lowering("qconcat")
def _lower_qconcat(ctx, op: Operator, *xs):
    a = op.attrs
    return qconcat(*xs, mults=a["mults"], zps=a["zps"], zp_out=a["zp_out"])


@register_lowering("quant")
def _lower_quant(ctx, op: Operator, x):
    return quantize_array(x, op.attrs["scale"], op.attrs["zp"])


@register_lowering("dequant")
def _lower_dequant(ctx, op: Operator, x):
    return dequantize_array(x, op.attrs["scale"], op.attrs["zp"])
