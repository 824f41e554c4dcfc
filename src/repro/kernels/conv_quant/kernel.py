"""Fused int8 conv/dwconv + requantize + ReLU Pallas kernels.

The quantized q-ops in ``graphs/cnn_ops.py`` lower to
``lax.conv_general_dilated`` over int32, which XLA CPU executes as a naive
convolution loop — the dominant cost of the compiled executor's warm path
(DESIGN.md §7).  These kernels recast each q-op as the int8 matmul /
shifted multiply-accumulate it really is and fuse the whole op — int32
accumulate, zero-point correction, round-half-even requantize,
zero-point-clamped ReLU — into one pass over output row tiles, so the int32
accumulator never round-trips through memory between the stages:

* ``qconv1x1_pallas`` — the MobileNet-dominant case: x viewed as
  (H·W, Cin) int8 against w (Cin, Cout), a 1-D grid over row blocks with
  one int8 × int8 → int32 MXU contraction per tile;
* ``qconv_pallas`` — general k×k/stride: each grid step owns one tile of
  output rows and accumulates k² (OW, Cin) @ (Cin, Cout) int8 contractions
  per output row;
* ``qdwconv_pallas`` — depthwise: k² elementwise int32 multiply-accumulates
  over the channel lane;
* ``qadd_pallas`` — the residual add's fixed-point requantize
  (``_qadd_replay``), elementwise, one block a lane.

Zero points.  The MXU takes int8 operands, so the convs never widen ``x``
to subtract ``zp_in`` first: they contract the raw int8 values and remove
the zero point afterwards as the exact int32 correction
``Σ(x − zp_in)·w = Σx·w − zp_in·Σw`` (``Σw`` per output channel, summed
over the taps and input channels in the wrapper).

Windows without strided or unaligned slices.  The wrapper materialises the
SAME/halo padding with ``zp_in``, splits the padded input into its stride
phases and column shifts — ``X[py, dx][R, c] = xp[R·s + py, c·s + dx]`` —
and cuts those into row tiles that carry their own halo rows.  Tap
``(dy, dx)`` of output row ``r`` is then the whole row ``r + dy // s`` of
plane ``(dy % s, dx)``: the kernel only indexes leading dimensions, which is
what Mosaic lowers on a TPU, and each grid step holds one row tile in VMEM,
never the whole input.

Numerics contract (unlike the f32 ``conv_pointwise`` kernel's float
tolerance): **bit-identical** to ``qconv2d``/``qdwconv2d``.  Integer
accumulation is exact and order-independent, so regrouping the convolution
into matmuls and moving the zero point out of the sum cannot change the
int32 sums; the fused requantize then applies literally the same
element-wise sequence as ``cnn_ops.requantize`` —
``round(acc.astype(f32) * f32(mult)) + zp_out``, clip to [zp_out, 127],
cast to int8 — and element-wise f32 ops are deterministic regardless of
fusion context.  Property-tested against the q-op semantics in
``tests/test_qkernels.py``.  Row padding up to the block size is dead
compute sliced off after.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

INT8_MAX = 127
INT8_MIN = -128

_MATMUL = (((1,), (0,)), ((), ()))     # (M, K) @ (K, N)


def _require_int8(name: str, arr) -> None:
    if arr.dtype != jnp.int8:
        raise TypeError(
            f"{name} must be int8 for the fused quantized kernels, got "
            f"{arr.dtype}; float convs go through conv_pointwise instead")


def _requant(acc, mult: float, zp_out: int, lo: int):
    # Must stay literally the element-wise sequence of cnn_ops.requantize:
    # any deviation (fma, different rounding) breaks the bit-identity
    # contract with the interpreter.
    y = jnp.round(acc.astype(jnp.float32) * jnp.float32(mult)) + zp_out
    return jnp.clip(y, lo, INT8_MAX).astype(jnp.int8)


def _imatmul(x, w):
    """int8 (M, K) @ int8 (K, N) -> exact int32 (M, N) on the MXU."""
    return lax.dot_general(x, w, _MATMUL, preferred_element_type=jnp.int32)


def _wsum(w, axes) -> jax.Array:
    """Σw per output channel as int32 (1, Cout): the zero-point term."""
    return jnp.sum(w.astype(jnp.int32), axis=axes).reshape(1, -1)


# add_params of the fused conv→add kernels, in cnn_ops.qadd argument order:
# (mult_a, mult_b, zp_a, zp_b, zp_out) where leg *a* is the conv's int8
# output and leg *b* the residual input.
AddParams = Tuple[float, float, int, int, int]

_QADD_SHIFT = 16    # must stay in lock-step with cnn_ops.QADD_SHIFT


def _qadd_replay(y, r, addp: AddParams):
    # Must stay literally the fixed-point sequence of cnn_ops.qadd: both
    # multipliers quantized to _QADD_SHIFT fractional bits at trace time,
    # int32 accumulate, integer round-half-even — integer ops cannot be
    # FMA-contracted, so this is bit-identical in every execution context
    # (no ReLU: the add has no fused activation in the q-graphs).
    mult_a, mult_b, zp_a, zp_b, zp_out = addp
    ma = int(round(float(mult_a) * (1 << _QADD_SHIFT)))
    mb = int(round(float(mult_b) * (1 << _QADD_SHIFT)))
    acc = ((y.astype(jnp.int32) - zp_a) * ma
           + (r.astype(jnp.int32) - zp_b) * mb)
    base = acc >> _QADD_SHIFT
    rem = acc - (base << _QADD_SHIFT)
    half = 1 << (_QADD_SHIFT - 1)
    z = jnp.where(rem > half, base + 1,
                  jnp.where(rem < half, base, base + (base & 1)))
    return jnp.clip(z + zp_out, INT8_MIN, INT8_MAX).astype(jnp.int8)


# ---------------------------------------------------------- residual add
def _qadd_kernel(a_ref, b_ref, o_ref, *, addp: AddParams):
    o_ref[...] = _qadd_replay(a_ref[...], b_ref[...], addp)


def qadd_pallas(a: jax.Array, b: jax.Array, *, add_params: AddParams,
                interpret: bool = False) -> jax.Array:
    """The int8 residual add ``cnn_ops.qadd`` as one kernel: a, b [..., C]
    int8 (same shape) -> int8, bit-identical.

    Elementwise, so the layout is free: the operands are viewed as
    lane-dense rows of 128 where their size allows and as (pixels, C)
    otherwise, and each lane of a batch is one whole block (a residual of
    a 512 KB arena is far below a core's VMEM)."""
    _require_int8("a", a)
    _require_int8("b", b)
    if a.shape != b.shape:
        raise ValueError(f"qadd operands differ in shape: {a.shape} vs "
                         f"{b.shape}")
    n = a.size
    view = (n // 128, 128) if n % 128 == 0 else (n // a.shape[-1],
                                                 a.shape[-1])
    out = pl.pallas_call(
        functools.partial(_qadd_kernel, addp=tuple(add_params)),
        out_shape=jax.ShapeDtypeStruct(view, jnp.int8),
        interpret=interpret,
        name="qadd",
    )(a.reshape(view), b.reshape(view))
    return out.reshape(a.shape)


# ------------------------------------------------------------- 1x1 pointwise
def _qconv1x1_kernel(x_ref, w_ref, ws_ref, *rest, mult: float, zp_in: int,
                     zp_out: int, lo: int, addp: Optional[AddParams]):
    acc = _imatmul(x_ref[...], w_ref[...]) - zp_in * ws_ref[...]
    y = _requant(acc, mult, zp_out, lo)
    if addp is None:
        (o_ref,) = rest
        o_ref[...] = y
    else:     # the conv's int8 output feeds the add without leaving VMEM
        r_ref, o_ref = rest
        o_ref[...] = _qadd_replay(y, r_ref[...], addp)


def _qconv1x1_call(x, w, r, *, mult: float, zp_in: int, zp_out: int,
                   lo: Optional[int], block_rows: int, interpret: bool,
                   addp: Optional[AddParams] = None) -> jax.Array:
    _require_int8("x", x)
    _require_int8("w", w)
    H, W, Cin = x.shape
    Cout = w.shape[1]
    M = H * W
    bm = min(block_rows, M)
    pad = (-M) % bm
    xm = x.reshape(M, Cin)
    if pad:     # zp_in rows: dead compute, sliced off below
        xm = jnp.concatenate(
            [xm, jnp.full((pad, Cin), zp_in, jnp.int8)], axis=0)
    operands = [xm, w, _wsum(w, 0)]
    in_specs = [pl.BlockSpec((bm, Cin), lambda i: (i, 0)),
                pl.BlockSpec((Cin, Cout), lambda i: (0, 0)),
                pl.BlockSpec((1, Cout), lambda i: (0, 0))]
    if r is not None:
        _require_int8("r", r)
        rm = r.reshape(M, Cout)
        if pad:
            rm = jnp.concatenate(
                [rm, jnp.zeros((pad, Cout), jnp.int8)], axis=0)
        operands.append(rm)
        in_specs.append(pl.BlockSpec((bm, Cout), lambda i: (i, 0)))
    out = pl.pallas_call(
        functools.partial(_qconv1x1_kernel, mult=mult, zp_in=zp_in,
                          zp_out=zp_out, lo=zp_out if lo is None else lo,
                          addp=None if addp is None else tuple(addp)),
        grid=((M + pad) // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, Cout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M + pad, Cout), jnp.int8),
        interpret=interpret,
        name="qconv1x1" if r is None else "qconv1x1_add",
    )(*operands)
    return out[:M].reshape(H, W, Cout)


def qconv1x1_pallas(x: jax.Array, w: jax.Array, *, mult: float, zp_in: int,
                    zp_out: int, lo: Optional[int] = None,
                    block_rows: int = 256,
                    interpret: bool = False) -> jax.Array:
    """x [H,W,Cin] int8; w [Cin,Cout] int8 -> [H,W,Cout] int8.

    The stride-1 1×1 case of ``qconv2d`` (no spatial window, no padding):
    one fused int8 matmul + requantize over (H·W, Cin) row tiles.  ``lo``
    is the lower clamp (default ``zp_out``: fused ReLU, as in ``qconv2d``).
    """
    return _qconv1x1_call(x, w, None, mult=mult, zp_in=zp_in,
                          zp_out=zp_out, lo=lo, block_rows=block_rows,
                          interpret=interpret)


def qconv1x1_add_pallas(x: jax.Array, w: jax.Array, r: jax.Array, *,
                        mult: float, zp_in: int, zp_out: int,
                        add_params: AddParams, lo: Optional[int] = None,
                        block_rows: int = 256,
                        interpret: bool = False) -> jax.Array:
    """Fused ``qconv2d(1x1) -> qadd`` in one pass: x [H,W,Cin] int8 against
    w [Cin,Cout] plus residual r [H,W,Cout] int8 -> [H,W,Cout] int8.

    The conv's requantized int8 tile feeds the add's requantize without a
    memory round-trip.  Bit-identical to running the two q-ops back to
    back (both requantize sequences are replayed literally).
    """
    return _qconv1x1_call(x, w, r, mult=mult, zp_in=zp_in, zp_out=zp_out,
                          lo=lo, block_rows=block_rows, interpret=interpret,
                          addp=add_params)


# ------------------------------------------------------- k×k conv / dwconv
def _row_tiles(x, k: int, stride: int, hpad: Tuple[int, int],
               wpad: Tuple[int, int], zp_in: int, ow: int, bm: int,
               nblk: int) -> jax.Array:
    """int8 [nblk, stride·k, bm + (k-1)//stride, ow, C]: tile ``i``, plane
    ``py·k + dx``, row ``j``, column ``c`` holds padded input element
    ``(i·bm + j)·stride + py, c·stride + dx``.  Padding (SAME/halo, and the
    bottom rows that only feed dead output rows of the last tile) is
    ``zp_in``, which the zero-point correction maps to 0."""
    s = stride
    hq = nblk * bm + (k - 1) // s            # phase rows any tile reaches
    H, W, _ = x.shape
    bottom = max(hq * s + s - (H + hpad[0]), 0)
    right = max((ow - 1) * s + k - (W + wpad[0]), 0)
    xp = jnp.pad(x, ((hpad[0], bottom), (wpad[0], right), (0, 0)),
                 constant_values=jnp.int8(zp_in))
    planes = jnp.stack([
        xp[py:py + (hq - 1) * s + 1:s, dx:dx + (ow - 1) * s + 1:s]
        for py in range(s) for dx in range(k)])  # [s·k, hq, ow, C]
    rows = (jnp.arange(nblk)[:, None] * bm
            + jnp.arange(bm + (k - 1) // s)[None, :])
    return jnp.moveaxis(planes[:, rows], 1, 0)


def _conv_taps(x_ref, w_ref, r: int, *, k: int, stride: int):
    """int32 Σx·w of output row ``r`` over the k² taps (raw int8 x)."""
    acc = None
    for dy in range(k):
        for dx in range(k):
            xw = x_ref[(dy % stride) * k + dx, r + dy // stride]  # [OW, Cin]
            t = _imatmul(xw, w_ref[dy, dx])                       # [OW, Cout]
            acc = t if acc is None else acc + t
    return acc


def _qconv_kernel(x_ref, w_ref, ws_ref, *rest, k: int, stride: int,
                  mult: float, zp_in: int, zp_out: int, lo: int, bm: int,
                  addp: Optional[AddParams] = None):
    o_ref = rest[-1]
    corr = zp_in * ws_ref[...]                        # [1, Cout]

    def row(r, carry):
        acc = _conv_taps(x_ref, w_ref, r, k=k, stride=stride) - corr
        y = _requant(acc, mult, zp_out, lo)
        if addp is not None:          # rest = (r_ref, o_ref)
            y = _qadd_replay(y, rest[0][r], addp)
        o_ref[r] = y
        return carry

    lax.fori_loop(0, bm, row, 0)


def _qdwconv_kernel(x_ref, w_ref, o_ref, *, k: int, stride: int,
                    mult: float, zp_in: int, zp_out: int, lo: int, bm: int):
    def row(r, carry):
        acc = None
        for dy in range(k):
            for dx in range(k):
                xw = x_ref[(dy % stride) * k + dx, r + dy // stride]
                t = ((xw.astype(jnp.int32) - zp_in)        # [OW, C]
                     * w_ref[dy, dx].astype(jnp.int32))    # [1, C]
                acc = t if acc is None else acc + t
        o_ref[r] = _requant(acc, mult, zp_out, lo)
        return carry

    lax.fori_loop(0, bm, row, 0)


def _windowed_call(name: str, kernel_body, x, w, cout: int, *, k: int,
                   stride: int,
                   mult: float, zp_in: int, zp_out: int, lo: int,
                   hpad: Tuple[int, int], wpad: Tuple[int, int],
                   block_rows: int, interpret: bool,
                   wsum: Optional[jax.Array] = None,
                   residual: Optional[jax.Array] = None,
                   addp: Optional[AddParams] = None) -> jax.Array:
    H, W, _ = x.shape
    oh = (H + hpad[0] + hpad[1] - k) // stride + 1
    ow = (W + wpad[0] + wpad[1] - k) // stride + 1
    bm = min(block_rows, oh)
    nblk = -(-oh // bm)
    xt = _row_tiles(x, k, stride, hpad, wpad, zp_in, ow, bm, nblk)
    tile = xt.shape[1:]
    operands = [xt, w]
    in_specs = [pl.BlockSpec((None,) + tile, lambda i: (i, 0, 0, 0, 0)),
                pl.BlockSpec(w.shape, lambda i: (0,) * w.ndim)]
    extra = {}
    if wsum is not None:
        operands.append(wsum)
        in_specs.append(pl.BlockSpec((1, cout), lambda i: (0, 0)))
    if residual is not None:
        _require_int8("r", residual)
        # residual rows pad to the block grid (dead compute, sliced off)
        operands.append(jnp.pad(residual,
                                ((0, nblk * bm - oh), (0, 0), (0, 0))))
        in_specs.append(pl.BlockSpec((bm, ow, cout), lambda i: (i, 0, 0)))
        extra["addp"] = tuple(addp)
    out = pl.pallas_call(
        functools.partial(kernel_body, k=k, stride=stride, mult=mult,
                          zp_in=zp_in, zp_out=zp_out, lo=lo, bm=bm,
                          **extra),
        grid=(nblk,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, ow, cout), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk * bm, ow, cout), jnp.int8),
        interpret=interpret,
        name=name,
    )(*operands)
    return out[:oh]


def qconv_pallas(x: jax.Array, w: jax.Array, *, stride: int, mult: float,
                 zp_in: int, zp_out: int, lo: Optional[int] = None,
                 hpad: Optional[Tuple[int, int]] = None,
                 wpad: Tuple[int, int] = (0, 0),
                 block_rows: int = 32, interpret: bool = False) -> jax.Array:
    """x [H,W,Cin] int8; w [k,k,Cin,Cout] int8 -> [OH,OW,Cout] int8.

    General k×k/stride quantized conv with fused requantize + ReLU
    (``lo`` defaults to ``zp_out``).  ``hpad``/``wpad`` are the explicit
    (before, after) paddings — pass the SAME pads for a whole op, a Pex
    slice's halo pads for a partial run.  Bit-identical to ``qconv2d``.
    """
    return qconv_add_pallas(x, w, None, stride=stride, mult=mult,
                            zp_in=zp_in, zp_out=zp_out, add_params=None,
                            lo=lo, hpad=hpad, wpad=wpad,
                            block_rows=block_rows, interpret=interpret)


def qconv_add_pallas(x: jax.Array, w: jax.Array, r: Optional[jax.Array], *,
                     stride: int, mult: float, zp_in: int, zp_out: int,
                     add_params: Optional[AddParams],
                     lo: Optional[int] = None,
                     hpad: Optional[Tuple[int, int]] = None,
                     wpad: Tuple[int, int] = (0, 0),
                     block_rows: int = 32,
                     interpret: bool = False) -> jax.Array:
    """Fused ``qconv2d -> qadd``: x [H,W,Cin] int8, w [k,k,Cin,Cout] int8,
    residual r [OH,OW,Cout] int8 -> [OH,OW,Cout] int8.

    General k×k/stride twin of ``qconv1x1_add_pallas``: the conv row's
    requantized int8 values feed the add's requantize in the same grid
    step.  Bit-identical to the two q-ops run separately.
    """
    _require_int8("x", x)
    _require_int8("w", w)
    k = w.shape[0]
    return _windowed_call(
        "qconv" if r is None else "qconv_add", _qconv_kernel, x, w,
        w.shape[3], k=k, stride=stride, mult=mult,
        zp_in=zp_in, zp_out=zp_out, lo=zp_out if lo is None else lo,
        hpad=(0, 0) if hpad is None else tuple(hpad), wpad=tuple(wpad),
        block_rows=block_rows, interpret=interpret,
        wsum=_wsum(w, (0, 1, 2)), residual=r, addp=add_params)


def qdwconv_pallas(x: jax.Array, w: jax.Array, *, stride: int, mult: float,
                   zp_in: int, zp_out: int, lo: Optional[int] = None,
                   hpad: Optional[Tuple[int, int]] = None,
                   wpad: Tuple[int, int] = (0, 0),
                   block_rows: int = 32,
                   interpret: bool = False) -> jax.Array:
    """x [H,W,C] int8; w [k,k,C] int8 -> [OH,OW,C] int8 (depthwise)."""
    _require_int8("x", x)
    _require_int8("w", w)
    k = w.shape[0]
    return _windowed_call(
        "qdwconv", _qdwconv_kernel, x, w.reshape(k, k, 1, -1), w.shape[2],
        k=k,
        stride=stride, mult=mult, zp_in=zp_in, zp_out=zp_out,
        lo=zp_out if lo is None else lo,
        hpad=(0, 0) if hpad is None else tuple(hpad), wpad=tuple(wpad),
        block_rows=block_rows, interpret=interpret)
