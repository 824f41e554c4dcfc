"""Fused int8 kernels vs the q-op reference semantics: differential
bit-identity grids in interpret mode, plus the compiled executor end-to-end
with ``use_pallas=True`` and zero-copy ring reads.

Unlike the float conv kernel (tolerance-checked: f32 accumulation order
differs), every assertion here is ``assert_array_equal``: int32 accumulation
of int8 products is exact and order-independent, and the kernels replay the
reference requantize sequence literally — so the fused path must cost zero
ULPs, on every shape, stride and padding the MCU graphs produce."""
from types import SimpleNamespace

import numpy as np
import pytest

import jax.numpy as jnp

import jax

from repro.core import ArenaPlanner, schedule
from repro.core.graph import Graph
from repro.core.partition import cascade_graph
from repro.graphs import quantize_graph, random_input
from repro.graphs.cnn_ops import CNNBuilder, qadd, qconv2d, qdwconv2d
from repro.kernels import qconv_add_fused, qconv_fused, qdwconv_fused
from repro.mcu import MicroInterpreter, compile_schedule
from repro.mcu.compile import resolve_interpret


def qrand(rng, shape):
    return jnp.asarray(rng.integers(-128, 128, size=shape, dtype=np.int8))


# --------------------------------------------------------- differential grids
# The first two cases per kernel are the fast-tier smoke; the rest run in the
# slow tier.  Deliberately hostile shapes: odd H/W (ragged row blocks),
# 1-lane channels, stride 2, asymmetric ``hpad`` overrides (a Pex slice's
# zp-padded halo), and tiny ``block_rows`` so the grid always has several
# steps with a ragged tail.
_CONV_GRID = [
    # H, W, Cin, Cout, k, stride, hpad, block_rows
    (12, 12, 8, 16, 1, 1, None, 40),          # 1x1 fast path, ragged blocks
    (11, 9, 4, 6, 3, 2, None, 2),             # odd shape, stride 2
    pytest.param(7, 9, 1, 5, 3, 1, None, 4, marks=pytest.mark.slow),
    pytest.param(10, 8, 3, 7, 3, 1, (0, 2), 4,       # Pex mid-slice pads
                 marks=pytest.mark.slow),
    pytest.param(9, 7, 5, 1, 3, 2, (2, 0), 4,        # 1-lane Cout, top halo
                 marks=pytest.mark.slow),
    pytest.param(8, 8, 3, 4, 5, 2, None, 3, marks=pytest.mark.slow),  # k=5
]

_DW_GRID = [
    # H, W, C, k, stride, hpad, block_rows
    (11, 9, 8, 3, 1, None, 4),                # odd shape
    (12, 10, 6, 3, 2, None, 2),               # stride 2
    pytest.param(9, 7, 1, 3, 1, None, 4, marks=pytest.mark.slow),  # 1 lane
    pytest.param(10, 8, 5, 3, 1, (2, 0), 4, marks=pytest.mark.slow),
    pytest.param(9, 9, 4, 3, 2, (0, 2), 3, marks=pytest.mark.slow),
]

# Non-trivial quantization params: fractional multiplier exercising
# round-half-even, off-zero input/output zero-points (so halo padding and
# the fused ReLU clamp are both off the integer origin).
_QP = dict(mult=0.0123, zp_in=3, zp_out=-5)


@pytest.mark.parametrize("H,W,Cin,Cout,k,stride,hpad,block_rows", _CONV_GRID)
def test_qconv_fused_bit_identical(H, W, Cin, Cout, k, stride, hpad,
                                   block_rows):
    rng = np.random.default_rng(11)
    x = qrand(rng, (H, W, Cin))
    w = qrand(rng, (k, k, Cin, Cout))
    got = qconv_fused(x, w, stride=stride, hpad=hpad,
                      block_rows=block_rows, interpret=True, **_QP)
    want = qconv2d(x, w, stride, _QP["mult"], _QP["zp_in"], _QP["zp_out"],
                   hpad=hpad)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("H,W,C,k,stride,hpad,block_rows", _DW_GRID)
def test_qdwconv_fused_bit_identical(H, W, C, k, stride, hpad, block_rows):
    rng = np.random.default_rng(13)
    x = qrand(rng, (H, W, C))
    w = qrand(rng, (k, k, C, 1))
    got = qdwconv_fused(x, w, stride=stride, hpad=hpad,
                        block_rows=block_rows, interpret=True, **_QP)
    want = qdwconv2d(x, w, stride, _QP["mult"], _QP["zp_in"], _QP["zp_out"],
                     hpad=hpad)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# Residual-leg quantization params for the fused conv->add chain, in the
# ``qadd`` argument order (mult_a, mult_b, zp_a, zp_b, zp_out); leg *a* is
# the conv's output, so its zero-point is the conv's ``zp_out``.
_ADDP = (0.71, 0.39, _QP["zp_out"], 2, -7)

_CONV_ADD_GRID = [
    # H, W, Cin, Cout, k, stride, hpad, block_rows
    (12, 12, 8, 16, 1, 1, None, 40),          # 1x1 fast path, ragged blocks
    (11, 9, 4, 6, 3, 2, None, 2),             # odd shape, stride 2
    pytest.param(10, 8, 3, 7, 3, 1, (0, 2), 4,       # Pex mid-slice pads
                 marks=pytest.mark.slow),
    pytest.param(9, 7, 5, 1, 3, 2, (2, 0), 4,        # 1-lane Cout, top halo
                 marks=pytest.mark.slow),
]


@pytest.mark.parametrize("H,W,Cin,Cout,k,stride,hpad,block_rows",
                         _CONV_ADD_GRID)
def test_qconv_add_fused_bit_identical(H, W, Cin, Cout, k, stride, hpad,
                                       block_rows):
    """Fused conv->add (residual requant folded into the conv kernel's
    epilogue) vs the two-op reference chain ``qconv2d -> qadd``: the
    intermediate conv output never leaves VMEM, yet every element must
    match bit-for-bit."""
    rng = np.random.default_rng(7)
    x = qrand(rng, (H, W, Cin))
    w = qrand(rng, (k, k, Cin, Cout))
    want_conv = qconv2d(x, w, stride, _QP["mult"], _QP["zp_in"],
                        _QP["zp_out"], hpad=hpad)
    r = qrand(rng, want_conv.shape)
    want = qadd(want_conv, r, *_ADDP)
    got = qconv_add_fused(x, w, r, stride=stride, hpad=hpad,
                          add_params=_ADDP, block_rows=block_rows,
                          interpret=True, **_QP)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qadd_fixed_point_jit_invariant():
    """``qadd`` must produce the same bits eager and jitted.  The
    fixed-point formulation exists precisely for this: with f32
    multipliers XLA's CPU codegen contracts the mul->add into a
    single-rounded FMA under jit (and ``optimization_barrier`` does not
    survive codegen), silently changing results vs eager — integer
    arithmetic cannot contract."""
    rng = np.random.default_rng(23)
    a = qrand(rng, (9, 11, 6))
    b = qrand(rng, (9, 11, 6))
    args = (0.37, 0.61, 3, -2, 5)
    eager = qadd(a, b, *args)
    jitted = jax.jit(qadd, static_argnums=(2, 3, 4, 5, 6))(a, b, *args)
    assert eager.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))


def test_qconv_fused_saturates_both_rails():
    """Extreme multiplier: outputs must pin to the int8 rails (the ReLU
    clamp floor is ``zp_out``, the ceiling INT8_MAX), never wrap."""
    rng = np.random.default_rng(17)
    x = qrand(rng, (6, 6, 4))
    w = qrand(rng, (3, 3, 4, 8))
    got = np.asarray(qconv_fused(x, w, stride=1, mult=1.0, zp_in=0,
                                 zp_out=-5, interpret=True))
    want = np.asarray(qconv2d(x, w, 1, 1.0, 0, -5))
    np.testing.assert_array_equal(got, want)
    assert got.min() >= -5 and got.max() <= 127
    assert (got == -5).any() and (got == 127).any()


# ------------------------------------------------------------- end-to-end
def _chain_cnn() -> Graph:
    """A small sequential CNN (cascadable chain) mixing every fused-kernel
    shape: k=3 conv, depthwise (stride 1 and 2), 1x1 pointwise."""
    g = Graph()
    b = CNNBuilder(g)
    x = b.input("input", 20, 20, 4)
    x = b.conv(x, 8, k=3)
    x = b.dwconv(x, k=3)
    x = b.conv(x, 12, k=1)
    x = b.dwconv(x, k=3, stride=2)
    x = b.conv(x, 8, k=1)
    g.set_outputs([x])
    return g


def test_compiled_use_pallas_bit_identical_e2e():
    """The compiled executor with ``use_pallas=True`` routes every int8
    conv through the fused kernels and must match the interpreter
    bit-for-bit — the acceptance gate for swapping the kernels in."""
    g = _chain_cnn()
    gq = quantize_graph(g, random_input(g)).graph
    sched = schedule(gq).schedule
    plan = ArenaPlanner.plan(gq, sched)
    x = random_input(gq)
    ref = MicroInterpreter(gq).run(x, schedule=sched)
    ex = compile_schedule(gq, sched, plan, use_pallas=True, interpret=True)
    out = ex.run(x)
    for o in gq.outputs:
        np.testing.assert_array_equal(ref.outputs[o], out[o])
    assert ex.arena_size == plan.arena_size    # kernels change no placement


def test_zero_copy_ring_reads_bit_identical():
    """Cascade ring reads fuse into their consumers (no arena round-trip):
    the zero-copy executor must count fused reads, keep the arena plan
    byte-identical, and agree bit-for-bit with the interpreter and with
    the copying executor — with and without the fused kernels."""
    g = _chain_cnn()
    gq = quantize_graph(g, random_input(g)).graph
    peak = gq.peak_usage(gq.default_schedule())
    cr = cascade_graph(gq, budget=int(peak * 0.6))
    assert cr.cascades, "chain must cascade under a 0.6x budget"
    gp = cr.graph
    sched = gp.default_schedule()
    plan = ArenaPlanner.plan(gp, sched)
    x = random_input(gq)
    ref = MicroInterpreter(gp).run(x, schedule=sched)
    copying = compile_schedule(gp, sched, plan, zero_copy_rings=False)
    assert copying.zero_copy_reads == 0
    for use_pallas in (False, True):
        ex = compile_schedule(gp, sched, plan, use_pallas=use_pallas,
                              interpret=True)
        assert ex.zero_copy_reads > 0
        assert ex.arena_size == plan.arena_size
        out = ex.run(x)
        for o in gp.outputs:
            np.testing.assert_array_equal(ref.outputs[o], out[o])
            np.testing.assert_array_equal(copying.run(x)[o], out[o])


def test_interpret_mode_is_resolved_once_from_the_device():
    """Mosaic for a TPU program, the interpreter elsewhere; asking for
    interpret-mode kernels on a TPU is an error, never a silent fallback.
    The executor records the decision."""
    cpu = jax.devices("cpu")[0]
    tpu = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert resolve_interpret(tpu) is False
    assert resolve_interpret(tpu, False) is False
    assert resolve_interpret(cpu) is True
    assert resolve_interpret(cpu, False) is False
    with pytest.raises(ValueError, match="interpret-mode"):
        resolve_interpret(tpu, True)
    ex = compile_schedule(_chain_cnn(), use_pallas=True, device=cpu)
    assert ex.interpret is True and ex.device == cpu
