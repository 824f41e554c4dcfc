"""MobileNet-v2's mechanisms: fused activations (ReLU, ReLU6, linear) and
their int8 lower clamps, the Pallas residual add, and the published
network deployed under an SRAM budget that forces partial execution.

Every int8 comparison is exact (``assert_array_equal``); the Pallas
kernels run in interpret mode on the CPU."""
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.deploy as deploy
from repro.core import schedule
from repro.core.graph import Graph
from repro.graphs import (int8_scheduling_graph, mobilenet_v1_graph,
                          mobilenet_v2_graph, quantize_graph, random_input)
from repro.graphs.cnn_ops import (CNNBuilder, INT8_MIN, activation_lo,
                                  conv2d, dwconv2d, qadd, qconv2d, qdwconv2d)
from repro.graphs.mobilenet_v2 import make_divisible
from repro.kernels import qadd_fused, qconv_fused, qdwconv_fused
from repro.mcu import MicroInterpreter


def qrand(rng, shape):
    return jnp.asarray(rng.integers(-128, 128, size=shape, dtype=np.int8))


# ------------------------------------------------------------------ graph
def test_published_block_table():
    g = mobilenet_v2_graph(1.0, 224, 1001)
    kinds = {}
    for op in g.operators:
        kinds[op.kind] = kinds.get(op.kind, 0) + 1
    # stem + 16 expansions + 17 projections + last conv; 17 depthwise;
    # 10 residual adds (n - 1 per stage)
    assert kinds == {"conv": 35, "dwconv": 17, "add": 10, "avgpool": 1,
                     "fc": 1}
    acts = [op.attrs["act"] for op in g.operators if op.kind == "conv"]
    assert acts[0] == "relu6" and acts[-1] == "relu6"
    assert acts[1:-1].count("none") == 17        # every projection linear
    assert all(op.attrs["act"] == "relu6" for op in g.operators
               if op.kind == "dwconv")
    assert g.tensors[g.outputs[0]].shape == (1, 1, 1001)
    # each add joins a projection with its block's input, same shape
    for op in g.operators:
        if op.kind == "add":
            a, b = (g.tensors[t].shape for t in op.inputs)
            assert a == b
            assert g.producer(op.inputs[0]).attrs["act"] == "none"


@pytest.mark.parametrize("v,want", [(32 * 0.35, 16), (16 * 0.35, 8),
                                    (24 * 0.5, 16), (96 * 1.0, 96),
                                    (1280 * 0.35, 448)])
def test_make_divisible_is_tf_slims_rule(v, want):
    assert make_divisible(v) == want


def test_int8_peaks_at_the_512k_rung():
    """The CPU probe behind the 512 KB cell: reordering alone needs
    1,505,280 B; under 524,288 B partial execution through the expansion
    chains fits 344,064 B, exactly as planned and as live."""
    gi = int8_scheduling_graph(mobilenet_v2_graph(1.0, 224, 1001))
    assert schedule(gi).peak == 1505280
    res = schedule(gi, arena_budget=524288)
    assert res.method == "default+pex"
    assert res.peak == 344064 and len(res.schedule) == 841
    assert res.graph.peak_usage(res.schedule) == 344064
    assert 0.32 < res.extra_macs_frac < 0.33


# ------------------------------------------------------------ activations
def test_float_activations():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((6, 6, 4)).astype(np.float32) * 10
    w = rng.standard_normal((1, 1, 4, 8)).astype(np.float32)
    lin = np.asarray(conv2d(x, w, 1, act="none"))
    assert lin.min() < 0 and lin.max() > 6
    np.testing.assert_array_equal(conv2d(x, w, 1), np.maximum(lin, 0))
    np.testing.assert_array_equal(conv2d(x, w, 1, act="relu6"),
                                  np.clip(lin, 0, 6))
    wd = rng.standard_normal((3, 3, 4, 1)).astype(np.float32)
    dlin = np.asarray(dwconv2d(x, wd, 1, act="none"))
    np.testing.assert_array_equal(dwconv2d(x, wd, 1, act="relu6"),
                                  np.clip(dlin, 0, 6))
    with pytest.raises(ValueError, match="activation"):
        conv2d(x, w, 1, act="gelu")


def _three_acts_graph():
    g = Graph()
    b = CNNBuilder(g)
    x = b.input("input", 8, 8, 3)
    x = b.conv(x, 8, k=3, act="relu")
    x = b.dwconv(x, act="relu6")
    x = b.conv(x, 8, k=1, act="none")
    g.set_outputs([x])
    return g


def test_act_becomes_the_int8_lower_clamp():
    qm = quantize_graph(_three_acts_graph())
    ops = {op.attrs["act"]: op for op in qm.graph.operators}
    assert ops["relu"].attrs["lo"] == ops["relu"].attrs["zp_out"]
    assert ops["relu6"].attrs["lo"] == ops["relu6"].attrs["zp_out"]
    assert ops["none"].attrs["lo"] == INT8_MIN
    # a linear output keeps negative reals: its zero point sits above -128
    assert ops["none"].attrs["zp_out"] > INT8_MIN
    assert activation_lo("relu", 5) == activation_lo("relu6", 5) == 5
    assert activation_lo("none", 5) == INT8_MIN
    x = random_input(qm.float_graph, 1)
    out = MicroInterpreter(qm.graph).run(qm.quantize_inputs(x)).outputs
    assert np.asarray(out[qm.graph.outputs[0]]).min() < \
        ops["none"].attrs["zp_out"]


def test_relu6_needs_no_upper_clamp():
    """Calibrated through the clip at 6, every ReLU6 output has zero point
    -128 and scale at most 6/255, so real 6 lies at or above 127."""
    qm = quantize_graph(mobilenet_v2_graph(0.35, 32, 10))
    relu6 = [op for op in qm.graph.operators
             if op.kind in ("qconv", "qdwconv")
             and op.attrs["act"] == "relu6"]
    assert len(relu6) == 35     # stem, 16 expansions, 17 dw, last conv
    for op in relu6:
        qp = qm.qparams[op.output]
        assert qp.zero_point == -128 and qp.scale <= 6 / 255 + 1e-12
        assert qp.zero_point + round(6 / qp.scale) >= 127, op.name


# ---------------------------------------------------------------- kernels
@pytest.mark.parametrize("shape", [(8, 8, 16), (7, 7, 24), (3, 5, 160),
                                   (1, 56, 24)])
def test_pallas_qadd_bit_identical(shape):
    rng = np.random.default_rng(sum(shape))
    a, b = qrand(rng, shape), qrand(rng, shape)
    for params in [(0.71, 0.39, -5, 2, -7), (1.0, 1.0, 0, 0, 0),
                   (0.5, 0.5, -128, -128, -128), (1.37, 0.0625, 17, -3, 9)]:
        got = qadd_fused(a, b, add_params=params, interpret=True)
        want = qadd(a, b, *params)
        assert got.dtype == jnp.int8 and got.shape == shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_qadd_under_vmap():
    rng = np.random.default_rng(3)
    a, b = qrand(rng, (4, 7, 7, 24)), qrand(rng, (4, 7, 7, 24))
    p = (0.83, 0.41, 3, -9, -2)
    got = jax.vmap(lambda x, y: qadd_fused(x, y, add_params=p,
                                           interpret=True))(a, b)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(qadd(a, b, *p)))


_QP = dict(mult=0.0123, zp_in=3, zp_out=-5)


@pytest.mark.parametrize("k,stride,hpad", [(1, 1, None), (3, 2, None),
                                           (3, 1, (0, 1))])
def test_linear_qconv_fused_bit_identical(k, stride, hpad):
    rng = np.random.default_rng(k + stride)
    x, w = qrand(rng, (9, 8, 16)), qrand(rng, (k, k, 16, 24))
    got = qconv_fused(x, w, stride=stride, lo=INT8_MIN, hpad=hpad,
                      interpret=True, **_QP)
    want = qconv2d(x, w, stride, _QP["mult"], _QP["zp_in"], _QP["zp_out"],
                   hpad=hpad, lo=INT8_MIN)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    relu = qconv2d(x, w, stride, _QP["mult"], _QP["zp_in"], _QP["zp_out"],
                   hpad=hpad)
    assert np.asarray(want).min() < _QP["zp_out"] <= np.asarray(relu).min()


@pytest.mark.parametrize("stride,hpad", [(1, None), (2, None), (1, (1, 0))])
def test_linear_qdwconv_fused_bit_identical(stride, hpad):
    rng = np.random.default_rng(10 + stride)
    x, w = qrand(rng, (9, 7, 24)), qrand(rng, (3, 3, 24, 1))
    got = qdwconv_fused(x, w, stride=stride, lo=INT8_MIN, hpad=hpad,
                        interpret=True, **_QP)
    want = qdwconv2d(x, w, stride, _QP["mult"], _QP["zp_in"],
                     _QP["zp_out"], hpad=hpad, lo=INT8_MIN)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(want).min() < _QP["zp_out"]


# ------------------------------------------------------------- deployment
@pytest.fixture(scope="module")
def small_v2():
    """MobileNet-v2 0.35@32, 10 classes, under half its reorder-only
    arena: the ladder slices the expansion chains (``+pex``)."""
    g = mobilenet_v2_graph(0.35, 32, 10)
    events = []

    def listen(event, value, **kw):
        if event.startswith("/repro/deploy/"):
            events.append((event, value))
    jax.monitoring.register_scalar_listener(listen)
    try:
        d = deploy.build(g, quantize=True, arena_budget=7680,
                         use_pallas=True, strict=True)
    finally:
        jax.monitoring.unregister_scalar_listener(listen)
    return g, d, events


def test_budgeted_v2_deployment_is_exact(small_v2):
    g, d, events = small_v2
    res = d.schedule_result
    assert res.method.endswith("+pex") and not d.degraded
    assert res.peak == d.exec_graph.peak_usage(d.schedule) == d.arena_bytes
    assert d.arena_bytes <= 7680
    assert sum(op.kind == "qadd" for op in d.schedule) == 10
    assert events == [("/repro/deploy/extra_macs_frac",
                       res.extra_macs_frac)]
    assert res.extra_macs_frac > 0.1


def test_budgeted_v2_matches_the_interpreter(small_v2):
    g, d, _ = small_v2
    out = g.outputs[0]
    for seed in (1, 2):
        xq = d.quantize_inputs(random_input(g, seed))
        got = np.asarray(d.run(xq)[out])
        want = np.asarray(MicroInterpreter(d.qmodel.graph).run(xq)
                          .outputs[out])
        np.testing.assert_array_equal(got, want)
        assert len(np.unique(got)) > 3          # logits, not saturation


# ------------------------------------------------ MobileNet-v1 untouched
# sha-256 of the lowered batched arena program (4 lanes, Pallas kernels in
# interpret mode) of MobileNet-v1 0.25@96 int8, recorded before
# activations and the lower clamp reached the lowerings: an all-ReLU graph
# must lower to the same program.
V1_PROGRAM_SHA256 = {
    None: (55296, 29, "6802ea8ae867d3574825c2422d8ec8af14a24d64183842a3"
                      "970cbd69a818bde3"),
    40 * 1024: (37224, 1713, "6da9da37f97e4f237a36f0b3d9e8190bd581b4400746"
                             "f7f2b87e9cb249ef2fb3"),
}
V1_OP_KINDS = {
    None: {"qconv": 14, "qdwconv": 13, "qavgpool": 1, "qfc": 1},
    40 * 1024: {"pex_concat": 36, "pex_ring_push": 432, "pex_ring_read": 324,
                "pex_slice": 144, "qavgpool": 1, "qconv": 442,
                "qdwconv": 333, "qfc": 1},
}


@pytest.mark.parametrize("budget", list(V1_PROGRAM_SHA256),
                         ids=["whole", "40k"])
def test_mobilenet_v1_lowers_to_the_same_program(budget):
    d = deploy.build(mobilenet_v1_graph(0.25, 96), quantize=True,
                     arena_budget=budget, use_pallas=True, strict=True)
    ex = d.executor
    lanes = jax.ShapeDtypeStruct((4, ex.arena_size), jnp.uint8)
    txt = ex.batched_fn().jitted.lower(lanes).as_text()
    arena, steps, digest = V1_PROGRAM_SHA256[budget]
    assert (d.arena_bytes, len(d.schedule)) == (arena, steps)
    assert hashlib.sha256(txt.encode()).hexdigest() == digest
    kinds = {}
    for op in d.schedule:
        kinds[op.kind] = kinds.get(op.kind, 0) + 1
    assert kinds == V1_OP_KINDS[budget]
    assert all(op.attrs["lo"] == op.attrs["zp_out"] for op in d.schedule
               if op.kind in ("qconv", "qdwconv"))


def test_v2_macs_are_the_published_300m():
    g = mobilenet_v2_graph(1.0, 224, 1001)
    macs = 0
    for op in g.operators:
        oh, ow, oc = g.tensors[op.output].shape
        if op.kind == "conv":
            cin = g.tensors[op.inputs[0]].shape[-1]
            macs += oh * ow * oc * op.attrs["k"] ** 2 * cin
        elif op.kind == "dwconv":
            macs += oh * ow * oc * op.attrs["k"] ** 2
        elif op.kind == "fc":
            macs += math.prod(g.tensors[op.inputs[0]].shape) * oc
    assert macs == 300775552                 # "300M" in Table 3
