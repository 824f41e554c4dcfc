"""Operation and byte counts against hand-counted MobileNet-v1 layers."""
import json

import numpy as np
import pytest

from conftest import BENCH
from lib import counting, reference as R
from small import SMALL, small_cell

# MobileNet-v1 1.0@192, multiply-accumulates per image, layer by layer:
# out_h * out_w * out_c * k * k (* in_c for a full conv), by hand
HAND_1_0_192 = [
    96 * 96 * 32 * 3 * 3 * 3,                      # stem 3x3/2
    96 * 96 * 32 * 9, 96 * 96 * 64 * 32,           # block 1
    48 * 48 * 64 * 9, 48 * 48 * 128 * 64,          # block 2 (dw /2)
    48 * 48 * 128 * 9, 48 * 48 * 128 * 128,        # block 3
    24 * 24 * 128 * 9, 24 * 24 * 256 * 128,        # block 4 (dw /2)
    24 * 24 * 256 * 9, 24 * 24 * 256 * 256,        # block 5
    12 * 12 * 256 * 9, 12 * 12 * 512 * 256,        # block 6 (dw /2)
] + [12 * 12 * 512 * 9, 12 * 12 * 512 * 512] * 5 + [   # blocks 7-11
    6 * 6 * 512 * 9, 6 * 6 * 1024 * 512,           # block 12 (dw /2)
    6 * 6 * 1024 * 9, 6 * 6 * 1024 * 1024,         # block 13
    1024 * 2,                                      # 2-class head
]


@pytest.fixture(scope="module")
def cfg_full():
    path = BENCH / "configs" / "mobilenet_v1_1.0_192_int8.reorder.json"
    return json.loads(path.read_text())


def test_model_macs_by_hand(cfg_full):
    macs = [layer.macs for layer in R.layers(cfg_full) if layer.macs]
    assert macs == HAND_1_0_192
    assert R.model_macs(cfg_full) == 417_099_776


class _W:
    def __init__(self, n):
        self.nbytes = n


def test_call_cost_pointwise_by_hand():
    # block 7's pointwise conv over 8 lanes: 12x12x512 -> 12x12x512
    attrs = {"k": 1, "stride": 1, "weight_q": _W(512 * 512)}
    ops, nbytes = counting.call_cost("qconv", attrs, (12, 12, 512),
                                     (12, 12, 512), lanes=8)
    assert ops == 2 * 37_748_736 * 8
    assert nbytes == 8 * (2 * 12 * 12 * 512) + 512 * 512


def test_call_cost_depthwise_by_hand():
    # block 2's depthwise conv, stride 2: 96x96x64 -> 48x48x64, one lane
    attrs = {"k": 3, "stride": 2, "weight_q": _W(3 * 3 * 64)}
    ops, nbytes = counting.call_cost("qdwconv", attrs, (96, 96, 64),
                                     (48, 48, 64), lanes=1)
    assert ops == 2 * 1_327_104
    assert nbytes == 96 * 96 * 64 + 48 * 48 * 64 + 576


@pytest.mark.parametrize("kind,attrs,kernel", [
    ("qconv", {"k": 1, "stride": 1}, "qconv1x1"),
    ("qconv", {"k": 1, "stride": 1, "pex_pads": (0, 0)}, "qconv1x1"),
    ("qconv", {"k": 1, "stride": 1, "pex_wpads": (1, 0)}, "qconv"),
    ("qconv", {"k": 3, "stride": 2}, "qconv"),
    ("qdwconv", {"k": 3, "stride": 1}, "qdwconv"),
])
def test_kernel_of(kind, attrs, kernel):
    assert counting.kernel_of(kind, attrs) == kernel


def test_least_time_takes_the_larger_bound():
    calls = [(2_000, 10), (10, 2_000)]          # one compute-, one memory-
    t, bound = counting.least_time_s(calls, peak_ops=1_000, peak_bw=100)
    assert t == pytest.approx(2.0 + 20.0)
    assert bound == "memory"


def test_whole_layer_calls_cover_the_model():
    """At the reorder-only rung every conv runs whole, once per dispatch:
    the kernels' operations are the model's, less the head."""
    import repro.deploy as deploy
    from repro.graphs import mobilenet_v1_graph
    cfg = small_cell("reorder.backlog").config
    d = deploy.build(mobilenet_v1_graph(SMALL["alpha"], SMALL["resolution"]),
                     quantize=True)
    calls = counting.kernel_calls(d.exec_graph, d.schedule, lanes=4)
    assert {k: len(v) for k, v in calls.items()} == {
        "qconv": 1, "qdwconv": 13, "qconv1x1": 13}
    ops = sum(o for v in calls.values() for o, _ in v)
    head = R.layers(cfg)[-1].macs
    assert ops == 2 * 4 * (R.model_macs(cfg) - head)
    weights = sum(int(np.asarray(op.attrs["weight_q"]).nbytes)
                  for op in d.schedule if op.kind in ("qconv", "qdwconv"))
    acts = sum(b for v in calls.values() for _, b in v) - weights
    assert acts % 4 == 0
