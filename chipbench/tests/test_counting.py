"""Operation and byte counts against hand-counted MobileNet-v1 layers,
and the kernel rules of ``kernels/<op kind>.py``."""
import hashlib
import json

import numpy as np
import pytest

from conftest import BENCH
from lib import counting, harness
from small import small_cell

# kernel_calls of the small deployment over 4 lanes (0.25@96, whole
# layers, and streamed in tiles under a 40 KB budget), recorded before
# the rules moved out of lib/counting.py into kernels/
PINNED_CALLS = {
    None: ({"qconv": 1, "qdwconv": 13, "qconv1x1": 13},
           "a66160dc7c695181f2de075a9fee7389749b52d48632232f6335f0f683323e70"),
    40 * 1024: ({"qconv": 144, "qdwconv": 333, "qconv1x1": 298},
                "9ce66d2f037966a0d861d96779f5d3709daa9bf5a039511a2aa75c28e6046ae0"),
}
PINNED_WHOLE_LAYER_CALLS = {
    "qconv": [[3981312, 184536]],
    "qconv1x1": [[2359296, 221312], [2359296, 111104], [4718592, 148480],
                 [2359296, 57344], [4718592, 77824], [2359296, 35840],
                 [4718592, 53248], [4718592, 53248], [4718592, 53248],
                 [4718592, 53248], [4718592, 53248], [2359296, 46592],
                 [4718592, 83968]],
    "qdwconv": [[1327104, 147528], [663552, 184464], [1327104, 147744],
                [331776, 92448], [663552, 74304], [165888, 46656],
                [331776, 38016], [331776, 38016], [331776, 38016],
                [331776, 38016], [331776, 38016], [82944, 24192],
                [165888, 20736]],
}

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


@pytest.fixture(scope="module")
def v1():
    return harness.load_model("mobilenet_v1")


def test_model_macs_by_hand(cfg_full, v1):
    macs = [layer.macs for layer in v1.layers(cfg_full) if layer.macs]
    assert macs == HAND_1_0_192
    assert v1.model_macs(cfg_full) == 417_099_776


class _W:
    def __init__(self, n):
        self.nbytes = n


def test_call_cost_pointwise_by_hand():
    # block 7's pointwise conv over 8 lanes: 12x12x512 -> 12x12x512
    attrs = {"k": 1, "stride": 1, "weight_q": _W(512 * 512)}
    ops, nbytes = counting.rule("qconv").call_cost(
        attrs, ((12, 12, 512),), (12, 12, 512), lanes=8)
    assert ops == 2 * 37_748_736 * 8
    assert nbytes == 8 * (2 * 12 * 12 * 512) + 512 * 512


def test_call_cost_depthwise_by_hand():
    # block 2's depthwise conv, stride 2: 96x96x64 -> 48x48x64, one lane
    attrs = {"k": 3, "stride": 2, "weight_q": _W(3 * 3 * 64)}
    ops, nbytes = counting.rule("qdwconv").call_cost(
        attrs, ((96, 96, 64),), (48, 48, 64), lanes=1)
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
    assert counting.rule(kind).kernel_of(attrs) == kernel


@pytest.mark.parametrize("kind", ["qavgpool", "qfc", "pex_slice",
                                  "pex_ring_push"])
def test_op_kind_without_a_rule_file_is_not_counted(kind):
    assert counting.rule(kind) is None


def test_least_time_takes_the_larger_bound():
    calls = [(2_000, 10), (10, 2_000)]          # one compute-, one memory-
    t, bound = counting.least_time_s(calls, peak_ops=1_000, peak_bw=100)
    assert t == pytest.approx(2.0 + 20.0)
    assert bound == "memory"


@pytest.fixture(scope="module")
def small_calls():
    """Budget -> (the deployment, its kernel calls over 4 lanes)."""
    import repro.deploy as deploy
    cell = small_cell("reorder.backlog")
    out = {}
    for budget in PINNED_CALLS:
        d = deploy.build(cell.model.program_graph(cell.config),
                         quantize=True, arena_budget=budget)
        out[budget] = d, counting.kernel_calls(d.exec_graph, d.schedule,
                                               lanes=4)
    return out


@pytest.mark.parametrize("budget", list(PINNED_CALLS))
def test_kernel_calls_reproduce_their_pinned_counts(small_calls, budget):
    _, calls = small_calls[budget]
    counts, digest = PINNED_CALLS[budget]
    assert {k: len(v) for k, v in calls.items()} == counts
    js = json.dumps({k: [list(c) for c in v]
                     for k, v in sorted(calls.items())})
    assert hashlib.sha256(js.encode()).hexdigest() == digest
    if budget is None:
        assert json.loads(js) == PINNED_WHOLE_LAYER_CALLS


def test_whole_layer_calls_cover_the_model(small_calls, v1):
    """At the reorder-only rung every conv runs whole, once per dispatch:
    the kernels' operations are the model's, less the head."""
    cfg = small_cell("reorder.backlog").config
    d, calls = small_calls[None]
    assert {k: len(v) for k, v in calls.items()} == {
        "qconv": 1, "qdwconv": 13, "qconv1x1": 13}
    ops = sum(o for v in calls.values() for o, _ in v)
    head = v1.layers(cfg)[-1].macs
    assert ops == 2 * 4 * (v1.model_macs(cfg) - head)
    weights = sum(int(np.asarray(op.attrs["weight_q"]).nbytes)
                  for op in d.schedule if op.kind in ("qconv", "qdwconv"))
    acts = sum(b for v in calls.values() for _, b in v) - weights
    assert acts % 4 == 0
