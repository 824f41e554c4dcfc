"""MobileNet-v2 in the benchmark: its reference (``models/mobilenet_v2.py``)
against the program, the int4 control, the ``qadd`` counting rule, and a
rehearsal of ``mnv2_512k.backlog``, all on the CPU at 0.35@32 with 10
classes under a 7,680 B budget, which makes the scheduler slice the
expansion chains.  Times from here are CPU times and say nothing about the
chip."""
import json

import numpy as np
import pytest

from conftest import BENCH
from lib import counting, harness, reference as R, traffic
from small import run_small, small_cell

CELL = "mnv2_512k.backlog"
SMALL_V2 = dict(alpha=0.35, resolution=32, num_classes=10,
                arena_budget_bytes=7680)


@pytest.fixture(scope="module")
def cell():
    return small_cell(CELL, **SMALL_V2)


@pytest.fixture(scope="module")
def deployed(cell):
    import repro.deploy as deploy
    cfg, model = cell.config, cell.model
    d = deploy.build(model.program_graph(cfg), quantize=True,
                     arena_budget=cfg["arena_budget_bytes"],
                     use_pallas=True, strict=True)
    return d


def test_configuration_is_the_published_model():
    path = BENCH / "configs" / "mobilenet_v2_1.0_224_int8.sram512k.json"
    cfg = json.loads(path.read_text())
    assert cfg["model"] == "mobilenet_v2" and cfg["reduced"] == []
    assert (cfg["alpha"], cfg["resolution"], cfg["num_classes"]) == \
        (1.0, 224, 1001)
    assert cfg["arena_budget_bytes"] == 524288 and cfg["lanes"] == 128
    model = harness.load_model("mobilenet_v2")
    ls = model.layers(cfg)
    assert sum(layer.kind == "add" for layer in ls) == 10
    assert [layer.cout for layer in ls if layer.kind == "conv"
            and layer.act == "none"] == \
        [16, 24, 24, 32, 32, 32, 64, 64, 64, 64, 96, 96, 96, 160, 160, 160,
         320]
    # by hand: stem 112*112*32*27; per block expansion, depthwise and
    # projection; last conv 7*7*1280*320; classifier 1280*1001
    hand = 112 * 112 * 32 * 27
    h, c = 112, 32
    for t, cb, n, s in cfg["blocks"]:
        for i in range(n):
            ho = h // (s if i == 0 else 1)
            e = c * t
            hand += (h * h * e * c if t != 1 else 0) + ho * ho * e * 9 \
                + ho * ho * cb * e
            h, c = ho, cb
    hand += 7 * 7 * 1280 * 320 + 1280 * 1001
    assert model.model_macs(cfg) == hand == 300775552


def test_reference_equals_the_program_bit_for_bit(cell, deployed):
    cfg, model = cell.config, cell.model
    d = deployed
    assert d.schedule_result.method.endswith("+pex")
    assert d.arena_bytes <= cfg["arena_budget_bytes"]
    qm = model.quantize_model(cfg)
    # the reference's activation parameters are the program's
    g = d.qmodel.float_graph
    prog = [d.qmodel.qparams["input"]] + [d.qmodel.qparams[op.output]
                                          for op in g.operators]
    assert [(q.scale, q.zp) for q in qm.act] == \
        [(q.scale, q.zero_point) for q in prog]
    images = traffic.pool_images(cfg, 6, 2**31 + 77)
    want = R.logits(model.int8_forward(qm), qm.quantize_input(images),
                    block=6)
    (name,) = [c for c in d.graph.constants() if d.graph.consumers(c)]
    got = [np.asarray(d.run(d.quantize_inputs({name: im}))[
        d.graph.outputs[0]]) for im in images]
    assert R.compare(got, want) == {"missing_answers": 0,
                                    "wrong_answers": 0,
                                    "max_logit_gap_lsb": 0}
    assert len(np.unique(want)) > 10          # logits, not saturation


def test_int4_control_reads_wrong(cell):
    cfg, model = cell.config, cell.model
    images = traffic.pool_images(cfg, 6, 2**31 + 78)
    qm8 = model.quantize_model(cfg)
    qm4 = model.quantize_model(cfg, weight_bits=4)
    want = R.logits(model.int8_forward(qm8), qm8.quantize_input(images),
                    block=6)
    ctrl = R.logits(model.int8_forward(qm4), qm4.quantize_input(images),
                    block=6)
    nums = R.compare(list(ctrl), want)
    assert nums["wrong_answers"] == 6 and nums["max_logit_gap_lsb"] > 10


def test_qadd_calls_are_counted(deployed):
    """Every scheduled residual add is one ``qadd`` call per dispatch:
    4 operations per output element, both inputs and the output once."""
    d = deployed
    calls = counting.kernel_calls(d.exec_graph, d.schedule, 4)
    adds = [op for op in d.schedule if op.kind == "qadd"]
    assert len(calls["qadd"]) == len(adds) == 10
    for op, (ops, nbytes) in zip(adds, calls["qadd"]):
        n = int(np.prod(d.exec_graph.tensors[op.output].shape))
        assert (ops, nbytes) == (4 * n * 4, 3 * n * 4)
    # pinned: the small deployment's kernels and their calls
    assert {k: len(v) for k, v in calls.items()} == \
        {"qconv": 1, "qconv1x1": 93, "qdwconv": 54, "qadd": 10}
    assert calls["qadd"][:3] == [(8192, 6144), (4096, 3072), (4096, 3072)]
    assert sum(o for o, _ in calls["qadd"]) == 26880
    least, bound = counting.least_time_s(calls["qadd"], 393e12, 819e9)
    assert bound == "memory" and least > 0


def test_rehearsal_backlog(cell):
    out = run_small(CELL, **SMALL_V2)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"setup_s", "throughput_rps"}
    assert all(c["value"] == 0 for c in out["checks"].values())


def test_rehearsal_traced(cell):
    out = run_small(CELL, trace=True, seconds=1.5, **SMALL_V2)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert {"build_s", "compile_s"} <= set(m)
    # the CPU has no device plane: no device metric may be reported
    assert "qadd_roofline" not in m and "dispatch_mfu" not in m
    assert "busy_s" in out["device"] and "window_s" in out["device"]
