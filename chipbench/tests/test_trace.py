"""The reduction from trace events to device busy and idle time, kernel
time, rooflines and MFU, on hand-made events and on a small trace recorded
on a TPU v5e chip."""
from types import SimpleNamespace

import numpy as np
import pytest

from lib import readers, trace as T


def test_union_overlap_gaps():
    u = T.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)])
    assert u == [(0, 2.5), (3, 4)]
    assert T.overlap(u, (1, 3.5)) == pytest.approx(1.5 + 0.5)
    assert T.gaps(u, (-1, 5)) == [(-1, 0), (2.5, 3), (4, 5)]


def _trace():
    ops = {0: [("fusion.1", 1.0, 0.5), ("qconv1x1.3", 1.5, 1.0),
               ("qconv1x1", 4.0, 1.0), ("qdwconv.7", 5.0, 0.5),
               ("copy.2", 9.5, 1.0)]}          # runs past the window
    spans = [("chipbench.window", 0.0, 10.0),
             ("chipbench.step", 0.5, 3.0), ("chipbench.step", 3.5, 2.5),
             ("chipbench.wait", 6.0, 3.0)]
    return T.from_events(ops, spans)


def test_op_name():
    assert T.op_name("%qconv1x1.3 = s8[8,12,12,512]{3,2,1,0} custom-call("
                     "s8[8,144,512] %x), custom_call_target=\"tpu\"") == \
        "qconv1x1.3"
    assert T.op_name("%while.49 = (s32[], s8[2]) while(%t)") == "while.49"
    assert T.op_name("fusion.7") == "fusion.7"


def test_window_busy_and_kernels():
    tr = _trace()
    assert tr.window == (0.0, 10.0)
    assert tr.busy() == [(1.0, 2.5), (4.0, 5.5), (9.5, 10.5)]
    assert tr.busy_s() == pytest.approx(1.5 + 1.5 + 0.5)
    assert tr.kernel_s("qconv1x1") == (2.0, 2)
    assert tr.kernel_s("qdwconv") == (0.5, 1)
    assert tr.kernel_s("qconv") == (0.0, 0)     # no prefix matches


def test_breakdown_names_gaps_by_host_span():
    b = T.breakdown(_trace())
    assert b["device_ops"][0] == ["qconv1x1", 2.0]
    longest = b["idle_gaps"][0]
    assert longest == ["wait", pytest.approx(4.0)]    # 5.5 .. 9.5
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def _run(tr, kind="backlog"):
    served = SimpleNamespace(done=np.array([1.0, 2.0, np.nan]),
                             due=np.zeros(3), step_start=np.zeros(3),
                             window_s=2.0)
    cell = SimpleNamespace(mix={"kind": kind},
                           open_loop=kind == "open_loop")
    calls = {"qconv1x1": [(2_000_000, 1_000)], "qdwconv": [(10, 100_000)]}
    return SimpleNamespace(trace=tr, served=served, cell=cell, calls=calls,
                           peaks={"int8_ops_per_s": 1e9,
                                  "hbm_bytes_per_s": 1e6},
                           model_macs=1_000_000)


def test_readers_on_hand_made_trace():
    run = _run(_trace())
    # two traced steps: 3.0 s with 1.5 s busy, 2.5 s with 1.5 s busy
    assert readers.device_ms_per_dispatch(run, "backlog") == \
        pytest.approx(1500.0)
    assert readers.host_ms_per_dispatch(run, "backlog") == \
        pytest.approx(((3.0 - 1.5) + (2.5 - 1.5)) / 2 * 1e3)
    assert readers.device_ms_per_dispatch(run, "open_loop") is None
    # qconv1x1: least 2e-3 s per dispatch x 2 dispatches over 2.0 s
    assert readers.roofline_pct(run, "qconv1x1") == pytest.approx(0.2)
    # qdwconv: memory bound, 0.1 s x 2 over 0.5 s of device time
    assert readers.roofline_pct(run, "qdwconv") == pytest.approx(40.0)
    assert readers.roofline_pct(run, "qconv") is None
    # 2 requests x 2 x 1e6 ops over 10 s at 1e9 ops/s
    assert readers.dispatch_mfu_pct(run) == pytest.approx(0.04)
    assert readers.idle_pct(run) == pytest.approx(65.0)


def test_no_device_ops_reads_nothing():
    tr = T.from_events({}, [("chipbench.window", 0.0, 1.0),
                            ("chipbench.step", 0.1, 0.5)])
    run = _run(tr)
    for v in (readers.device_ms_per_dispatch(run, "backlog"),
              readers.host_ms_per_dispatch(run, "backlog"),
              readers.roofline_pct(run, "qconv1x1"),
              readers.dispatch_mfu_pct(run), readers.idle_pct(run)):
        assert v is None


# ------------------------------------------------ a trace from the chip
def _recorded():
    """``data/reorder_l4_trace.json``: 3 dispatches of MobileNet-v1
    1.0@192 int8 reorder-only at 4 lanes on one TPU v5 lite, cut from the
    profiler's trace to the device's ``XLA Ops`` and the harness spans."""
    import json
    from conftest import BENCH
    rec = json.loads((BENCH / "tests" / "data" /
                      "reorder_l4_trace.json").read_text())
    ops = {int(k): [(n, t * 1e-9, d * 1e-9) for n, t, d in v]
           for k, v in rec["ops"].items()}
    return T.from_events(ops, [(n, t * 1e-9, d * 1e-9)
                               for n, t, d in rec["spans"]])


def test_recorded_trace_kernels_match_the_schedule():
    tr = _recorded()
    steps = tr.host("chipbench.step")
    assert len(steps) == 3
    # 1 stem, 13 depthwise and 13 pointwise calls per dispatch
    assert tr.kernel_s("qconv")[1] == 3
    assert tr.kernel_s("qdwconv")[1] == 13 * 3
    assert tr.kernel_s("qconv1x1")[1] == 13 * 3
    assert 0 < tr.busy_s() < tr.window_s


def test_recorded_trace_rooflines_stay_under_100():
    import repro.deploy as deploy
    from repro.graphs import mobilenet_v1_graph
    from lib import counting
    d = deploy.build(mobilenet_v1_graph(1.0, 192), quantize=True)
    calls = counting.kernel_calls(d.exec_graph, d.schedule, lanes=4)
    run = _run(_recorded())
    run.calls = calls
    run.peaks = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    for kernel in ("qconv", "qconv1x1", "qdwconv"):
        share = readers.roofline_pct(run, kernel)
        assert 0 < share <= 100, (kernel, share)
