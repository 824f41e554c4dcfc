"""The program's spans, scopes and set-up events read from a trace
(``lib/spans.py``), on hand-made events, on the recorded chip traces, and
through ``split.py`` on the CPU.  The readers of ``lib/readers.py`` must
read a ``ProgramTrace`` exactly as they read the harness's ``Trace``."""
import json

import pytest

from conftest import BENCH
from lib import readers, spans as S, trace as T
from small import PEAKS, small_cell
from test_trace import _recorded, _run


def test_scope_path_unwraps_transforms():
    assert S.scope_path("jit(call_wrapped)/vmap(loop0)/while/body/"
                        "closed_call/dw_3.s0/add:") == \
        "loop0/while/body/closed_call/dw_3.s0/add"
    assert S.scope_path("jit(call_wrapped)/vmap(conv1)/jit(qconv_fused)/"
                        "reshape:") == "conv1/qconv_fused/reshape"
    assert S.scope_path("") == ""


def _key(field, kind):
    return _varint(field << 3 | kind)


def _varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(field, *parts):
    body = b"".join(p.encode() if isinstance(p, str) else p for p in parts)
    return _key(field, 2) + _varint(len(body)) + body


def _num(field, n):
    return _key(field, 0) + _varint(n)


def _plane(name, *ops):
    """An ``XPlane`` with stat names 7 ``tf_op``, 8 ``hlo_category`` and
    9 an interned ``tf_op`` value; ``ops`` are ``XEventMetadata``."""
    stat = [_msg(5, _num(1, k), _msg(2, _num(1, k), _msg(2, v)))
            for k, v in ((7, "tf_op"), (8, "hlo_category"),
                         (9, "jit(f)/vmap(conv1)/copy:"))]
    meta = [_msg(4, _num(1, i), _msg(2, _num(1, i), *op))
            for i, op in enumerate(ops, 1)]
    line = _msg(3, _num(1, 1), _msg(2, "XLA Ops"),
                _key(4, 1) + bytes(8))           # a fixed64 to step over
    return _msg(1, _num(1, 3), _msg(2, name), line, *stat, *meta)


def test_op_scopes_read_tf_op_from_event_metadata():
    fusion = "%fusion.3 = s8[4]{0} fusion(%p), kind=kLoop"
    copy = "%copy.2 = s8[4]{0} copy(%fusion.3)"
    xspace = _plane(
        "/device:TPU:0",
        [_msg(2, fusion),
         _msg(5, _num(1, 8), _msg(5, "loop fusion")),
         _msg(5, _num(1, 7), _msg(5, "jit(f)/vmap(loop1)/while/body/add:"))],
        [_msg(2, copy), _msg(5, _num(1, 7), _num(7, 9))],
        [_msg(2, "%constant.1 = s8[] constant(0)")]) + _plane(
        "/host:CPU", [_msg(2, "x"), _msg(5, _num(1, 7), _msg(5, "y:"))])
    assert S.op_scopes(xspace) == {
        fusion: "jit(f)/vmap(loop1)/while/body/add:",
        copy: "jit(f)/vmap(conv1)/copy:"}


def _trace():
    """Two steps; step 1: admit .. extract with a loop op in ``wait``."""
    ops = {0: [("copy.1", 1.0, 0.5, "conv1"),            # in launch
               ("while.2", 2.0, 1.0, "loop0/while"),     # in wait
               ("fusion.3", 2.1, 0.4, "loop0/while/body/add"),
               ("reduce.4", 3.5, 0.25, ""),               # in extract
               ("copy.5", 7.0, 0.5, "conv1")]}            # step 2, wait
    spans = [("chipbench.window", 0.0, 10.0),
             ("chipbench.step", 0.5, 4.0), ("chipbench.step", 5.0, 3.0),
             ("repro.serving.admit", 0.5, 0.1),
             ("repro.serving.stage", 0.6, 0.3),
             ("repro.serving.launch", 0.9, 1.0),
             ("repro.serving.wait", 1.9, 1.4),
             ("repro.serving.extract", 3.3, 1.2),
             ("repro.serving.admit", 5.0, 0.5),
             ("repro.serving.stage", 5.5, 0.5),
             ("repro.serving.launch", 6.0, 0.5),
             ("repro.serving.wait", 6.5, 1.0),
             ("repro.serving.extract", 7.5, 0.5)]
    return S.from_events(ops, spans)


def test_phases_program_and_loops_per_dispatch():
    tr = _trace()
    assert S.phase_ms_per_dispatch(tr, "extract") == pytest.approx(
        (1.2 + 0.5) / 2 * 1e3)
    assert S.phase_ms_per_dispatch(tr, "admit") == pytest.approx(300.0)
    cover = S.phase_cover(tr)
    assert cover[0] == pytest.approx(1.0)
    assert cover[1] == pytest.approx(1.0)
    # launch and wait: 0.5 + 1.0 busy in step 1, 0.5 in step 2; the
    # extraction op's 0.25 is left out
    assert S.arena_program_ms_per_dispatch(tr) == pytest.approx(1000.0)
    assert readers.device_ms_per_dispatch(_run(tr), "backlog") == \
        pytest.approx((1.75 + 0.5) / 2 * 1e3)
    # the loop's union: 2.0 .. 3.0 in step 1, none in step 2
    assert S.cascade_loop_ms_per_dispatch(tr) == pytest.approx(500.0)


def test_nothing_to_read_reads_none():
    tr = T.from_events({0: [("copy", 1.0, 0.5)]},
                       [("chipbench.window", 0.0, 2.0),
                        ("chipbench.step", 0.5, 1.0)])
    bare = S.from_events({0: [("copy", 1.0, 0.5, "")]},
                         [("chipbench.window", 0.0, 2.0),
                          ("chipbench.step", 0.5, 1.0)])
    for t in (tr, bare):
        assert S.phase_ms_per_dispatch(t, "stage") is None
        assert S.phase_cover(t) is None
        assert S.arena_program_ms_per_dispatch(t) is None
    assert S.cascade_loop_ms_per_dispatch(bare) is None
    assert S.deploy_s([("/jax/x", 1.0)], "schedule") is None
    assert S.deploy_s([("/repro/deploy/schedule", 1.5),
                       ("/repro/deploy/schedule", 0.5)], "schedule") == 2.0


def test_gaps_go_to_the_innermost_span():
    tr = _trace()
    assert S.span_label("repro.serving.extract") == "serving.extract"
    assert S.span_label("chipbench.step") == "step"
    # 3.75 .. 4.5 lies in the step and in extract: extract is shorter
    assert S.gap_name(tr, (3.75, 4.5)) == "serving.extract"
    assert S.gap_name(tr, (4.6, 4.9)) == "no span"
    # 0.5 .. 1.0: step covers all of it, but holds admit, stage and
    # launch; stage covers the most
    assert S.gap_name(tr, (0.5, 1.0)) == "serving.stage"
    assert S.gap_name(tr, (0.55, 1.0)) == "serving.stage"
    # 0.5 .. 1.5: stage and launch cover 0.3 and 0.6 of it
    assert S.gap_name(tr, (0.5, 1.5)) == "serving.launch"
    # 4.0 .. 5.8: extract 0.5 of step 1, admit 0.5 and stage 0.3 of step 2
    assert S.gap_name(tr, (4.0, 5.8)) == "serving.admit"
    # 5.2 .. 6.4: admit 0.3, stage 0.5, launch 0.4
    assert S.gap_name(tr, (5.2, 6.4)) == "serving.stage"
    b = S.breakdown(tr)
    # 3.75 .. 7.0: 0.75 in step 1's extract, 0.5 in each phase of step
    # 2 up to its wait; 7.5 .. 10: 0.5 in step 2's extract, then none
    assert b["idle_gaps"][:2] == [["serving.extract", pytest.approx(3.25)],
                                  ["serving.extract", pytest.approx(2.5)]]
    assert b["scopes"][0] == ["conv1", pytest.approx(1.0)]
    assert ["loop0", pytest.approx(0.4)] in b["scopes"]


# the harness's readers on the PR 12 recording, as they read it then
RECORDED = {
    "program_ms": 2.0404309999999075, "host_ms": 16.91221800000009,
    "idle_pct": 89.34621266590665, "mfu_pct": 6.961788846960162,
}


def test_existing_readers_keep_their_values():
    old = _recorded()
    rec = json.loads((BENCH / "tests" / "data" /
                      "reorder_l4_trace.json").read_text())
    new = S.from_events(
        {int(k): [(n, t * 1e-9, d * 1e-9, "") for n, t, d in v]
         for k, v in rec["ops"].items()},
        [(n, t * 1e-9, d * 1e-9) for n, t, d in rec["spans"]])
    for tr in (old, new):
        run = _run(tr)
        assert readers.device_ms_per_dispatch(run, "backlog") == \
            RECORDED["program_ms"]
        assert readers.host_ms_per_dispatch(run, "backlog") == \
            RECORDED["host_ms"]
        assert readers.idle_pct(run) == RECORDED["idle_pct"]
        assert readers.dispatch_mfu_pct(run) == RECORDED["mfu_pct"]
        assert tr.kernel_s("qdwconv") == (0.000370755, 39)
        assert T.breakdown(tr) == T.breakdown(old)


def _recorded_spans():
    """``data/reorder_l4_spans_trace.json``: 3 dispatches of MobileNet-v1
    1.0@192 int8 reorder-only at 4 lanes on one TPU v5 lite with the
    program's spans and each op's scope path."""
    rec = json.loads((BENCH / "tests" / "data" /
                      "reorder_l4_spans_trace.json").read_text())
    ops = {int(k): [(n, t * 1e-9, d * 1e-9, sc) for n, t, d, sc in v]
           for k, v in rec["ops"].items()}
    spans = [(n, t * 1e-9, d * 1e-9) for n, t, d in rec["spans"]]
    return S.from_events(ops, spans), T.from_events(
        {k: [e[:3] for e in v] for k, v in ops.items()}, spans)


def test_recorded_spans_cover_each_step_in_order():
    tr, _ = _recorded_spans()
    steps = tr.host("chipbench.step")
    assert len(steps) == 3
    for st in steps:
        inside = sorted((t, n) for n, t, d in tr.spans
                        if n.startswith(S.PHASE) and st[0] <= t < st[1])
        assert [n for _, n in inside] == [S.PHASE + p for p in S.PHASES]
    assert min(S.phase_cover(tr)) >= 0.98
    assert all(S.phase_ms_per_dispatch(tr, p) > 0 for p in S.PHASES)
    # the device's gaps fall in the phases, never between them
    names = {n for n, _ in S.breakdown(tr)["idle_gaps"]}
    assert names <= {"serving." + p for p in S.PHASES}, names


def test_recorded_scopes_name_the_operators():
    tr, plain = _recorded_spans()
    run = _run(plain)
    program = S.arena_program_ms_per_dispatch(tr)
    # the per-lane extraction ops are device time of the step, not of
    # the arena program
    assert 0 < program < readers.device_ms_per_dispatch(run, "backlog")
    assert readers.host_ms_per_dispatch(_run(tr), "backlog") == \
        readers.host_ms_per_dispatch(run, "backlog")
    scopes = dict(S.top_scopes(tr, top=100))
    # every operator of the 29-step reorder schedule ran in its scope
    from repro.graphs import mobilenet_v1_graph
    ops = mobilenet_v1_graph(1.0, 192).default_schedule()
    assert len(ops) == 29
    assert {op.name for op in ops} <= set(scopes)
    assert max(scopes, key=scopes.get) == "conv1"
    assert S.cascade_loop_ms_per_dispatch(tr) is None   # no rolled loops


def test_split_run_on_the_cpu():
    """``split.py``'s run at 0.25@96 on the CPU: the harness's result is
    unchanged, and the host spans and set-up events are read.  The CPU
    has no device plane, so no device number may be read."""
    import time
    import jax
    import split
    cell = small_cell("reorder.backlog")
    summary, tr = split.split_run(
        cell, seed=2**31 + 17, seconds=1.0,
        devices=jax.devices("cpu")[:1], peaks=PEAKS,
        t_start=time.perf_counter())
    assert summary["result"]["correct"]
    sp = summary["split"]
    assert sp["steps"] > 0
    assert all(v > 0 for v in sp["phase_ms_per_dispatch"].values())
    assert sp["phase_cover_min"] > 0.9
    assert sp["arena_program_ms_per_dispatch"] is None
    assert set(sp["deploy_s"]) == {"quantize", "schedule", "plan", "lower"}
