"""The program's own measurement points: the serving step's host spans
(``repro.serving.*``, written into the profiler's trace), the operator and
rolled-loop named scopes of the arena program, and ``deploy.build``'s phase
events (``/repro/deploy/*`` on ``jax.monitoring``).  All of them attach
names or report times; none may change an answer."""
import collections
import glob
import os

import jax
import jax.numpy as jnp
import pytest

import repro.deploy as deploy
from repro.core import partition_graph
from repro.graphs import figure1_int8_graph, mobilenet_v1_graph, random_input
from repro.mcu import compile_schedule

PHASES = ("admit", "stage", "launch", "wait", "extract")


def _serve(d, requests, lanes=4):
    """Every request through ``ShardedServingEngine.step``, each step in a
    ``test.step`` span; returns the outputs in request order."""
    eng = d.engine(micro_batch=lanes, replicas=1)
    rids = [eng.submit(r) for r in requests]
    while eng.pending:
        with jax.profiler.TraceAnnotation("test.step"):
            eng.step()
    return [eng.take(rid) for rid in rids]


def _host_spans(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name == "test.step"
                  or e.name.startswith("repro.serving."))


@pytest.mark.parametrize("guard_bytes", [0, 16],
                         ids=["production", "guarded"])
def test_serving_step_spans_each_phase_once_in_order(tmp_path, guard_bytes):
    d = deploy.build(figure1_int8_graph(), guard_bytes=guard_bytes)
    requests = [random_input(d.graph, seed=i) for i in range(10)]
    want = _serve(d, requests)
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = _serve(d, requests)
    finally:
        jax.profiler.stop_trace()

    spans = _host_spans(str(tmp_path))
    steps = [s for s in spans if s[2] == "test.step"]
    assert len(steps) == 3                   # 10 requests at 4 lanes
    for a, b, _ in steps:
        inside = [s for s in spans if s[2] != "test.step"
                  and a <= s[0] and s[1] <= b]
        assert [n for _, _, n in inside] == [
            f"repro.serving.{p}" for p in PHASES]
        for (_, end, _), (start, _, _) in zip(inside, inside[1:]):
            assert end <= start
    # every phase span lies inside a step
    assert len(spans) == len(steps) * (1 + len(PHASES))

    for w, g in zip(want, got):
        assert w.keys() == g.keys()
        for k in w:
            assert w[k].dtype == g[k].dtype
            assert w[k].tobytes() == g[k].tobytes()


@pytest.mark.parametrize("quantize", [False, True])
def test_build_reports_each_phase_once(quantize):
    seen = collections.Counter()

    def listen(event, secs, **kw):
        if event.startswith("/repro/deploy/"):
            assert secs >= 0
            seen[event] += 1

    g = mobilenet_v1_graph(0.25, 32) if quantize else figure1_int8_graph()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        deploy.build(g, quantize=quantize, strict=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    want = {"/repro/deploy/schedule": 1, "/repro/deploy/plan": 1,
            "/repro/deploy/lower": 1}
    if quantize:
        want["/repro/deploy/quantize"] = 1
    assert dict(seen) == want


def test_lowering_scopes_every_operator_and_rolled_loop():
    gp = partition_graph(mobilenet_v1_graph(), budget=48 * 1024).graph
    sched = gp.default_schedule()
    ex = compile_schedule(gp, sched)
    assert ex.rolled_loops >= 2
    jaxpr = jax.make_jaxpr(ex.raw_fn)(
        jax.ShapeDtypeStruct((ex.arena_size,), jnp.uint8)).jaxpr
    scopes = collections.defaultdict(list)
    for e in jaxpr.eqns:
        scopes[str(e.source_info.name_stack).split("/")[0]].append(
            e.primitive.name)
    loops = {f"loop{k}" for k in range(ex.rolled_loops)}
    names = {op.name for op in sched}
    assert loops <= set(scopes)
    assert set(scopes) <= names | loops          # no equation unscoped
    for k in loops:
        assert scopes[k] == ["scan"]             # the fori_loop, whole
    # the operators the loops roll are the ones with no scope of their own
    assert len(set(scopes) - loops) == len(sched) - ex.rolled_ops

