"""Serving-tier tests: the ``repro.deploy`` facade, ragged-tail padding
accounting, typed ``EngineStats``, and admission-order invariance of the
sharded continuous-batching engine.

The invariance contract is the serving-layer analogue of the executor's
bit-identity contract: whatever the arrival interleaving (one-shot serve,
submit/step interleavings, ragged tails) and whatever replica/lane a
request lands on, its outputs are **bit-identical** to a one-shot
``Deployment.run`` of that request alone.  A subprocess leg re-runs the
grid on a forced 3-device host mesh so real multi-replica pmap assignment
is covered, not just the degenerate 1-device mesh of the test process.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import repro.deploy as deploy
from repro.graphs import figure1_int8_graph, quantize_graph, random_input
from repro.graphs.cnn_ops import CNNBuilder
from repro.core.graph import Graph
from repro.serving import (EngineStats, GraphServingEngine,
                           ShardedServingEngine, percentile_ms)


def _tiny_cnn() -> Graph:
    g = Graph()
    b = CNNBuilder(g)
    x = b.input("input", 12, 12, 3)
    x = b.conv(x, 6, k=3)
    y = b.maxpool(x, k=2, stride=2)
    y = b.conv(y, 6, k=1)
    y = b.avgpool(y)
    y = b.fc(y, 4)
    g.set_outputs([y])
    return g


def _tiny_cnn_int8() -> Graph:
    g = _tiny_cnn()
    return quantize_graph(g, random_input(g)).graph


# fixed-seed grid: the int8 golden graph plus a quantized CNN and its
# float build (three dtype/shape regimes through the same engines)
_GRID = {
    "figure1_int8": figure1_int8_graph,
    "tiny_cnn_int8": _tiny_cnn_int8,
    "tiny_cnn_f32": _tiny_cnn,
}


def _requests(g, n, seed0=0):
    return [random_input(g, seed=seed0 + i) for i in range(n)]


# ------------------------------------------------------------------ facade
def test_deploy_build_matches_manual_chain():
    """build() is exactly the schedule→plan→validate→compile chain."""
    from repro.core import ArenaPlanner, schedule
    from repro.mcu import compile_schedule

    g = figure1_int8_graph()
    d = deploy.build(g)
    res = schedule(g)
    assert d.schedule_result.peak == res.peak
    assert [op.name for op in d.schedule] == [op.name for op in res.schedule]
    plan = ArenaPlanner.plan(g, res.schedule)
    assert d.arena_bytes == plan.arena_size
    x = random_input(g)
    ref = compile_schedule(g, res.schedule, plan).run(x)
    out = d.run(x)
    for o in g.outputs:
        np.testing.assert_array_equal(ref[o], out[o])


def test_deploy_quantize_builds_int8():
    g = _tiny_cnn()
    d = deploy.build(g, quantize=True)
    assert d.qmodel is not None
    assert all(t.dtype == "int8" for t in d.exec_graph.tensors.values())
    xq = d.quantize_inputs(random_input(g))
    out = d.run(xq)
    deq = d.dequantize_outputs(out)
    for o in g.outputs:
        assert out[o].dtype == np.int8
        assert deq[o].dtype != np.int8


def test_deploy_stats_typed():
    d = deploy.build(figure1_int8_graph())
    s = d.stats
    assert isinstance(s, EngineStats)
    j = s.as_json()
    assert j["arena_bytes"] == d.arena_bytes > 0
    assert j["schedule_method"]
    # never-measured serve fields stay out of the payload
    assert "requests_per_s" not in j and "p99_ms" not in j


def test_engine_stats_legacy_keys():
    s = EngineStats(arena_bytes=7, dispatches=3)
    assert s["arena_bytes"] == 7
    assert s["micro_batches"] == 3          # legacy spelling of dispatches
    assert "micro_batches" in s
    with pytest.raises(KeyError):
        s["no_such_stat"]


def test_percentile_ms():
    lat = [0.001 * (i + 1) for i in range(100)]
    assert percentile_ms(lat, 50) == pytest.approx(50.0, abs=1.5)
    assert percentile_ms(lat, 99) == pytest.approx(99.0, abs=1.5)
    assert percentile_ms([], 99) == 0.0


# -------------------------------------------------------- ragged-tail fix
def test_ragged_tail_accounting_and_outputs():
    """Regression: a ragged final micro-batch must (a) return correct
    outputs for every true request, (b) report true request count vs pad
    lanes explicitly, (c) keep pad lanes out of per-request stats."""
    g = _tiny_cnn()
    d = deploy.build(g)
    eng = GraphServingEngine(deployment=d, micro_batch=4)
    reqs = _requests(g, 6)                  # 4 + ragged tail of 2 (2 pads)
    outs = eng.serve(reqs)
    assert len(outs) == 6
    for r, o in zip(reqs, outs):
        ref = d.run(r)
        for name in g.outputs:
            np.testing.assert_array_equal(ref[name], o[name])
    st = eng.stats
    assert st.requests == 6
    assert st.padded_lanes == 2
    assert st.dispatches == 2
    assert len(outs) == st.requests         # pads never extracted
    assert st.requests_per_s > 0 and st.p99_ms >= st.p50_ms > 0
    j = st.as_json()
    assert j["requests"] == 6 and j["padded_lanes"] == 2


def test_no_padding_on_exact_batches():
    g = _tiny_cnn()
    eng = GraphServingEngine(g, micro_batch=2)
    eng.serve(_requests(g, 4))
    assert eng.stats.padded_lanes == 0
    assert eng.stats.dispatches == 2


# ---------------------------------------------- admission-order invariance
@pytest.mark.parametrize("name", sorted(_GRID))
def test_sharded_outputs_invariant_under_interleaving(name):
    """Per-request outputs are bit-identical to one-shot Deployment.run,
    regardless of how submits interleave with dispatch boundaries."""
    g = _GRID[name]()
    d = deploy.build(g)
    reqs = _requests(g, 7, seed0=11)
    refs = [d.run(r) for r in reqs]

    eng = ShardedServingEngine(d, lanes=2)

    # interleaving A: everything up front (one-shot serve, ragged tail)
    outs = eng.serve(reqs)
    for ref, o in zip(refs, outs):
        for t in g.outputs:
            np.testing.assert_array_equal(ref[t], o[t])

    # interleaving B: late arrivals join later dispatch boundaries
    rids = [eng.submit(reqs[0]), eng.submit(reqs[1])]
    eng.step()                               # boundary: 0,1 complete
    rids += [eng.submit(r) for r in reqs[2:5]]
    eng.step()                               # boundary: 2,3 (lanes=2) ...
    rids += [eng.submit(r) for r in reqs[5:]]
    done = eng.drain()
    assert sorted(done) == sorted(rids)
    for ref, rid in zip(refs, rids):
        for t in g.outputs:
            np.testing.assert_array_equal(ref[t], done[rid][t])
    st = eng.stats
    assert st.requests == 7 and st.dispatches >= 3


def test_sharded_admission_is_fifo_at_boundaries():
    g = figure1_int8_graph()
    eng = ShardedServingEngine(deploy.build(g), lanes=2)
    a = eng.submit(random_input(g, seed=1))
    b = eng.submit(random_input(g, seed=2))
    c = eng.submit(random_input(g, seed=3))
    done_now = eng.step()                    # capacity 2: admits a, b only
    assert done_now == 2
    assert eng.pending == 1
    out_a = eng.take(a)
    out_b = eng.take(b)
    out_c = eng.drain()[c]                  # drain returns what's left
    for out, seed in ((out_a, 1), (out_b, 2), (out_c, 3)):
        ref = deploy.build(g).run(random_input(g, seed=seed))
        for t in g.outputs:
            np.testing.assert_array_equal(ref[t], out[t])


def test_sharded_rejects_build_opts_on_deployment():
    d = deploy.build(figure1_int8_graph())
    with pytest.raises(ValueError, match="already a Deployment"):
        ShardedServingEngine(d, arena_budget=1024)


_MULTI_DEVICE_SCRIPT = """
from repro.serving import force_host_devices
force_host_devices(3)
import jax
assert jax.local_device_count() == 3, jax.devices()
import numpy as np
import repro.deploy as deploy
from repro.graphs import figure1_int8_graph, random_input
from repro.serving import ShardedServingEngine

g = figure1_int8_graph()
d = deploy.build(g)
reqs = [random_input(g, seed=20 + i) for i in range(8)]
refs = [d.run(r) for r in reqs]
eng = ShardedServingEngine(d, replicas=3, lanes=2)
assert eng.replicas == 3 and eng.capacity == 6
outs = eng.serve(reqs)                  # 8 over capacity 6: ragged 2nd step
for ref, o in zip(refs, outs):
    for t in g.outputs:
        np.testing.assert_array_equal(ref[t], o[t])
st = eng.stats
assert st.dispatches == 2 and st.padded_lanes == 4 and st.requests == 8
# requests land on every replica; the ragged 2nd step fills replica 0
assert st.replica_requests == [4, 2, 2], st.replica_requests
assert eng.devices == jax.devices()[:3]
print("MULTI_OK")
"""


def test_sharded_multi_replica_bit_identical_subprocess():
    """Real replica assignment: a forced 3-device host mesh (must be set
    before jax init, hence the subprocess) with requests landing on every
    replica — outputs stay bit-identical to single-request execution."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _MULTI_DEVICE_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, f"\n{proc.stdout}\n{proc.stderr}"
    assert "MULTI_OK" in proc.stdout


def test_cpu_platform_requested_reads_only_the_environment(monkeypatch):
    """Benchmarks start a forced-host-device child only on the CPU; on a
    chip the parent holds the devices, so the choice must not need JAX."""
    from repro.serving import cpu_platform_requested
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert cpu_platform_requested()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert not cpu_platform_requested()
    monkeypatch.delenv("JAX_PLATFORMS")
    assert not cpu_platform_requested()
