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
import functools
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import repro.deploy as deploy
from repro.graphs import figure1_int8_graph, quantize_graph, random_input
from repro.graphs.cnn_ops import CNNBuilder
from repro.core.graph import Graph
from repro.serving import (EngineStats, FaultPlan, GraphServingEngine,
                           RequestError, ShardedServingEngine, percentile_ms)


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


def _uint8_mask() -> Graph:
    """A float32 input to one uint8 output."""
    g = Graph()
    g.add_tensor("x", 4 * 24, shape=(4, 6), dtype="float32")
    g.add_tensor("h", 4 * 24, shape=(4, 6), dtype="float32")
    g.add_tensor("mask", 24, shape=(4, 6), dtype="uint8")
    g.add_operator("scale", ["x"], "h", fn=lambda x: x * 0.5 + 0.25)
    g.add_operator("to_mask", ["h"], "mask",
                   fn=lambda h: jnp.clip(jnp.abs(h) * 90, 0, 255)
                   .astype(jnp.uint8))
    g.set_outputs(["mask"])
    return g


def _two_outputs() -> Graph:
    """Two outputs: 3 bfloat16 (6 bytes) then 5 float32, so the float32
    bytes start unaligned in the concatenated output block."""
    g = Graph()
    g.add_tensor("x", 4 * 16, shape=(16,), dtype="float32")
    g.add_tensor("h", 4 * 16, shape=(16,), dtype="float32")
    g.add_tensor("head", 2 * 3, shape=(3,), dtype="bfloat16")
    g.add_tensor("tail", 4 * 5, shape=(5,), dtype="float32")
    g.add_operator("scale", ["x"], "h", fn=lambda x: x * 0.5 + 0.25)
    g.add_operator("first", ["h"], "head",
                   fn=lambda h: h[:3].astype(jnp.bfloat16))
    g.add_operator("last", ["h"], "tail", fn=lambda h: h[6:11] * h[11:])
    g.set_outputs(["head", "tail"])
    return g


# fixed-seed grid: the int8 golden graph, a quantized CNN and its float
# build, a uint8 output and a graph with two outputs (int8, uint8,
# float32 and bfloat16 outputs through the same engines)
_GRID = {
    "figure1_int8": figure1_int8_graph,
    "tiny_cnn_int8": _tiny_cnn_int8,
    "tiny_cnn_f32": _tiny_cnn,
    "uint8_mask": _uint8_mask,
    "two_outputs": _two_outputs,
}


@functools.lru_cache(maxsize=None)
def _grid_deployment(name: str):
    return deploy.build(_GRID[name]())


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


# ------------------------------------------- one-transfer output extraction
def _assert_outputs_equal(ref, out, names):
    assert sorted(out) == sorted(names)
    for t in names:
        assert out[t].dtype == ref[t].dtype
        assert out[t].shape == ref[t].shape
        assert not out[t].flags.writeable
        np.testing.assert_array_equal(ref[t], out[t])


@pytest.mark.parametrize("lanes,n", [(1, 1), (3, 2), (4, 4)],
                         ids=["L1", "ragged", "full"])
@pytest.mark.parametrize("name", sorted(_GRID))
def test_output_block_matches_outputs_from(name, lanes, n):
    """The one-transfer path's per-lane outputs are bit-identical to
    ``outputs_from`` of the same lane's arena; only the first ``n`` lanes
    are returned (the rest are pads)."""
    d = _grid_deployment(name)
    ex = d.executor
    reqs = _requests(d.exec_graph, n, seed0=40)
    stack = [ex.make_arena(r) for r in reqs]
    stack += [ex.pad_arena()] * (lanes - n)
    arenas = ex.replicated_fn(1)(np.stack(stack).reshape(1, lanes, -1))
    block = ex.output_block_fn(1)(arenas)
    out_bytes = sum(ex.offsets[o][1] for o in ex.graph.outputs)
    assert block.shape == (1, lanes, out_bytes)
    outs = ex.outputs_from_block(block, n)
    assert len(outs) == n
    for b, out in enumerate(outs):
        _assert_outputs_equal(ex.outputs_from(arenas[0, b]), out,
                              ex.graph.outputs)


@pytest.mark.parametrize("name", sorted(_GRID))
def test_single_device_fallback_reads_outputs_in_one_transfer(name):
    """A failed replica-mesh init serves through the jitted single-device
    program; its ``[1, L, arena]`` output takes the one-transfer path."""
    d = _grid_deployment(name)
    reqs = _requests(d.exec_graph, 5, seed0=60)
    eng = ShardedServingEngine(d, lanes=3,
                               faults=FaultPlan(fail_engine_init=True))
    assert eng.replicas == 1 and eng.stats.degraded is None
    outs = eng.serve(reqs)                   # 3 + a ragged 2
    for r, o in zip(reqs, outs):
        _assert_outputs_equal(d.run(r), o, d.exec_graph.outputs)
    s = eng.stats
    assert s.degraded and "single-device" in s.degraded[-1]
    assert (s.requests, s.padded_lanes) == (5, 1)
    assert s.batched_extracts == s.dispatches == 2


def test_batched_extracts_counts_every_no_fault_dispatch():
    d = _grid_deployment("figure1_int8")
    eng = ShardedServingEngine(d, lanes=2)
    eng.serve(_requests(d.exec_graph, 5))
    assert eng.stats.dispatches == 3
    assert eng.stats.batched_extracts == 3
    assert eng.stats.as_json()["batched_extracts"] == 3
    eng.serve(_requests(d.exec_graph, 2))    # drain() resets the count
    assert eng.stats.batched_extracts == eng.stats.dispatches == 1


@pytest.mark.parametrize("path", ["lane_faults", "guard_bytes"])
def test_host_copy_path_counts_no_batched_extracts(path):
    """Lane faults or guard bytes keep the writable host-copy path: no
    dispatch is counted as batched, and every answer it gives is still
    bit-identical to ``Deployment.run``."""
    g = _tiny_cnn()
    if path == "lane_faults":
        d = _grid_deployment("tiny_cnn_f32")
        eng = ShardedServingEngine(
            d, lanes=2, max_retries=4,
            faults=FaultPlan(seed=3, corrupt_rate=0.3, nan_rate=0.3))
    else:
        d = deploy.build(g, guard_bytes=32)
        assert d.executor.guard_regions
        eng = ShardedServingEngine(d, lanes=2)
    reqs = _requests(g, 6, seed0=80)
    outs = eng.serve(reqs)
    ok = [(r, o) for r, o in zip(reqs, outs)
          if not isinstance(o, RequestError)]
    assert ok
    for r, o in ok:
        ref = d.run(r)
        for t in g.outputs:
            np.testing.assert_array_equal(ref[t], o[t])
    assert eng.stats.dispatches >= 3
    assert eng.stats.batched_extracts == 0


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
assert st.batched_extracts == 2
assert eng.devices == jax.devices()[:3]

# the one-transfer read of a [3, 2] dispatch, from the replicas' own
# devices (no gather first), ragged: 5 requests and one pad lane
ex = d.executor
stack = [ex.make_arena(r) for r in reqs[:5]] + [ex.pad_arena()]
arenas = ex.replicated_fn(3)(np.stack(stack).reshape(3, 2, -1))
block = ex.output_block_fn(3)(arenas)
assert block.sharding.device_set == arenas.sharding.device_set
assert len(block.sharding.device_set) == 3
outs = ex.outputs_from_block(block, 5)
assert len(outs) == 5
for i, o in enumerate(outs):
    ref = ex.outputs_from(arenas[divmod(i, 2)])
    for t in g.outputs:
        assert not o[t].flags.writeable
        np.testing.assert_array_equal(ref[t], o[t])
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
