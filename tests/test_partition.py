"""Partial-execution (Pex) subsystem: slicing correctness, the memory model,
and scheduler/jaxpr integration.

Property tests use plain ``random`` (not hypothesis) so they always run in
tier 1: (a) a partitioned graph evaluates bit-identically to the original
through the micro-interpreter, (b) the arena planner validates sliced
schedules, (c) partitioning never loses to reorder-only scheduling.
"""
import random

import numpy as np
import pytest

from repro.core import (ArenaPlanner, Graph, partition_graph, schedule,
                        sliceable_runs)
from repro.graphs import figure1_graph, mobilenet_v1_graph
from repro.graphs.cnn_ops import CNNBuilder
from repro.graphs.figure1 import DEFAULT_PEAK, OPTIMAL_PEAK
from repro.mcu import MicroInterpreter


def random_cnn_graph(seed: int, h: int = 24, w: int = 24) -> Graph:
    """A random CNN-shaped DAG: sliceable chains (conv/dwconv/maxpool/add)
    interleaved with branch+concat joins and non-sliceable ops."""
    rng = random.Random(seed)
    g = Graph()
    b = CNNBuilder(g)
    x = b.input("input", h, w, rng.choice([3, 4]))
    x = b.conv(x, rng.choice([4, 8]), k=3)

    def chain(t, n):
        for _ in range(n):
            r = rng.random()
            if r < 0.35:
                # MobileNet-style expand→filter→project: fat interior
                t = b.conv(t, rng.choice([16, 24, 32]), k=1)
                t = b.dwconv(t, k=3)
                t = b.conv(t, rng.choice([4, 8]), k=1)
            elif r < 0.6:
                t = b.conv(t, rng.choice([4, 8, 16]), k=rng.choice([1, 3]))
            elif r < 0.85:
                t = b.dwconv(t, k=3)
            else:
                cout = b.shapes[t][2]
                t = b.add(t, b.conv(t, cout, k=1)) \
                    if rng.random() < 0.5 else b.conv(t, cout, k=3)
        return t

    for _ in range(rng.randint(1, 3)):
        if rng.random() < 0.5:
            stem = b.conv(x, rng.choice([8, 16]), k=1)
            a = chain(stem, rng.randint(1, 3))
            c = b.dwconv(stem, k=3)
            x = b.concat([a, c])
        else:
            x = chain(x, rng.randint(1, 4))
        if rng.random() < 0.4:
            x = b.maxpool(x, k=2, stride=2)
    x = b.avgpool(x)
    x = b.fc(x, 4)
    g.set_outputs([x])
    return g


def _inputs(g, seed=0):
    h, w, c = g.tensors["input"].shape
    rng = np.random.default_rng(seed)
    return {"input": rng.standard_normal((h, w, c)).astype(np.float32)}


# ------------------------------------------------------------ core validation
def test_figure1_paper_constants_stay_valid():
    # (kept in the fast tier even when hypothesis is unavailable and the
    # property-test modules skip)
    g = figure1_graph()
    assert g.peak_usage(g.default_schedule()) == DEFAULT_PEAK == 5216
    assert schedule(g).peak == OPTIMAL_PEAK == 4960


def test_ineligible_graph_returned_unchanged():
    g = figure1_graph()           # no shapes, no slice specs
    pr = partition_graph(g)
    assert pr.graph is g and not pr.segments
    res = schedule(g, partition=True)
    assert res.graph is None      # no rewrite happened


def test_sliceable_runs_classification():
    g = mobilenet_v1_graph()      # pure chain of conv/dwconv + avgpool + fc
    runs = sliceable_runs(g)
    assert runs, "mobilenet must expose sliceable runs"
    kinds = {op.kind for run in runs for op in run}
    assert kinds <= {"conv", "dwconv", "maxpool", "add"}
    # the global avgpool and fc must never be inside a run
    assert all(op.kind not in ("avgpool", "fc", "concat")
               for run in runs for op in run)


# -------------------------------------------------------------- property (a)
def test_partitioned_graph_bit_identical_on_random_dags():
    partitioned = 0
    for seed in range(6):
        g = random_cnn_graph(seed)
        # small K set: fewer clone shapes to compile, same properties
        res = schedule(g, partition=True,
                       partition_opts={"k_choices": (2, 4)})
        if res.graph is None:
            continue
        partitioned += 1
        x = _inputs(g, seed)
        ref = MicroInterpreter(g).run(x)
        got = MicroInterpreter(res.graph).run(x, schedule=res.schedule)
        for o in g.outputs:
            np.testing.assert_array_equal(ref.outputs[o], got.outputs[o])
        # the simulator's dynamic-allocator peak must agree with the
        # liveness model on the sliced schedule (inplace concat included)
        assert got.peak_sram == res.graph.peak_usage(res.schedule)
    assert partitioned >= 2, "generator produced too few partitionable DAGs"


# -------------------------------------------------------------- property (b)
def test_arena_planner_validates_sliced_schedules():
    for seed in range(5):
        g = random_cnn_graph(seed)
        res = schedule(g, partition=True)
        gp = res.graph if res.graph is not None else g
        plan = ArenaPlanner.plan(gp, res.schedule)
        ArenaPlanner.validate(plan)
        assert plan.arena_size >= gp.peak_usage(res.schedule) \
            or plan.arena_size == gp.peak_usage(res.schedule)
        if res.graph is not None:
            # the inplace concat chain must share one buffer
            shared = [p for p in plan.placements if p.alias is not None]
            assert shared
            by_alias = {}
            for p in shared:
                by_alias.setdefault(p.alias, set()).add(p.offset)
            assert all(len(offs) == 1 for offs in by_alias.values())


# -------------------------------------------------------------- property (c)
def test_partitioned_peak_never_worse_than_reorder_only():
    for seed in range(8):
        g = random_cnn_graph(seed)
        base = schedule(g)
        res = schedule(g, partition=True)
        assert res.peak <= base.peak


def test_partition_strictly_beats_reorder_on_chain_model():
    # MobileNet is a pure chain: reordering cannot help at all, partial
    # execution can (the Pex claim).
    g = mobilenet_v1_graph()                       # 0.25x @ 96
    base = schedule(g)
    res = schedule(g, partition=True)
    assert res.graph is not None and res.peak < base.peak
    plan = ArenaPlanner.plan(res.graph, res.schedule)
    ArenaPlanner.validate(plan)
    assert plan.arena_size <= base.peak


@pytest.mark.slow
def test_partition_bit_identical_on_mobilenet():
    g = mobilenet_v1_graph()
    res = schedule(g, partition=True)
    x = _inputs(g)
    ref = MicroInterpreter(g).run(x)
    got = MicroInterpreter(res.graph).run(x, schedule=res.schedule)
    for o in g.outputs:
        np.testing.assert_array_equal(ref.outputs[o], got.outputs[o])


def test_budget_mode_only_partitions_when_needed():
    g = mobilenet_v1_graph()
    base = schedule(g)
    # generous budget: reordering alone suffices, graph untouched
    assert schedule(g, arena_budget=base.peak).graph is None
    # tight budget: partitioning must kick in and meet it
    tight = int(base.peak * 0.9)
    res = schedule(g, arena_budget=tight)
    assert res.graph is not None and res.peak <= tight


# ------------------------------------------------------------------ jaxpr pex
def _int_valued_mlp_operands(rng):
    """Small-integer-valued f32 MLP operands: every sum is exact in f32,
    so sliced dot_generals must match the whole one bit-for-bit whatever
    GEMM blocking XLA CPU picks for each row count."""
    def ints(shape):
        return rng.integers(-3, 4, size=shape).astype(np.float32)
    import jax.numpy as jnp
    return ints((32, 512)), ints((512, 32)), jnp.asarray(ints((256, 32)))


def test_jaxpr_partial_execution_mlp():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from jax._src.core import eval_jaxpr
    from repro.core.jaxpr_partial import partial_execute_closed_jaxpr
    from repro.core.jaxpr_reorder import peak_liveness

    rng = np.random.default_rng(0)
    w1, w2, x = _int_valued_mlp_operands(rng)

    def mlp(x):
        return jnp.maximum(x @ w1, 0.0) @ w2      # fat (256, 512) interior

    closed = jax.make_jaxpr(mlp)(x)
    pc, n_runs = partial_execute_closed_jaxpr(closed)
    assert n_runs == 1
    assert peak_liveness(pc) < peak_liveness(closed)
    ref = np.asarray(eval_jaxpr(closed.jaxpr, closed.consts, x)[0])
    got = np.asarray(eval_jaxpr(pc.jaxpr, pc.consts, x)[0])
    np.testing.assert_array_equal(got, ref)


def test_jaxpr_elementwise_slicing_bit_identical():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from jax._src.core import eval_jaxpr
    from repro.core.jaxpr_partial import _expand_run

    def f(x):
        return jnp.exp(jnp.tanh(x))

    x = jnp.asarray(np.random.default_rng(1)
                    .standard_normal((64, 16)).astype(np.float32))
    closed = jax.make_jaxpr(f)(x)
    jaxpr = closed.jaxpr
    new_eqns = _expand_run(list(jaxpr.eqns), 4)
    new_jaxpr = jaxpr.replace(eqns=new_eqns)
    ref = np.asarray(eval_jaxpr(jaxpr, closed.consts, x)[0])
    got = np.asarray(eval_jaxpr(new_jaxpr, closed.consts, x)[0])
    np.testing.assert_array_equal(ref, got)


def test_jaxpr_reorder_with_partition_budget():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from jax._src.core import eval_jaxpr
    from repro.core.jaxpr_reorder import reorder_closed_jaxpr

    rng = np.random.default_rng(2)
    w1, w2, x = _int_valued_mlp_operands(rng)

    def mlp(x):
        return jnp.maximum(x @ w1, 0.0) @ w2

    closed = jax.make_jaxpr(mlp)(x)
    _, base = reorder_closed_jaxpr(closed)
    budget = base.peak_after // 2
    nc, rep = reorder_closed_jaxpr(closed, partition_budget=budget)
    assert rep.method.endswith("+pex") and rep.peak_after < base.peak_after
    ref = np.asarray(eval_jaxpr(closed.jaxpr, closed.consts, x)[0])
    got = np.asarray(eval_jaxpr(nc.jaxpr, nc.consts, x)[0])
    np.testing.assert_array_equal(got, ref)
    # without a budget the behaviour is unchanged
    _, plain = reorder_closed_jaxpr(closed)
    assert plain.peak_after == base.peak_after
