"""``repro.deploy`` — the one-call deployment facade.

Every consumer of the paper pipeline used to hand-roll the same four-step
chain::

    res  = schedule(graph, arena_budget=..., partition=...)
    g    = res.graph if res.graph is not None else graph
    plan = ArenaPlanner.plan(g, res.schedule)
    ArenaPlanner.validate(plan, g)
    ex   = compile_schedule(g, res.schedule, plan, use_pallas=...)

duplicated (with drift) across the serving engines, the benchmarks, the
examples and the tests.  ``build()`` is that chain as one call returning a
``Deployment`` — the documented way to go from a graph to something that
runs::

    import repro.deploy as deploy

    d = deploy.build(graph, arena_budget=256 * 1024)
    out = d.run({"input": x})            # one request
    outs = d.serve(requests)             # micro-batched engine
    d.stats.arena_bytes                  # typed, not stringly-keyed

The raw ``schedule()``/``ArenaPlanner``/``compile_schedule`` chain stays
importable and supported — ``build`` adds no semantics on top of it, so
anything the facade can express the chain can too (and vice versa; the
facade is for the 95% path).

``quantize=True`` accepts a *float* graph and post-training-quantizes it
first (``graphs/quantize.py``); the returned deployment carries the
``QuantizedModel`` so callers can ``d.quantize_inputs(...)`` /
``d.dequantize_outputs(...)`` at the edges while ``run``/``serve`` keep
the honest int8 dtype contract inside.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import ArenaPlanner, schedule as _schedule
from repro.core.allocator import ArenaPlan
from repro.core.graph import Graph, Operator
from repro.core.scheduler import ScheduleResult
from repro.errors import (BudgetUnreachableError, DeploymentError,
                          InputValidationError, NaNActivationError)
from repro.mcu.compile import (_JNP_DTYPES, CompiledExecutor,
                               compile_schedule)

# Graceful-degradation ladder (strict=False): each entry is the rung set
# handed to ``core.schedule(rungs=...)``; ``None`` = the full ladder.  When
# a rung set fails (a rewrite crashes, a plan fails validation, a lowering
# refuses to compile), build() drops to the next entry — progressively
# disabling the most intricate rewrites first (2-D tiles, then ring
# cascades, then whole-externals Pex) until only plain reordering is left.
# Reordering cannot be disabled: it is the identity fallback.
_FALLBACK_RUNGS: Tuple[Optional[Tuple[str, ...]], ...] = (
    None,
    ("reorder", "pex", "cascade", "solver"),
    ("reorder", "pex", "solver"),
    ("reorder",),
)


@dataclasses.dataclass
class Deployment:
    """A graph scheduled, planned, validated and compiled — ready to run.

    ``graph`` is the graph the caller handed in; ``exec_graph`` is the one
    the schedule's operators belong to (a Pex/cascade rewrite, or the int8
    rewrite under ``quantize=True`` — the same graph when no rewrite
    fired).  ``schedule`` is the operator order, ``plan`` the validated
    arena plan the executor runs against.
    """

    graph: Graph                          # as passed to build()
    exec_graph: Graph                     # what the schedule executes
    schedule_result: ScheduleResult
    plan: ArenaPlan
    executor: CompiledExecutor
    qmodel: Optional[object] = None       # QuantizedModel when quantize=True
    # failure layer (DESIGN.md §12): what build(strict=False) gave up on —
    # [] means nothing degraded; each note names the rung/budget and why
    degraded: List[str] = dataclasses.field(default_factory=list)
    guard_bytes: int = 0                  # canary width planned (0 = off)

    @property
    def schedule(self) -> List[Operator]:
        return self.schedule_result.schedule

    @property
    def arena_bytes(self) -> int:
        return int(self.plan.arena_size)

    # ------------------------------------------------------------ validation
    def validate_inputs(self, inputs: Dict[str, Any]) -> None:
        """Reject malformed request inputs with a typed
        ``InputValidationError`` *before* they reach the arena.

        The executor's own ``make_arena`` checks are narrower than they
        look: ``jnp.asarray`` silently downcasts float64 → float32 before
        the dtype check fires, and the element-count check silently accepts
        any wrong *shape* with the right flat size.  On an MCU deployment
        both are wrong-answer factories, so the facade checks name, shape,
        dtype, finiteness, and the int8 quantization domain up front."""
        g = self.executor.graph
        if not isinstance(inputs, dict):
            raise InputValidationError(
                f"inputs must be a dict of tensor name -> array, got "
                f"{type(inputs).__name__}")
        needed = {c for c in g.constants() if g.consumers(c)}
        missing = needed - set(inputs)
        if missing:
            raise InputValidationError(
                f"missing graph inputs: {sorted(missing)}")
        for name, value in inputs.items():
            if name not in g.tensors:
                raise InputValidationError(
                    f"unknown input tensor {name!r}; graph inputs are "
                    f"{sorted(needed)}")
            if g.producer(name) is not None:
                raise InputValidationError(
                    f"{name!r} is produced by operator "
                    f"{g.producer(name).name!r}, not a graph input")
            t = g.tensors[name]
            val = np.asarray(value)
            want = np.dtype(_JNP_DTYPES[t.dtype]) if t.dtype != "bfloat16" \
                else None
            if want is not None and val.dtype != want:
                hint = ""
                if t.dtype == "int8":
                    hint = (" — int8 graphs take quantized inputs in "
                            "[-128, 127]; use d.quantize_inputs(...) at "
                            "the float edge")
                raise InputValidationError(
                    f"input {name!r} is {val.dtype}, graph declares "
                    f"{t.dtype}{hint}")
            shape = tuple(t.shape) if t.shape else (t.elements,)
            if tuple(val.shape) != shape and val.size == t.elements:
                raise InputValidationError(
                    f"input {name!r} has shape {tuple(val.shape)}, graph "
                    f"declares {shape} (same element count — refusing the "
                    f"silent flatten)")
            if val.size != t.elements:
                raise InputValidationError(
                    f"input {name!r} has {val.size} elements, graph "
                    f"declares {t.elements} (shape {shape})")
            if val.dtype.kind == "f" and not np.isfinite(val).all():
                raise InputValidationError(
                    f"input {name!r} contains non-finite values (NaN/Inf "
                    f"poison every downstream activation)")

    # ------------------------------------------------------------- running
    def run(self, inputs: Dict[str, Any], as_numpy: bool = True, *,
            validate: bool = True, faults=None) -> Dict[str, Any]:
        """One request through the compiled arena program.

        ``validate=True`` (default) runs ``validate_inputs`` first —
        malformed requests raise ``InputValidationError`` instead of being
        silently cast/flattened.  ``faults`` (a ``serving.FaultPlan`` or
        ``FaultInjector``; test-only) exercises the one-shot path under the
        same fault taxonomy as the engines: transient device errors are
        retried, corruption is surfaced by the guard canaries
        (``GuardViolation``) and NaN poison by a genuine output scan
        (``NaNActivationError``) — never returned as an answer."""
        if validate:
            self.validate_inputs(inputs)
        ex = self.executor
        if faults is None:
            return ex.run(inputs, as_numpy=as_numpy)
        from repro.serving.faults import (FaultInjector, FaultPlan,
                                          dispatch_with_retry)
        inj = FaultInjector(faults) if isinstance(faults, FaultPlan) \
            else faults
        arena, _retried, _trips = dispatch_with_retry(
            lambda: ex.fn(ex.make_arena(inputs)), faults=inj)
        a = np.array(arena)   # writable host copy: never mutate jax buffers
        if inj.corrupt_lanes(1):
            inj.corrupt_arena(a, ex.guard_regions)
        ex.verify_guards(a)                    # raises GuardViolation
        if inj.nan_lanes(1):
            inj.inject_nan(a, ex)
        out = ex.outputs_from(a, as_numpy=True)
        for name, val in out.items():
            arr = np.asarray(val)
            if arr.dtype.kind == "f" and np.isnan(arr).any():
                raise NaNActivationError(
                    f"output {name!r} contains NaN activations")
        return out

    def serve(self, requests: Sequence[Dict[str, Any]], *,
              micro_batch: int = 8) -> List[Dict[str, Any]]:
        """Micro-batched one-shot serve (single device).  For sharded
        continuous batching build an engine with ``engine(...)``."""
        return self.engine(micro_batch=micro_batch).serve(requests)

    def engine(self, *, micro_batch: int = 8, replicas: Optional[int] = None,
               **kw):
        """A serving engine over this deployment.  ``replicas=None`` gives
        the single-device micro-batching ``GraphServingEngine``; any other
        value the sharded continuous-batching ``ShardedServingEngine``
        (``replicas=0`` = one replica per visible device)."""
        if replicas is None:
            from repro.serving.engine import GraphServingEngine
            return GraphServingEngine(deployment=self,
                                      micro_batch=micro_batch, **kw)
        from repro.serving.sharded import ShardedServingEngine
        return ShardedServingEngine(self, replicas=replicas or None,
                                    lanes=micro_batch, **kw)

    # ------------------------------------------------------------- stats
    @property
    def stats(self):
        """Deployment-level ``EngineStats`` (schedule/arena fields; the
        serve-level fields belong to an engine's ``.stats``)."""
        from repro.serving.stats import EngineStats
        return EngineStats(
            arena_bytes=self.arena_bytes,
            schedule_peak_bytes=int(self.schedule_result.peak),
            schedule_method=self.schedule_result.method)

    # --------------------------------------------------- quantized edges
    def quantize_inputs(self, inputs: Dict[str, Any]) -> Dict[str, Any]:
        if self.qmodel is None:
            return inputs
        return self.qmodel.quantize_inputs(inputs)

    def dequantize_outputs(self, outputs: Dict[str, Any]) -> Dict[str, Any]:
        if self.qmodel is None:
            return outputs
        return self.qmodel.dequantize_outputs(outputs)


@contextlib.contextmanager
def _phase(name: str):
    """Report the wall time of one phase of ``build`` as the
    ``jax.monitoring`` duration event ``/repro/deploy/<name>``; a phase
    that raises reports nothing."""
    t = time.perf_counter()
    yield
    jax.monitoring.record_event_duration_secs(
        f"/repro/deploy/{name}", time.perf_counter() - t)


def build(graph: Graph, *, arena_budget: Optional[int] = None,
          quantize: bool = False, calibration=None,
          use_pallas: bool = False, objective: str = "memory",
          partition: bool = False, macs_cap: Optional[float] = None,
          fuse: bool = False, strict: bool = True, guard_bytes: int = 0,
          **schedule_opts) -> Deployment:
    """schedule → plan → validate → compile, one call.

    * ``arena_budget`` — target arena bytes; the scheduler escalates
      reorder → Pex → cascaded streaming until it fits.  ``strict=True``
      (default) raises ``BudgetUnreachableError`` on a miss;
      ``strict=False`` deploys best-effort with the miss recorded in
      ``Deployment.degraded``.
    * ``quantize`` — post-training-quantize a float graph to int8 first
      (``calibration``: input dict(s); default = deterministic synthetic).
    * ``use_pallas`` — route int8 convs through the fused Pallas kernels
      (bit-identical; DESIGN.md §9).  They compile with Mosaic on a TPU
      and run through the Pallas interpreter elsewhere.
    * ``objective`` — ``"memory"`` (lowest peak) or ``"latency"``
      (cheapest in-budget schedule; needs ``arena_budget``).
    * ``macs_cap`` — max halo-recompute extra-MACs fraction.
    * ``strict=False`` — graceful degradation: when a scheduler rung fails
      (a rewrite crashes, a plan fails validation, a lowering refuses to
      compile), fall back through progressively simpler rung sets
      (cascade2d → cascade → pex → reorder) instead of raising; every
      fallback and budget miss is a note in ``Deployment.degraded``.
      Only when *every* rung set fails does ``DeploymentError`` escape.
    * ``guard_bytes`` — debug mode: plan ``guard_bytes`` of never-placed
      slack around every placement and fill/verify canary bytes there at
      run time (``GuardViolation`` on a stomp).  0 (default) is
      byte-identical to the historical planner/executor.
    * extra keyword arguments are forwarded to ``core.schedule()``.

    Each phase of an attempt reports its wall time as a ``jax.monitoring``
    duration event: ``/repro/deploy/quantize`` (``quantize=True`` only),
    ``/repro/deploy/schedule``, ``/repro/deploy/plan`` (plan and
    validate) and ``/repro/deploy/lower``.  The deployed schedule's
    recomputed share of the graph's MACs (``extra_macs_frac``) is the
    ``jax.monitoring`` scalar ``/repro/deploy/extra_macs_frac``.
    """
    qmodel = None
    if quantize:
        from repro.graphs import quantize_graph
        with _phase("quantize"):
            qmodel = quantize_graph(graph, calibration)
        graph = qmodel.graph

    # one attempt = the full schedule → plan → validate → compile chain for
    # one rung set; any failure inside is that rung set's failure
    def attempt(rungs):
        with _phase("schedule"):
            res = _schedule(graph, arena_budget=arena_budget,
                            partition=partition, objective=objective,
                            macs_cap=macs_cap,
                            **(schedule_opts if rungs is None
                               else {**schedule_opts, "rungs": rungs}))
        eg = res.graph if res.graph is not None else graph
        with _phase("plan"):
            plan = ArenaPlanner.plan(eg, res.schedule,
                                     guard_bytes=guard_bytes)
            ArenaPlanner.validate(plan, eg)
        with _phase("lower"):
            ex = compile_schedule(eg, res.schedule, plan,
                                  use_pallas=use_pallas, fuse=fuse)
        return res, eg, plan, ex

    ladder = (_FALLBACK_RUNGS if "rungs" not in schedule_opts
              else (schedule_opts.pop("rungs"),))
    degraded: List[str] = []
    res = None
    if strict:
        res, exec_graph, plan, executor = attempt(ladder[0])
    else:
        for rungs in ladder:
            try:
                res, exec_graph, plan, executor = attempt(rungs)
                break
            except Exception as e:       # noqa: BLE001 — each rung may fail
                tag = "full ladder" if rungs is None else "+".join(rungs)
                degraded.append(f"rung set [{tag}] failed: "
                                f"{type(e).__name__}: {e}")
        if res is None:
            raise DeploymentError(
                "every scheduler rung set failed — nothing left to degrade "
                "to:\n  " + "\n  ".join(degraded))
    jax.monitoring.record_scalar("/repro/deploy/extra_macs_frac",
                                 float(res.extra_macs_frac))
    if arena_budget is not None and plan.arena_size > arena_budget:
        miss = (f"arena budget missed: need {int(plan.arena_size)} B > "
                f"budget {int(arena_budget)} B (best rung: {res.method})")
        if strict:
            raise BudgetUnreachableError(
                miss + " — pass strict=False to deploy best-effort")
        degraded.append(miss)
    return Deployment(graph=graph, exec_graph=exec_graph,
                      schedule_result=res, plan=plan, executor=executor,
                      qmodel=qmodel, degraded=degraded,
                      guard_bytes=guard_bytes)


__all__ = ["Deployment", "build"]
