"""Sharded continuous-batching serving engine (the production tier).

``GraphServingEngine`` amortises XLA dispatch across vmap lanes but still
runs every batch on one device and makes late requests wait for the whole
serve loop.  ``ShardedServingEngine`` scales that out and opens the batch
boundary:

* **Replica sharding** — the deployed arena program is ``pmap(vmap(...))``
  over ``replicas`` devices: each dispatch executes an ``[R, L, arena]``
  stack, R replicas × L vmap lanes, with no collectives (requests are
  embarrassingly parallel), so per-lane results are bit-identical to a
  single ``Deployment.run``.  On CPU hosts the replica mesh comes from
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — call
  ``force_host_devices(N)`` (importable without touching jax) before the
  first jax import.
* **Continuous batching at dispatch granularity** — requests enter an
  admission queue (``submit``); every ``step`` admits up to R×L queued
  requests *at that batch boundary*.  A late arrival joins the next
  dispatch instead of waiting for the current serve loop to finish.
* **Deadline/priority admission** (``serving/admission.py``) — requests
  carry optional ``priority`` (larger admits first; ties FIFO, so the
  default queue is exactly the old FIFO) and an absolute ``deadline``:
  past-deadline requests are *never executed*, they complete as typed
  ``RequestError("expired")`` results.  ``max_pending`` bounds the queue —
  excess submissions shed immediately as ``RequestError("shed")``
  (backpressure as a typed outcome, not a latency cliff).
* **Bounded retry + watchdog** — each dispatch runs through
  ``faults.dispatch_with_retry``: transient device errors retry up to
  ``max_retries``; a ``dispatch_timeout`` turns persistent slowness into a
  typed failure (post-hoc watchdog — see that function's honesty note).
  Exhausted budgets become ``RequestError("dispatch_failed")`` for the
  admitted requests, never an exception out of the serve loop.
* **Fault detection + degradation** (DESIGN.md §12) — with a seeded
  ``FaultPlan``, injected arena corruption is caught by genuine guard-
  canary verification (``guard_bytes`` deployments) or the injector's
  ECC-style lane report, and NaN poison by a genuine output scan; poisoned
  requests re-queue (bounded by ``max_retries``) or fail typed.  Replica-
  mesh init failure degrades to the single-device batched program with a
  note in ``stats.degraded`` instead of refusing to serve.
* **Honest ragged tails** — pad lanes are explicit all-zero arenas:
  executed (one compiled shape), counted in ``stats.padded_lanes``, never
  returned, never in per-request latency.
* **One transfer a dispatch** — with no lane faults and no guards, every
  lane's outputs leave the device in one op and one transfer
  (``CompiledExecutor.output_block_fn``) and are cut per lane on the host
  as read-only views; ``stats.batched_extracts`` counts those dispatches.
* **Typed stats** — latency p50/p99 and throughput plus the failure-layer
  counters (admitted/expired/shed/retried/failed/watchdog_trips/
  batched_extracts) in
  ``EngineStats``; ``benchmarks/bench_serving.py`` gates requests/s as a
  floor and expired/shed as exact zeros in the no-fault configuration.

With no faults, no guards, and default admission (no deadlines, no bound)
outputs are bit-identical to ``Deployment.run`` under any arrival
interleaving.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from repro.errors import (DeviceInitError, DispatchFailedError,
                          GuardViolation)
from repro.serving.admission import (AdmissionQueue, QueuedRequest,
                                     RequestError)
from repro.serving.faults import (FaultInjector, FaultPlan,
                                  dispatch_with_retry)
from repro.serving.stats import EngineStats


def _span(phase: str):
    """A host span ``repro.serving.<phase>`` in the profiler's trace, on
    the device trace's clock; a few microseconds when no profiler runs."""
    return jax.profiler.TraceAnnotation(f"repro.serving.{phase}")


class ShardedServingEngine:
    """Continuous-batching engine over an ``[R, L]`` replica × lane grid.

    ``deployment`` is a ``repro.deploy.Deployment`` (or a graph, which is
    built through the facade).  ``replicas=None`` takes every visible
    device; ``lanes`` is the vmap width per replica, so one dispatch
    serves up to ``replicas * lanes`` requests.

    Failure-layer knobs (all default-off; see the module docstring):
    ``max_pending`` bounds the queue, ``max_retries``/``dispatch_timeout``
    bound the retry/watchdog loop, ``faults`` injects a seeded
    ``FaultPlan``, ``fallback_single_device`` controls mesh-init
    degradation, and ``clock`` is injectable so deadline/latency logic is
    testable against a fake clock.
    """

    def __init__(self, deployment, *, replicas: Optional[int] = None,
                 lanes: int = 4, max_pending: Optional[int] = None,
                 max_retries: int = 2,
                 dispatch_timeout: Optional[float] = None,
                 faults: Union[FaultPlan, FaultInjector, None] = None,
                 fallback_single_device: bool = True,
                 clock: Callable[[], float] = time.perf_counter,
                 **build_opts):
        from repro.deploy import Deployment, build
        if not isinstance(deployment, Deployment):
            deployment = build(deployment, **build_opts)
        elif build_opts:
            raise ValueError(f"build options {sorted(build_opts)} are for "
                             f"graph arguments; this is already a Deployment")
        self.deployment = deployment
        self.executor = deployment.executor
        self._clock = clock
        self.max_retries = int(max_retries)
        self.dispatch_timeout = dispatch_timeout
        self._faults = (FaultInjector(faults)
                        if isinstance(faults, FaultPlan) else faults)
        self._degraded: List[str] = list(deployment.degraded)
        devices = jax.devices(self.executor.device.platform)
        self.replicas = (len(devices) if replicas is None
                         else min(replicas, len(devices)))
        if self.replicas < 1:
            raise ValueError("need at least one replica")
        self.lanes = int(lanes)
        try:
            if self._faults is not None:
                self._faults.engine_init()
            self._fn = self.executor.replicated_fn(self.replicas)
            self._out_fn = self.executor.output_block_fn(self.replicas)
        except (DeviceInitError, RuntimeError) as e:
            if not fallback_single_device:
                raise
            # graceful degradation: the replica mesh is unavailable — serve
            # everything through the single-device batched program (shaped
            # back to [1, L, arena] so the step loop is unchanged)
            self._degraded.append(
                f"replica mesh init failed ({type(e).__name__}: {e}); "
                f"falling back to single-device serving")
            self.replicas = 1
            devices = [self.executor.device]
            size = self.executor.arena_size
            batched = self.executor.batched_fn()
            self._fn = (lambda batch:
                        batched(batch.reshape(self.lanes, size))
                        .reshape(1, self.lanes, size))
            self._out_fn = self.executor.output_block_fn()
        # the devices the replicas run on, in replica order
        self.devices = list(devices[:self.replicas])
        self._per_replica = [0] * self.replicas
        self._queue = AdmissionQueue(max_pending=max_pending)
        self._results: Dict[int, Any] = {}
        self._latencies: List[float] = []
        self._next_rid = 0
        self._dispatches = 0
        self._padded = 0
        self._completed = 0
        self._admitted = 0
        self._retried = 0
        self._failed = 0
        self._trips = 0
        self._batched_extracts = 0
        self._t_first_submit: Optional[float] = None
        self.stats = EngineStats(
            arena_bytes=deployment.arena_bytes,
            schedule_peak_bytes=int(deployment.schedule_result.peak),
            schedule_method=deployment.schedule_result.method,
            replicas=self.replicas, lanes=self.lanes)

    # ------------------------------------------------------ admission queue
    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def capacity(self) -> int:
        """Requests per dispatch: replicas × lanes."""
        return self.replicas * self.lanes

    def submit(self, inputs: Dict[str, Any], *, priority: int = 0,
               deadline: Optional[float] = None) -> int:
        """Enqueue one request; returns its rid.  ``priority`` (larger
        first, ties FIFO) and ``deadline`` (absolute, on this engine's
        clock; None = never expires) drive admission.  A submission over
        ``max_pending`` is shed: its result is immediately a typed
        ``RequestError("shed")`` — the rid contract is unchanged."""
        rid = self._next_rid
        self._next_rid += 1
        now = self._clock()
        if self._t_first_submit is None:
            self._t_first_submit = now
        req = QueuedRequest(rid, inputs, now, priority=priority,
                            deadline=deadline)
        if not self._queue.push(req):
            self._results[rid] = RequestError(
                rid, "shed",
                f"queue at max_pending={self._queue.max_pending}")
        return rid

    # --------------------------------------------------------- fault layer
    def _detect_lane(self, lane: np.ndarray, injected_corrupt: bool
                     ) -> Optional[str]:
        """Post-dispatch poison detection for one lane's host arena copy.
        Returns the typed error code, or None for a clean lane."""
        ex = self.executor
        if ex.guard_regions:
            try:
                ex.verify_guards(lane)       # genuine canary verification
            except GuardViolation:
                if self._faults is None:
                    raise        # no injection active: a real OOB write
                return "corrupted"
        out = ex.outputs_from(lane)
        for val in out.values():
            arr = np.asarray(val)
            if arr.dtype.kind == "f" and np.isnan(arr).any():
                return "nan_output"          # genuine NaN scan
        if injected_corrupt and not ex.guard_regions:
            # guard-less runs: the injector's lane report stands in for the
            # ECC/bus-fault signal real hardware raises on a flipped byte
            return "corrupted"
        return None

    def _resolve_poisoned(self, req: QueuedRequest, code: str) -> None:
        """A poisoned lane either re-queues (bounded) or fails typed."""
        if req.retries < self.max_retries:
            req.retries += 1
            self._retried += 1
            self._queue.requeue(req)
        else:
            self._results[req.rid] = RequestError(
                req.rid, code,
                f"retry budget ({self.max_retries}) exhausted")
            self._failed += 1

    # -------------------------------------------------------------- serving
    def step(self) -> int:
        """One dispatch: admit up to ``capacity`` queued requests by
        (priority, arrival) — expiring past-deadline ones — pad the ragged
        remainder with zero arenas, execute the replicated program under
        retry/watchdog, detect injected poison, complete the survivors.
        Returns how many completed successfully.

        Each phase is a host span in the profiler's trace:
        ``repro.serving.admit`` (pop and expire), ``.stage`` (arenas and
        pads), ``.launch`` (stack, transfer, enqueue) and ``.wait``
        (device), once per attempt, and ``.extract`` (outputs)."""
        if not self._queue:
            return 0
        ex = self.executor
        with _span("admit"):
            now = self._clock()
            admitted, expired = self._queue.pop_ready(self.capacity, now)
            for req in expired:
                self._results[req.rid] = RequestError(
                    req.rid, "expired",
                    f"deadline {req.deadline:.6f} passed at {now:.6f}")
        if not admitted:
            return 0
        self._admitted += len(admitted)
        with _span("stage"):
            stack = [ex.make_arena(req.inputs) for req in admitted]
            n_pad = self.capacity - len(stack)
            if n_pad:
                pad = ex.pad_arena()
                stack.extend([pad] * n_pad)
                self._padded += n_pad

        # the pmap path does not donate, but the single-device fallback's
        # batched_fn does — re-stacking per attempt keeps retry safe in
        # both (the per-lane arenas in ``stack`` are never donated)
        def dispatch():
            with _span("launch"):
                batch = np.stack(stack).reshape(
                    (self.replicas, self.lanes, ex.arena_size))
                arenas = self._fn(batch)
            with _span("wait"):
                jax.block_until_ready(arenas)
            return arenas

        try:
            arenas, r, w = dispatch_with_retry(
                dispatch, faults=self._faults,
                max_retries=self.max_retries,
                dispatch_timeout=self.dispatch_timeout, clock=self._clock)
        except DispatchFailedError as e:
            for req in admitted:
                self._results[req.rid] = RequestError(
                    req.rid, "dispatch_failed", str(e))
            self._failed += len(admitted)
            self._retried += getattr(e, "retried", self.max_retries)
            self._trips += getattr(e, "watchdog_trips", 0)
            return 0
        self._retried += r
        self._trips += w
        self._dispatches += 1
        t_done = self._clock()
        with _span("extract"):
            return self._extract(admitted, arenas, t_done)

    def _extract(self, admitted: List[QueuedRequest], arenas,
                 t_done: float) -> int:
        """Complete the admitted requests from a dispatch's ``[R, L]``
        arenas; returns how many completed successfully."""
        ex = self.executor
        lane_faults = (self._faults is not None
                       and self._faults.plan.any_lane_faults())
        if not lane_faults and not ex.guard_regions:
            # production path: every lane's outputs in one device op and
            # one transfer, cut per lane as read-only host views; lanes
            # i >= len(admitted) are pads: never returned
            outs = ex.outputs_from_block(self._out_fn(arenas), len(admitted))
            for i, (req, out) in enumerate(zip(admitted, outs)):
                self._results[req.rid] = out
                self._latencies.append(t_done - req.t_submit)
                self._per_replica[i // self.lanes] += 1
            self._batched_extracts += 1
            self._completed += len(admitted)
            return len(admitted)

        # fault/guard path: work on a writable host copy (np.asarray of a
        # jax buffer is a read-only view — the device buffer is never
        # mutated), inject per-lane poison, then detect and resolve
        host = np.array(arenas)
        corrupt = set()
        if lane_faults:
            corrupt = set(self._faults.corrupt_lanes(len(admitted)))
            for i in corrupt:
                r_, b_ = divmod(i, self.lanes)
                self._faults.corrupt_arena(host[r_, b_], ex.guard_regions)
            for i in self._faults.nan_lanes(len(admitted)):
                if i in corrupt:
                    continue
                r_, b_ = divmod(i, self.lanes)
                self._faults.inject_nan(host[r_, b_], ex)
        done = 0
        for i, req in enumerate(admitted):
            r_, b_ = divmod(i, self.lanes)
            lane = host[r_, b_]
            code = self._detect_lane(lane, i in corrupt)
            if code is not None:
                self._resolve_poisoned(req, code)
                continue
            self._results[req.rid] = ex.outputs_from(lane)
            self._latencies.append(t_done - req.t_submit)
            self._per_replica[r_] += 1
            done += 1
        self._completed += done
        return done

    def take(self, rid: int):
        """The completed result for ``rid`` (pops it): an outputs dict, or
        a typed ``RequestError`` for expired/shed/failed requests."""
        return self._results.pop(rid)

    def drain(self) -> Dict[int, Any]:
        """Step until the queue is empty; returns {rid: result} for every
        result completed and not yet taken (outputs dicts and typed
        ``RequestError`` entries), and records serve stats — including the
        failure-layer counters — over the window since the first
        un-drained submit."""
        while self._queue:
            self.step()
        wall = (self._clock() - self._t_first_submit
                if self._t_first_submit is not None else 0.0)
        self.stats.record_serve(
            requests=self._completed, padded_lanes=self._padded,
            dispatches=self._dispatches, wall_s=wall,
            latencies_s=self._latencies)
        self.stats.admitted = self._admitted
        self.stats.expired = self._queue.expired
        self.stats.shed = self._queue.shed
        self.stats.retried = self._retried
        self.stats.failed = self._failed
        self.stats.watchdog_trips = self._trips
        self.stats.batched_extracts = self._batched_extracts
        self.stats.degraded = list(self._degraded) or None
        self.stats.replica_requests = list(self._per_replica)
        self._per_replica = [0] * self.replicas
        self._completed = 0
        self._admitted = 0
        self._retried = 0
        self._failed = 0
        self._trips = 0
        self._batched_extracts = 0
        self._dispatches = 0
        self._padded = 0
        self._latencies = []
        self._queue.expired = 0
        self._queue.shed = 0
        self._t_first_submit = None
        out, self._results = self._results, {}
        return out

    # -------------------------------------------------------- one-shot API
    def serve(self, requests: Sequence[Dict[str, Any]]
              ) -> List[Dict[str, Any]]:
        """Submit every request, drain, return outputs in request order
        (same contract as ``GraphServingEngine.serve``)."""
        rids = [self.submit(r) for r in requests]
        done = self.drain()
        return [done[rid] for rid in rids]
