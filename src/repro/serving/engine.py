"""Serving engines over deployed graphs (single-device tier).

``GraphServingEngine`` serves CNN computation graphs through a
``repro.deploy.Deployment`` (schedule → plan → validate → compile in one
facade call): requests run in **micro-batches** — each batch vmaps the
compiled arena program over a [B, arena_size] stack of arenas, so B
inferences share one XLA dispatch.  A ragged final batch is padded with
explicit all-zero arenas: pad lanes are executed (one compiled shape for
the whole serve loop instead of an XLA recompile per remainder size) but
are **accounted separately** (``stats.padded_lanes``) and never extracted
— they are not requests, and per-request stats never count them.

For replica-sharded continuous batching see ``serving/sharded.py``; both
engines report the same typed ``EngineStats`` (``serving/stats.py``).

``ServingEngine`` runs prefill + greedy decode over batches of LLM
requests.  The paper's contribution shows up at two levels (DESIGN.md §2,
L1/L2):

* **L1 — operator reordering of the decode step**: the jitted step function
  is traced and its jaxpr equations re-scheduled with the paper's algorithm;
  the engine reports the peak-liveness delta (on TPU, XLA re-schedules after
  us, so the simulated liveness is the contract — same accounting the paper
  uses for TFLite).

* **L2 — KV-block arena planning**: each admitted request owns a KV block
  whose lifetime is [admission, completion).  Blocks live in one HBM arena
  managed either by the paper's §4 dynamic allocator (first-fit + defrag,
  online) or by the §6 offline ``ArenaPlanner`` when the request schedule is
  known (batch mode).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.allocator import DynamicAllocator
from repro.core.graph import Graph
from repro.core.jaxpr_reorder import reorder_closed_jaxpr
from repro.models.model import Model, init_cache
from repro.serving.stats import EngineStats


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [S] int32
    max_new_tokens: int = 16


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: List[int]
    prefill_ms: float
    decode_ms: float


def kv_block_bytes(cfg: ModelConfig, cache_len: int) -> int:
    """Per-request KV/state bytes at full cache length (batch=1)."""
    c = jax.eval_shape(lambda: init_cache(cfg, 1, cache_len))
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(c))


class GraphServingEngine:
    """Micro-batched single-device serving of a deployed CNN graph.

    Construct from a graph (the facade runs schedule→plan→compile) or pass
    an existing ``deployment=`` to share one compiled program between
    engines.  ``serve`` runs micro-batches of ``micro_batch`` vmap lanes;
    ``stats`` is a typed ``EngineStats``.
    """

    def __init__(self, graph: Optional[Graph] = None, *,
                 deployment=None, arena_budget: Optional[int] = None,
                 partition: bool = False, micro_batch: int = 8,
                 use_pallas: bool = False, faults=None,
                 max_retries: int = 2,
                 dispatch_timeout: Optional[float] = None):
        if deployment is None:
            if graph is None:
                raise ValueError("need a graph or a deployment")
            from repro.deploy import build
            deployment = build(graph, arena_budget=arena_budget,
                               partition=partition, use_pallas=use_pallas)
        self.deployment = deployment
        # failure layer (DESIGN.md §12): seeded fault injection + bounded
        # retry/watchdog around each micro-batch dispatch.  All off by
        # default — the no-fault path adds zero work per dispatch.
        from repro.serving.faults import FaultInjector, FaultPlan
        self.faults = (FaultInjector(faults)
                       if isinstance(faults, FaultPlan) else faults)
        self.max_retries = int(max_retries)
        self.dispatch_timeout = dispatch_timeout
        # aliases kept from the pre-facade engine API
        self.result = deployment.schedule_result
        self.exec_graph = deployment.exec_graph
        self.plan = deployment.plan
        self.executor = deployment.executor
        self.micro_batch = micro_batch
        self._batched = self.executor.batched_fn()
        self.stats = EngineStats(
            arena_bytes=int(self.plan.arena_size),
            schedule_peak_bytes=int(self.result.peak),
            schedule_method=self.result.method,
            replicas=1, lanes=micro_batch)

    def serve(self, requests: Sequence[Dict[str, Any]]
              ) -> List[Dict[str, Any]]:
        """Run every request's input dict through the compiled graph;
        returns one output dict per request, in order."""
        from repro.serving.faults import dispatch_with_retry
        ex = self.executor
        results: List[Dict[str, Any]] = []
        latencies: List[float] = []
        padded = 0
        n_batches = 0
        retried = 0
        trips = 0
        t_start = time.perf_counter()
        for i in range(0, len(requests), self.micro_batch):
            chunk = requests[i:i + self.micro_batch]
            stack = [ex.make_arena(r) for r in chunk]
            # pad a ragged tail up to micro_batch with explicit zero
            # arenas: one compiled shape for the whole serve loop instead
            # of one XLA compile (seconds on MobileNet-scale graphs) per
            # distinct remainder size.  Pad lanes are executed but are
            # not requests: counted in stats.padded_lanes, never
            # extracted, never in per-request latency.
            n_pad = self.micro_batch - len(chunk)
            if n_pad:
                pad = ex.pad_arena()
                stack.extend([pad] * n_pad)
                padded += n_pad
            # the jitted batch fn donates its input, so each retry attempt
            # must re-stack from the (undonated) per-lane arenas
            arenas, r, w = dispatch_with_retry(
                lambda s=stack: self._batched(np.stack(s)),
                faults=self.faults, max_retries=self.max_retries,
                dispatch_timeout=self.dispatch_timeout)
            retried += r
            trips += w
            n_batches += 1
            if ex.guard_regions:          # guard-byte debug mode only
                for b in range(len(chunk)):
                    ex.verify_guards(arenas[b])
            for b in range(len(chunk)):       # pad lanes b >= len(chunk)
                results.append(ex.outputs_from(arenas[b]))   # skipped here
            t_done = time.perf_counter()
            # one-shot serve admits everything at t_start, so a request's
            # latency is its batch's completion time
            latencies.extend([t_done - t_start] * len(chunk))
        wall = time.perf_counter() - t_start
        self.stats.record_serve(requests=len(requests), padded_lanes=padded,
                                dispatches=n_batches, wall_s=wall,
                                latencies_s=latencies)
        self.stats.admitted = len(requests)
        self.stats.retried = retried
        self.stats.watchdog_trips = trips
        return results


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 cache_len: int = 128, mesh=None,
                 execute_reordered: bool = False,
                 hbm_budget: Optional[int] = None):
        self.cfg = cfg
        self.model = Model(cfg, mesh)
        self.params = params
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.execute_reordered = execute_reordered
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, cache_len=cache_len))
        self._decode = jax.jit(self.model.decode_step)
        # ---- L2: KV arena (virtual HBM bookkeeping)
        self.block_bytes = kv_block_bytes(cfg, cache_len)
        self.arena = DynamicAllocator(capacity=hbm_budget)
        self.reorder_report = None
        self.stats = EngineStats(lanes=max_batch)

    # --------------------------------------------------------- L1 reorder
    def analyse_decode_schedule(self, batch_size: int):
        """Trace the decode step, apply the paper's scheduler to its jaxpr,
        record the liveness report.  Returns the report."""
        cache = jax.eval_shape(
            lambda: init_cache(self.cfg, batch_size, self.cache_len))
        toks = jax.ShapeDtypeStruct((batch_size,), jnp.int32)
        closed = jax.make_jaxpr(
            lambda p, c, t: self.model.decode_step(p, c, t))(
            self.params, cache, toks)
        _, rep = reorder_closed_jaxpr(closed)
        self.reorder_report = rep
        return rep

    # ------------------------------------------------------------ serving
    def serve(self, requests: Sequence[Request]) -> List[RequestResult]:
        """Batch-mode serving: admit up to max_batch requests at a time.
        All prompts in a batch are right-aligned to the longest one."""
        results: List[RequestResult] = []
        pending = list(requests)
        peak_concurrent = 0
        t_start = time.perf_counter()
        latencies: List[float] = []
        n_batches = 0
        while pending:
            batch = pending[:self.max_batch]
            pending = pending[self.max_batch:]
            # L2: allocate a KV block per admitted request
            for r in batch:
                self.arena.alloc(f"req{r.rid}", self.block_bytes)
            peak_concurrent = max(peak_concurrent, len(batch))
            results.extend(self._run_batch(batch))
            n_batches += 1
            t_done = time.perf_counter()
            latencies.extend([t_done - t_start] * len(batch))
            for r in batch:
                self.arena.free(f"req{r.rid}")
            self.arena.defragment()
        wall = time.perf_counter() - t_start
        self.stats.record_serve(requests=len(requests), padded_lanes=0,
                                dispatches=n_batches, wall_s=wall,
                                latencies_s=latencies)
        self.stats.kv_arena_peak_bytes = self.arena.stats.peak_bytes
        self.stats.kv_static_bytes = self.block_bytes * len(requests)
        self.stats.peak_concurrent = peak_concurrent
        return results

    def _run_batch(self, batch: Sequence[Request]) -> List[RequestResult]:
        cfg = self.cfg
        B = len(batch)
        S = max(len(r.prompt) for r in batch)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(batch):       # left-pad with token 0
            toks[i, S - len(r.prompt):] = r.prompt
        feed = {"tokens": jnp.asarray(toks)}
        if cfg.num_patch_tokens:
            feed["patches"] = jnp.zeros(
                (B, cfg.num_patch_tokens, cfg.frontend_dim), jnp.float32)
        if cfg.arch_type == "audio":
            feed["frames"] = jnp.zeros(
                (B, cfg.encoder_seq, cfg.frontend_dim), jnp.float32)
        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, feed)
        logits.block_until_ready()
        t_pre = (time.perf_counter() - t0) * 1e3

        max_new = max(r.max_new_tokens for r in batch)
        out = [[] for _ in batch]
        t0 = time.perf_counter()
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for step in range(max_new):
            for i, r in enumerate(batch):
                if step < r.max_new_tokens:
                    out[i].append(int(tok[i]))
            if step == max_new - 1:
                break
            logits, cache = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        t_dec = (time.perf_counter() - t0) * 1e3
        return [RequestResult(r.rid, out[i], t_pre, t_dec)
                for i, r in enumerate(batch)]
