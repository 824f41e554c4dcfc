"""The one traffic generator: it reads a mix's data file and drives an
engine through ``submit``, ``step`` and ``take``.

A mix (``chipbench/traffic/<name>.json``) is one of two kinds:

* ``backlog`` — offline work: before every ``step`` the queue is topped up
  to ``pending_lanes`` dispatches' worth of requests, so the device never
  waits for arrivals.  Every request that a step inside the window served
  counts.
* ``open_loop`` — independent clients: arrivals at a rate fixed in the
  cell, whatever the engine does.  ``phases`` is a cycle of
  ``{"seconds": s, "rate_of_knee": r}`` (``seconds`` null: the whole
  window); a phase holds exactly ``round(r * knee_rps * s)`` arrivals at
  uniform random times, so every seed offers the same load in another
  order (a Poisson process, conditioned on its count).  Each request is
  timed from its due time, every request due in the window counts, and
  the queue is drained after the window.

Requests cycle through a pool of ``pool`` distinct images drawn from the
seed.  Host spans around the engine calls are written into the profiler's
trace (``jax.profiler.TraceAnnotation``), where the per-layer readers find
them on the same clock as the device's operations.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, List, Optional

import numpy as np

import jax


@dataclasses.dataclass
class Served:
    """What one window did, in host-clock seconds from its start."""

    pool_index: np.ndarray       # per request: which pool image
    due: np.ndarray              # when it was due (open loop) or submitted
    submitted: np.ndarray
    step_start: np.ndarray       # start of the step that served it
    done: np.ndarray             # end of that step (nan: never served)
    answers: List[Any]           # int8 logits, or None when none came
    errors: List[str]            # what came in place of an answer
    steps: List[tuple]           # (start, end, admitted) per step
    window_s: float              # from the window's start to its last step
    late_s: np.ndarray           # per open-loop request: submitted - due


def pool_images(cfg: dict, n: int, seed: int) -> np.ndarray:
    """``n`` distinct float images (standard normals) drawn from ``seed``."""
    r = cfg["resolution"]
    rng = np.random.default_rng([seed, 0])
    return rng.standard_normal((n, r, r, cfg["input_channels"]),
                               dtype=np.float32)


def arrivals(mix: dict, knee_rps: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Sorted due times in [0, seconds) for an open-loop mix."""
    out, t = [], 0.0
    while t < seconds:
        for ph in mix["phases"]:
            span = seconds if ph["seconds"] is None else ph["seconds"]
            span = min(span, seconds - t)
            n = int(round(ph["rate_of_knee"] * knee_rps * span))
            out.append(t + np.sort(rng.uniform(0.0, span, n)))
            t += span
            if t >= seconds:
                break
    return np.concatenate(out) if out else np.zeros(0)


def _take(engine, rid: int):
    try:
        res = engine.take(rid)
    except KeyError:
        return None, "no result"
    if isinstance(res, dict):
        (val,) = res.values()
        return np.asarray(val), ""
    return None, f"{type(res).__name__}: {res}"


def drive(engine, pool: List[dict], mix: dict, *, knee_rps: Optional[float],
          seconds: float, seed: int,
          clock=time.perf_counter) -> Served:
    """Run one window of ``mix`` against ``engine`` (already warm)."""
    cap = engine.capacity
    rng = np.random.default_rng([seed, 1])
    if mix["kind"] == "open_loop":
        sched = arrivals(mix, knee_rps, seconds, rng)
    elif mix["kind"] == "backlog":
        sched = None
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")

    pool_index: List[int] = []
    due: List[float] = []
    submitted: List[float] = []
    step_start: List[float] = []
    done: List[float] = []
    answers: List[Any] = []
    errors: List[str] = []
    steps: List[tuple] = []
    queue: collections.deque = collections.deque()   # (request, rid)

    def submit(t_due: float) -> None:
        i = len(pool_index)
        pool_index.append(i % len(pool))
        rid = engine.submit(pool[i % len(pool)])
        t = clock() - t0
        due.append(t if t_due is None else t_due)
        submitted.append(t)
        for lst, v in ((step_start, np.nan), (done, np.nan)):
            lst.append(v)
        answers.append(None)
        errors.append("not served")
        queue.append((i, rid))

    def step() -> None:
        k = min(len(queue), cap)
        with jax.profiler.TraceAnnotation("chipbench.step"):
            ts = clock() - t0
            engine.step()
            te = clock() - t0
        steps.append((ts, te, k))
        for _ in range(k):
            i, rid = queue.popleft()
            step_start[i], done[i] = ts, te
            answers[i], errors[i] = _take(engine, rid)

    t0 = clock()
    if sched is None:
        depth = int(mix["pending_lanes"]) * cap
        while clock() - t0 < seconds:
            with jax.profiler.TraceAnnotation("chipbench.submit"):
                while len(queue) < depth:
                    submit(None)
            step()
        # requests still queued were never due: they leave the record
        n = len(pool_index) - len(queue)
    else:
        nxt = 0
        while nxt < len(sched) or queue:
            now = clock() - t0
            if nxt < len(sched) and sched[nxt] <= now:
                with jax.profiler.TraceAnnotation("chipbench.submit"):
                    while nxt < len(sched) and sched[nxt] <= now:
                        submit(float(sched[nxt]))
                        nxt += 1
            elif queue:
                step()
            else:
                with jax.profiler.TraceAnnotation("chipbench.wait"):
                    time.sleep(max(0.0, sched[nxt] - now - 2e-4))
                    while clock() - t0 < sched[nxt]:
                        pass
        n = len(pool_index)
    arr = (lambda xs: np.asarray(xs[:n], np.float64))
    return Served(
        pool_index=np.asarray(pool_index[:n], np.int64),
        due=arr(due), submitted=arr(submitted), step_start=arr(step_start),
        done=arr(done), answers=answers[:n], errors=errors[:n],
        steps=steps, window_s=steps[-1][1] if steps else 0.0,
        late_s=(arr(submitted) - arr(due)) if sched is not None
        else np.zeros(0))
