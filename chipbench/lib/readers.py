"""Arithmetic shared by the metric readers under ``metrics/``.

Each reader is a file of its own that calls one of these with its
parameters; each returns ``None`` where the run holds nothing to read.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from . import counting, trace as tracing


def latency_ms(run, q: float) -> Optional[float]:
    """Percentile ``q`` of due-to-answer time over every request due in an
    open-loop window; a request never answered counts as unbounded."""
    s = run.served
    if not run.cell.open_loop or not len(s.due):
        return None
    lat = np.where(np.isnan(s.done), np.inf, s.done - s.due)
    return float(np.percentile(lat, q) * 1e3)


def throughput_rps(run) -> Optional[float]:
    """Requests answered per second over the whole backlog window."""
    s = run.served
    if run.cell.open_loop or s.window_s <= 0:
        return None
    return int(np.sum(~np.isnan(s.done))) / s.window_s


def queue_wait_ms(run, q: float) -> Optional[float]:
    """Percentile of due time to the start of the step that served it."""
    s = run.served
    if not run.cell.open_loop or not len(s.due):
        return None
    wait = (s.step_start - s.due)[~np.isnan(s.step_start)]
    return float(np.percentile(wait, q) * 1e3) if len(wait) else None


def _traced(run) -> bool:
    """A trace in which some operation ran on a device."""
    return run.trace is not None and bool(run.trace.device_ops())


def _steps(run, kind: str) -> Optional[List[Tuple[float, float]]]:
    if not _traced(run) or run.cell.mix["kind"] != kind:
        return None
    steps = run.trace.host("chipbench.step")
    return steps or None


def device_ms_per_dispatch(run, kind: str) -> Optional[float]:
    """Mean device busy time inside each traced step."""
    steps = _steps(run, kind)
    if steps is None:
        return None
    busy = run.trace.busy()
    return float(np.mean([tracing.overlap(busy, st) for st in steps])) * 1e3


def host_ms_per_dispatch(run, kind: str) -> Optional[float]:
    """Mean step wall time less the device busy time inside it."""
    steps = _steps(run, kind)
    if steps is None:
        return None
    busy = run.trace.busy()
    return float(np.mean([(b - a) - tracing.overlap(busy, (a, b))
                          for a, b in steps])) * 1e3


def roofline_pct(run, kernel: str) -> Optional[float]:
    """Least time of the kernel's traced calls over their device time."""
    if not _traced(run) or kernel not in run.calls:
        return None
    dev_s, n_events = run.trace.kernel_s(kernel)
    dispatches = len(run.trace.host("chipbench.step"))
    if n_events == 0 or dev_s <= 0 or dispatches == 0:
        return None
    least, _ = counting.least_time_s(run.calls[kernel],
                                     run.peaks["int8_ops_per_s"],
                                     run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * dispatches / dev_s


def dispatch_mfu_pct(run) -> Optional[float]:
    """Operations of the requests served in the traced window (the
    unrewritten graph's, 2 per multiply-accumulate) over the window times
    the chip's int8 peak."""
    if not _traced(run) or run.trace.window_s <= 0:
        return None
    s = run.served
    served = int(np.sum(~np.isnan(s.done)))
    ops = 2.0 * run.model_macs * served
    return 100.0 * ops / (run.trace.window_s * run.peaks["int8_ops_per_s"])


def idle_pct(run) -> Optional[float]:
    if not _traced(run) or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
