"""From a profiler trace to the numbers the per-layer readers take.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes are named ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per operation the device ran, named by its HLO text
(``%qconv1x1.3 = s8[...] custom-call(...)``), of which the instruction's
name is kept.  The harness's own host
spans (``chipbench.*``, written by ``TraceAnnotation``) sit on a host
plane, on the same clock.  Everything here works on plain tuples, so a
small recorded trace checks it (``tests/test_trace.py``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_s, end_s)

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "chipbench."


@dataclasses.dataclass
class Trace:
    """One traced window, times in seconds on the trace's clock."""

    window: Interval
    ops: Dict[int, List[Tuple[str, float, float]]]   # device -> (name, t, dur)
    spans: List[Tuple[str, float, float]]            # host (name, t, dur)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def device_ops(self) -> List[Tuple[str, float, float]]:
        return [e for evs in self.ops.values() for e in evs]

    def busy(self, device: Optional[int] = None) -> List[Interval]:
        """Union of the intervals in which an operation ran."""
        evs = (self.device_ops() if device is None
               else self.ops.get(device, []))
        return union((t, t + d) for _, t, d in evs)

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(overlap(self.busy(dev), self.window)
                   for dev in self.ops) / len(self.ops)

    def host(self, name: str) -> List[Interval]:
        return [(t, t + d) for n, t, d in self.spans if n == name]

    def kernel_s(self, kernel: str) -> Tuple[float, int]:
        """Summed device time and count of a kernel's events."""
        pat = kernel_pattern(kernel)
        hits = [d for n, _, d in self.device_ops() if pat.match(n)]
        return sum(hits), len(hits)


def kernel_pattern(kernel: str) -> re.Pattern:
    """A kernel's events: its name, or its name and a numeric suffix."""
    return re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def overlap(intervals: Sequence[Interval], w: Interval) -> float:
    return sum(max(0.0, min(b, w[1]) - max(a, w[0])) for a, b in intervals)


def gaps(busy: Sequence[Interval], w: Interval) -> List[Interval]:
    """The idle intervals of the window between busy intervals."""
    out, t = [], w[0]
    for a, b in busy:
        if a > t:
            out.append((t, min(a, w[1])))
        t = max(t, b)
        if t >= w[1]:
            break
    if t < w[1]:
        out.append((t, w[1]))
    return [g for g in out if g[1] > g[0]]


def op_name(event_name: str) -> str:
    """``%qconv1x1.3 = s8[...] custom-call(...)`` -> ``qconv1x1.3``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load_file(paths[-1])


def load_file(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        (op_name(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(HOST_PREFIX))
    return from_events(ops, spans)


def from_events(ops: Dict[int, List[Tuple[str, float, float]]],
                spans: List[Tuple[str, float, float]]) -> Trace:
    """The window is the harness's ``chipbench.window`` span."""
    win = [(t, t + d) for n, t, d in spans if n == HOST_PREFIX + "window"]
    if not win:
        raise ValueError("the trace holds no chipbench.window span")
    w = win[0]
    clipped = {dev: [e for e in evs if e[1] < w[1] and e[1] + e[2] > w[0]]
               for dev, evs in ops.items()}
    return Trace(window=w, ops=clipped,
                 spans=[s for s in spans if s[0] != HOST_PREFIX + "window"])


def breakdown(tr: Trace, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time, and the longest idle
    gaps named by the host span that covers most of each.  A loop's event
    spans the operations of its body, which have events of their own, so
    loops are left out of the first list."""
    per: Dict[str, float] = {}
    for n, _, d in tr.device_ops():
        key = re.sub(r"\.\d+$", "", n)
        if key != "while":
            per[key] = per.get(key, 0.0) + d
    ndev = max(1, len(tr.ops))
    device_ops = sorted(([k, v / ndev] for k, v in per.items()),
                        key=lambda kv: -kv[1])[:top]
    dev0 = min(tr.ops) if tr.ops else None
    idle = gaps(tr.busy(dev0), tr.window) if dev0 is not None else []
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    named = []
    for g in idle:
        best, cover = "no span", 0.0
        for n, t, d in tr.spans:
            c = overlap([(t, t + d)], g)
            if c > cover:
                best, cover = n[len(HOST_PREFIX):], c
        named.append([best, g[1] - g[0]])
    return {"device_ops": device_ops, "idle_gaps": named}
