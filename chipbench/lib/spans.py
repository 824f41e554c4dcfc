"""The program's own spans and scopes in a traced window, and the numbers
they give per layer.

The program writes three kinds of marks (PERF.md §3):

* host spans ``repro.serving.<phase>`` around each phase of
  ``ShardedServingEngine.step`` (``admit``, ``stage``, ``launch``,
  ``wait``, ``extract``), by ``jax.profiler.TraceAnnotation`` like the
  harness's own ``chipbench.*`` spans, so on the device trace's clock;
* a ``jax.named_scope`` per operator and per rolled loop (``loop<k>``) of
  the arena program, which reaches each device op's ``op_name``, kept in
  the trace as the ``tf_op`` stat of the op's event metadata;
* ``jax.monitoring`` duration events ``/repro/deploy/<phase>`` from
  ``deploy.build``.

``lib/trace.py`` keeps only the harness's spans and each op's name;
``load_file`` here reads the same ``.xplane.pb`` into a ``ProgramTrace``,
a ``trace.Trace`` that also holds the program's spans and each op's scope
path, so every reader of ``lib/readers.py`` reads it unchanged.  Each
function returns ``None`` where the trace holds nothing to read, as on a
program that writes none of these marks.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import trace as tracing

PREFIXES = ("chipbench.", "repro.")
PHASE = "repro.serving."
PHASES = ("admit", "stage", "launch", "wait", "extract")
DEPLOY_EVENT = "/repro/deploy/"
LOOP = re.compile(r"^loop\d+$")
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")


@dataclasses.dataclass
class ProgramTrace(tracing.Trace):
    """A ``trace.Trace`` whose ``spans`` also hold the program's, with the
    scope path of each device op: ``scopes[dev][i]`` is that of
    ``ops[dev][i]`` ("" where the op carries none)."""

    scopes: Dict[int, List[str]] = dataclasses.field(default_factory=dict)

    def scoped_ops(self) -> List[Tuple[str, float, float, str]]:
        return [e + (s,) for dev, evs in self.ops.items()
                for e, s in zip(evs, self.scopes.get(dev, []))]


def scope_path(tf_op: str) -> str:
    """``jit(f)/vmap(loop0)/while/body/add:`` -> ``loop0/while/body/add``:
    an op's ``op_name`` (``tf_op``, ending in ``:``) less its program, each
    transform's wrapper (``vmap(...)``) taken off the scope it wraps."""
    out = []
    for p in tf_op.rstrip(":").split("/")[1:]:
        m = _WRAPPED.match(p)
        while m:
            p = m.group(1)
            m = _WRAPPED.match(p)
        out.append(p)
    return "/".join(out)


def _fields(buf: bytes, i: int, end: int):
    """The (field number, value) pairs of a protobuf message in
    ``buf[i:end]``; a length-delimited value is its ``(start, end)``."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def op_scopes(xspace: bytes) -> Dict[str, str]:
    """Each device op's HLO text -> its ``tf_op`` (its ``op_name``
    metadata), from an ``.xplane.pb``.  The trace keeps ``tf_op`` as a stat
    of the op's event metadata, which ``ProfileData`` does not expose, so
    this reads the fields of ``XSpace`` it needs: ``planes`` (1); of an
    ``XPlane``, ``name`` (2), ``event_metadata`` (4) and ``stat_metadata``
    (5), maps whose entries hold a key (1) and a value (2); of an
    ``XEventMetadata``, ``name`` (2) and ``stats`` (5); of an ``XStat``,
    ``metadata_id`` (1), ``str_value`` (5) and ``ref_value`` (7); of an
    ``XStatMetadata``, ``name`` (2)."""
    def text(span):
        return xspace[span[0]:span[1]].decode("utf-8", "replace")

    def entry(span):
        got = dict(_fields(xspace, *span))
        return got.get(1), got.get(2)
    out: Dict[str, str] = {}
    for f, plane in _fields(xspace, 0, len(xspace)):
        if f != 1:
            continue
        fs = list(_fields(xspace, *plane))
        name = next((text(v) for k, v in fs if k == 2), "")
        if not tracing.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for k, v in fs:
            if k == 5:
                key, meta = entry(v)
                stat_names[key] = next(
                    (text(n) for g, n in _fields(xspace, *meta) if g == 2),
                    "")
        for k, v in fs:
            if k != 4:
                continue
            op, tf_op = None, None
            for g, w in _fields(xspace, *entry(v)[1]):
                if g == 2:
                    op = text(w)
                elif g == 5:
                    stat = dict(_fields(xspace, *w))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = (text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if op is not None and tf_op:
                out[op] = tf_op
    return out


def load(trace_dir: str) -> ProgramTrace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load_file(paths[-1])


def load_file(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    scopes = op_scopes(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    ops: Dict[int, List[Tuple[str, float, float, str]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        (tracing.op_name(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9,
                         scope_path(scopes.get(e.name, "")))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(PREFIXES))
    return from_events(ops, spans)


def from_events(ops: Dict[int, List[Tuple[str, float, float, str]]],
                spans: List[Tuple[str, float, float]]) -> ProgramTrace:
    """As ``trace.from_events``, each op with its scope path last."""
    tr = tracing.from_events(ops, spans)
    return ProgramTrace(
        window=tr.window, spans=tr.spans,
        ops={d: [e[:3] for e in evs] for d, evs in tr.ops.items()},
        scopes={d: [e[3] for e in evs] for d, evs in tr.ops.items()})


# ------------------------------------------------------------- per step
def _steps(tr: tracing.Trace) -> List[tracing.Interval]:
    return tr.host("chipbench.step")


def _inside(spans: Sequence[tracing.Interval], step: tracing.Interval
            ) -> List[tracing.Interval]:
    return [s for s in spans if step[0] <= s[0] < step[1]]


def phase_ms_per_dispatch(tr: tracing.Trace, phase: str) -> Optional[float]:
    """Mean over the traced steps of the time in ``repro.serving.<phase>``
    spans inside each."""
    steps, spans = _steps(tr), tr.host(PHASE + phase)
    if not steps or not spans:
        return None
    return float(np.mean([sum(b - a for a, b in _inside(spans, st))
                          for st in steps])) * 1e3


def phase_cover(tr: tracing.Trace) -> Optional[List[float]]:
    """Per traced step, the share of its wall time that the five phase
    spans inside it cover."""
    steps = _steps(tr)
    spans = [s for p in PHASES for s in tr.host(PHASE + p)]
    if not steps or not spans:
        return None
    return [tracing.overlap(tracing.union(_inside(spans, st)), st)
            / (st[1] - st[0]) for st in steps]


def arena_program_ms_per_dispatch(tr: tracing.Trace) -> Optional[float]:
    """Mean device busy time inside each step's ``launch`` and ``wait``
    spans: the arena program with its upload, without the per-lane
    extraction ops that follow in ``extract``."""
    steps = _steps(tr)
    spans = tr.host(PHASE + "launch") + tr.host(PHASE + "wait")
    if not steps or not spans or not tr.device_ops():
        return None
    busy = tr.busy()
    return float(np.mean([
        sum(tracing.overlap(busy, s)
            for s in tracing.union(_inside(spans, st)))
        for st in steps])) * 1e3


def cascade_loop_ms_per_dispatch(tr: ProgramTrace) -> Optional[float]:
    """Mean device time per step of the ops under a ``loop<k>`` scope (the
    rolled cascade loops), as the union of their intervals."""
    loops = [(t, t + d) for _, t, d, s in tr.scoped_ops()
             if any(LOOP.match(p) for p in s.split("/"))]
    steps = _steps(tr)
    if not loops or not steps:
        return None
    busy = tracing.union(loops)
    return float(np.mean([tracing.overlap(busy, st) for st in steps])) * 1e3


def deploy_s(events: Sequence[Tuple[str, float]], phase: str
             ) -> Optional[float]:
    """Summed seconds of the ``/repro/deploy/<phase>`` events of a run."""
    secs = [s for e, s in events if e == DEPLOY_EVENT + phase]
    return float(sum(secs)) if secs else None


# ------------------------------------------------------------- breakdown
def span_label(name: str) -> str:
    """A span's name less its own prefix: ``chipbench.step`` -> ``step``,
    ``repro.serving.extract`` -> ``serving.extract``."""
    for p in PREFIXES:
        if name.startswith(p):
            return name[len(p):]
    return name


def gap_name(tr: tracing.Trace, gap: tracing.Interval) -> str:
    """The innermost span that covers most of ``gap``: of the spans over
    it that hold no other span over it, the one that covers the most of
    it, ties to the shorter.  (A step holds its phases, so a gap that
    runs from one step's extraction into the next step's staging goes to
    the larger of its parts, not to the step.)"""
    over = [(t, t + d, n) for n, t, d in tr.spans
            if tracing.overlap([(t, t + d)], gap) > 0]
    inner = [o for o in over if not any(
        p != o and o[0] <= p[0] and p[1] <= o[1] for p in over)]
    if not inner:
        return "no span"
    a, b, n = max(inner, key=lambda o: (
        tracing.overlap([o[:2]], gap), o[0] - o[1]))
    return span_label(n)


def top_scopes(tr: ProgramTrace, top: int = 10) -> List[list]:
    """Device time by the first part of each op's scope path: an operator
    or a rolled loop of the arena program, the primitive of a one-op
    program (an extraction's ``dynamic_slice``), "" where the op has no
    ``tf_op``.  Loops' own ``while`` events are left out: their bodies'
    ops have events of their own."""
    per: Dict[str, float] = {}
    for n, _, d, s in tr.scoped_ops():
        if re.sub(r"\.\d+$", "", n) != "while":
            k = s.split("/", 1)[0]
            per[k] = per.get(k, 0.0) + d
    ndev = max(1, len(tr.ops))
    return sorted(([k, v / ndev] for k, v in per.items()),
                  key=lambda kv: -kv[1])[:top]


def breakdown(tr: ProgramTrace, top: int = 10) -> Dict[str, list]:
    """``trace.breakdown``'s device ops, the longest idle gaps named by
    ``gap_name``, and ``top_scopes``."""
    dev0 = min(tr.ops) if tr.ops else None
    idle = tracing.gaps(tr.busy(dev0), tr.window) if dev0 is not None else []
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": tracing.breakdown(tr, top)["device_ops"],
            "idle_gaps": [[gap_name(tr, g), g[1] - g[0]] for g in idle],
            "scopes": top_scopes(tr, top)}
