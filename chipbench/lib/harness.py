"""One run of one cell: set-up, the measured window, the check, the line.

``run`` takes a loaded ``Cell`` and the devices to use, and returns the
result object that ``run.py`` prints.  It does not look for a chip itself:
``run.py`` refuses anything but a TPU before calling it, and the CPU
rehearsal in ``tests/`` calls it directly at a small size.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import counting, loader, reference, trace as tracing, traffic

HERE = Path(__file__).resolve().parents[1]          # chipbench/
MODELS = HERE / "models"
# a traced run's window: traces of the arena programs are large and slow
# to read, and tracing slows the host, so per-layer metrics come from a
# few seconds of their own
TRACE_SECONDS = 4.0


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: dict
    model: ModuleType           # models/<config's model>.py
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def open_loop(self) -> bool:
        return self.mix["kind"] == "open_loop"


def load_model(name: str, models: Path = MODELS) -> ModuleType:
    """The model file ``<models>/<name>.py``.  It gives what the harness
    takes from a model: ``program_graph(cfg)``, the program's graph and
    the only call into the program; ``quantize_model(cfg, *,
    weight_bits=8)``, whose result has ``quantize_input(images)``;
    ``int8_forward(qm)``, the plain reference's jitted int8 forward; and
    ``model_macs(cfg)``, multiply-accumulates of one image."""
    path = models / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model {name!r}: {models} holds no "
                                f"{name}.py")
    return loader.load(path, "model")


def load_cell(root: Path, workload: str, *, models: Path = MODELS) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(ms):
        return [m for m in ms if workload in m.get("workloads", [workload])]
    return Cell(workload, int(w["chips"]), config,
                load_model(config["model"], models), mix,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


@dataclasses.dataclass
class RunData:
    """Everything a metric's reader may look at."""

    cell: Cell
    setup: Dict[str, float]         # setup_s, build_s, compile_s
    served: traffic.Served
    trace: Optional[tracing.Trace]
    calls: Dict[str, List[counting.Call]]   # kernel calls of one dispatch
    peaks: dict
    model_macs: int                 # per image, unrewritten graph


def reader(name: str) -> Callable[[RunData], Optional[float]]:
    return loader.load(HERE / "metrics" / f"{name}.py", "metric").read


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        devices: list, peaks: dict, t_start: float) -> Dict[str, Any]:
    import jax
    import repro.deploy as deploy

    cfg, mix, model = cell.config, cell.mix, cell.model
    lanes = int(cfg["lanes"])
    dev = devices[0]

    # ------------------------------------------------------------ set-up
    t = time.perf_counter()
    d = deploy.build(model.program_graph(cfg), quantize=True,
                     arena_budget=cfg["arena_budget_bytes"],
                     use_pallas=True, strict=True)
    build_s = time.perf_counter() - t
    if d.degraded or d.executor.device != dev:
        raise RuntimeError(f"deployment degraded ({d.degraded}) or not on "
                           f"{dev} (on {d.executor.device})")
    images = traffic.pool_images(cfg, int(mix["pool"]), seed)
    (name,) = [c for c in d.graph.constants() if d.graph.consumers(c)]
    pool = [d.quantize_inputs({name: im}) for im in images]

    t = time.perf_counter()
    eng = d.engine(micro_batch=lanes, replicas=1,
                   fallback_single_device=False)
    for _ in range(2):              # the first compiles, the second checks
        rids = [eng.submit(pool[i % len(pool)]) for i in range(lanes)]
        eng.step()
        for rid in rids:
            if not isinstance(eng.take(rid), dict):
                raise RuntimeError("warm-up request failed")
    compile_s = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    calls = counting.kernel_calls(d.exec_graph, d.schedule, lanes)
    log(f"[setup] cell={cell.name} arena_bytes={d.arena_bytes} "
        f"steps={len(d.schedule)} method={d.schedule_result.method} "
        f"lanes={lanes} build_s={build_s} compile_s={compile_s} "
        f"setup_s={setup_s} kernel_calls="
        f"{ {k: len(v) for k, v in calls.items()} }")

    # ------------------------------------------------------------ window
    compiles = []        # backend compiles while the window runs: none due
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    tmp = None
    if trace:
        tmp = tempfile.TemporaryDirectory()
        jax.profiler.start_trace(tmp.name)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            served = traffic.drive(
                eng, pool, mix, knee_rps=cfg.get("knee_rps"),
                seconds=min(seconds, TRACE_SECONDS) if trace else seconds,
                seed=seed)
    finally:
        if trace:
            jax.profiler.stop_trace()
    n_compiles = len(compiles)
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    del eng, d, pool
    gc.collect()
    tr = None
    if trace:
        t = time.perf_counter()
        tr = tracing.load(tmp.name)
        tmp.cleanup()
        bounds = {k: counting.least_time_s(
            v, peaks["int8_ops_per_s"], peaks["hbm_bytes_per_s"])[1]
            for k, v in calls.items()}
        log(f"[trace] read_s={time.perf_counter() - t} window_s="
            f"{tr.window_s} device_ops={len(tr.device_ops())} "
            f"steps={len(tr.host('chipbench.step'))} kernel_events="
            f"{ {k: tr.kernel_s(k)[1] for k in calls} } "
            f"kernel_bound={bounds}")
    n = len(served.answers)
    log(f"[window] requests={n} steps={len(served.steps)} "
        f"window_s={served.window_s} compiles={n_compiles}")
    if cell.open_loop and n:
        log(f"[generator] late_ms p50={np.percentile(served.late_s, 50) * 1e3}"
            f" p99={np.percentile(served.late_s, 99) * 1e3} "
            f"max={served.late_s.max() * 1e3}")

    # ------------------------------------------------------------ check
    t = time.perf_counter()
    qm = model.quantize_model(cfg)
    want = reference.logits(model.int8_forward(qm),
                            qm.quantize_input(images))
    nums = reference.compare(served.answers, want[served.pool_index])
    log(f"[check] reference_s={time.perf_counter() - t} images={len(images)}")
    failed = sum(a is None for a in served.answers)
    if failed:
        log(f"[check] first failure: "
            f"{next(e for e in served.errors if e)}")
    checks = {k: {"value": v, "limit": 0} for k, v in nums.items()}
    correct = n > 0 and all(c["value"] <= c["limit"]
                            for c in checks.values())

    data = RunData(cell, {"setup_s": setup_s, "build_s": build_s,
                          "compile_s": compile_s},
                   served, tr, calls, peaks, model.model_macs(cfg))
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(data)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct), "attempted": n, "failed": failed,
           "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = tracing.breakdown(tr)
    out["checks"] = checks
    for k, c in checks.items():
        log(f"[check] {k}={c['value']} limit={c['limit']}")
    return out
