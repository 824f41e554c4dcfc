"""Where a traced window's time goes, by the program's own spans, scopes
and set-up events; run on the chip, by hand:

    python chipbench/split.py --workload reorder.backlog --seed <n>

It makes the harness's traced run of the cell (``--trace 1``, the same
set-up, window, check and result line), reads the same trace with
``lib/spans.py`` before the harness drops it, and prints one JSON object:
the harness's result, then per step the five ``repro.serving.*`` phases,
the arena program's device time, the rolled loops' device time and the
share of each step the phases cover, the idle gaps named by the innermost
span, device time by scope, and the ``/repro/deploy/*`` phases of set-up.
``--lanes`` replaces the configuration's lanes; ``--record <file>`` also
writes the first three steps' device ops (with scopes) and spans as JSON,
times in ns from the first span, as ``tests/data/`` keeps them.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
RECORD_STEPS = 3


def record(tr, path: str, source: str) -> None:
    """The first ``RECORD_STEPS`` steps of ``tr`` as JSON: ops with their
    scopes and spans, times in ns from the first step's start, under a
    ``chipbench.window`` span that holds them."""
    steps = sorted(tr.host("chipbench.step"))[:RECORD_STEPS]
    lo, hi = steps[0][0], steps[-1][1]

    def ns(t):
        return round((t - lo) * 1e9)
    ops = {str(dev): [[n, ns(t), round(d * 1e9), s]
                      for (n, t, d), s in zip(evs, tr.scopes[dev])
                      if lo <= t < hi]
           for dev, evs in tr.ops.items()}
    spans = [[n, ns(t), round(d * 1e9)] for n, t, d in tr.spans
             if lo <= t < hi]
    spans.insert(0, ["chipbench.window", 0, ns(hi)])
    Path(path).write_text(json.dumps(
        {"source": source, "ops": ops, "spans": spans},
        separators=(",", ":")))


def split_run(cell, *, seed: int, seconds: float, devices: list,
              peaks: dict, t_start: float):
    """The harness's traced run of ``cell`` and the split of its trace;
    returns the summary that ``main`` prints and the ``ProgramTrace``."""
    import jax
    from lib import harness, spans, trace as tracing

    events = []

    def listen(event, secs, **kw):
        if event.startswith(spans.DEPLOY_EVENT):
            events.append((event, secs))
    seen = {}
    harness_load = tracing.load

    def load(trace_dir):             # the harness deletes the directory
        seen["trace"] = spans.load(trace_dir)
        return harness_load(trace_dir)
    jax.monitoring.register_event_duration_secs_listener(listen)
    tracing.load = load
    try:
        out = harness.run(cell, seed=seed, seconds=seconds, trace=True,
                          devices=devices, peaks=peaks, t_start=t_start)
    finally:
        tracing.load = harness_load
        jax.monitoring.unregister_event_duration_listener(listen)
    tr = seen["trace"]
    cover = spans.phase_cover(tr)
    split = {
        "lanes": int(cell.config["lanes"]),
        "steps": len(tr.host("chipbench.step")),
        "phase_ms_per_dispatch": {
            p: spans.phase_ms_per_dispatch(tr, p) for p in spans.PHASES},
        "arena_program_ms_per_dispatch":
            spans.arena_program_ms_per_dispatch(tr),
        "cascade_loop_ms_per_dispatch":
            spans.cascade_loop_ms_per_dispatch(tr),
        "phase_cover_min": min(cover) if cover else None,
        "phase_cover_mean": sum(cover) / len(cover) if cover else None,
        "scoped_ops": sum(bool(s) for v in tr.scopes.values() for s in v),
        "device_ops": len(tr.device_ops()),
        "deploy_s": {p: spans.deploy_s(events, p) for p in sorted(
            {e[len(spans.DEPLOY_EVENT):] for e, _ in events})},
    }
    return {"result": out, "split": split,
            "breakdown": spans.breakdown(tr)}, tr


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--lanes", type=int, default=0)
    ap.add_argument("--record", default="")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from lib import harness

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("split: JAX found no TPU", file=sys.stderr)
        return 1
    cell = harness.load_cell(ROOT, args.workload)
    if args.lanes:
        cell.config["lanes"] = args.lanes
    peaks = json.loads((HERE / "peaks.json").read_text())[dev.device_kind]
    summary, tr = split_run(cell, seed=args.seed, seconds=args.seconds,
                            devices=[dev], peaks=peaks, t_start=T_START)
    if args.record:
        record(tr, args.record,
               f"{args.workload}, {cell.config['lanes']} lanes, seed "
               f"{args.seed}, {dev.device_kind}: XLA Ops (name, start, "
               f"duration, scope) and host spans, ns from the first span")
    print(json.dumps({"workload": args.workload, **summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
