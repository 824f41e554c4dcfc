"""Lane and rate sweeps that fixed each configuration's ``lanes`` and
``knee_rps`` and each open-loop mix's rate; run on the chip, once, by hand:

    python chipbench/sweep.py --config mobilenet_v1_1.0_192_int8.reorder \
        --lanes 8,32,128 --rates 0.6,0.7,0.8,0.9,1.0 --rate-lanes 32

One process builds the configuration's deployment once, from the graph
of its model (``models/<model>.py``).  For each lane count it times the
engine's construction and first dispatches (a cold compile where the
compile cache is empty) and serves a backlog window;
then, at ``--rate-lanes``, it offers open-loop load at each fraction of
that lane count's backlog throughput (the knee).  One JSON object per
measurement goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--lanes", default="8,32,128")
    ap.add_argument("--rates", default="")
    ap.add_argument("--rate-lanes", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=2**31 + 101)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import repro.deploy as deploy
    from lib import harness, traffic

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 1
    cfg = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    model = harness.load_model(cfg["model"])
    t = time.perf_counter()
    d = deploy.build(model.program_graph(cfg), quantize=True,
                     arena_budget=cfg["arena_budget_bytes"],
                     use_pallas=True, strict=True)
    emit(config=args.config, build_s=time.perf_counter() - t,
         arena_bytes=d.arena_bytes, steps=len(d.schedule),
         device=dev.device_kind)
    images = traffic.pool_images(cfg, 128, args.seed)
    (name,) = [c for c in d.graph.constants() if d.graph.consumers(c)]
    pool = [d.quantize_inputs({name: im}) for im in images]
    backlog = {"kind": "backlog", "pool": 128, "pending_lanes": 2}
    engines, knee = {}, {}
    for lanes in [int(x) for x in args.lanes.split(",") if x]:
        t = time.perf_counter()
        eng = d.engine(micro_batch=lanes, replicas=1,
                       fallback_single_device=False)
        for _ in range(2):
            rids = [eng.submit(pool[i % 128]) for i in range(lanes)]
            eng.step()
            for rid in rids:
                eng.take(rid)
        compile_s = time.perf_counter() - t
        s = traffic.drive(eng, pool, backlog, knee_rps=None,
                          seconds=args.seconds, seed=args.seed)
        knee[lanes] = len(s.done) / s.window_s
        dt = [b - a for a, b, _ in s.steps]
        emit(lanes=lanes, compile_s=compile_s, throughput_rps=knee[lanes],
             ms_per_dispatch=float(np.median(dt)) * 1e3,
             dispatches=len(dt))
        engines[lanes] = eng
    if args.rates:
        lanes = args.rate_lanes or max(knee, key=knee.get)
        for frac in [float(x) for x in args.rates.split(",")]:
            mix = {"kind": "open_loop", "pool": 128,
                   "phases": [{"seconds": None, "rate_of_knee": frac}]}
            s = traffic.drive(engines[lanes], pool, mix,
                              knee_rps=knee[lanes], seconds=args.seconds,
                              seed=args.seed)
            lat = (s.done - s.due) * 1e3
            wait = (s.step_start - s.due) * 1e3
            emit(lanes=lanes, rate_of_knee=frac,
                 rate_rps=frac * knee[lanes], requests=len(lat),
                 p50_ms=float(np.percentile(lat, 50)),
                 p99_ms=float(np.percentile(lat, 99)),
                 queue_wait_p50_ms=float(np.percentile(wait, 50)),
                 late_p99_ms=float(np.percentile(s.late_s, 99)) * 1e3,
                 drain_s=s.window_s - args.seconds,
                 mean_batch=float(np.mean([k for _, _, k in s.steps])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
