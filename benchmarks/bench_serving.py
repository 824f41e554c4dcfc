"""Serving throughput: requests/s at measured p50/p99 latency, single-device
micro-batching vs the sharded continuous-batching engine.

The comparison is **weak scaling at fixed per-replica lanes**: the
single-device ``GraphServingEngine`` dispatches ``lanes`` vmap lanes per
XLA call; the ``ShardedServingEngine`` dispatches ``replicas x lanes``
lanes per call across a replica mesh of forced host devices
(``--xla_force_host_platform_device_count``, SNIPPETS.md Snippet 2).
Per-dispatch work per replica is identical, so with >= ``replicas`` real
cores the sharded engine's requests/s scales with the mesh while per
-request p50/p99 stays at single-device levels; on fewer cores the
replicas time-share and the ratio honestly degrades (the row still
reports it).  Output rows:

    serving.<case>.single_rps    us = us/request, derived = requests/s
    serving.<case>.sharded_rps   us = us/request, derived = requests/s
    serving.<case>.speedup_x     derived = sharded / single requests/s

The ``*_rps`` rows carry ``requests_per_s`` (floor-gated by
``benchmarks/compare.py --rps-tol``), ``p50_ms``/``p99_ms``, and the
deterministic ``arena_bytes`` of the deployment (strict bytes gate).
Outputs are checked bit-identical to one-shot ``Deployment.run`` before
any timing is reported.

On the CPU (``JAX_PLATFORMS=cpu``) the whole benchmark runs in a fresh
subprocess: the replica mesh of virtual host devices only exists if
XLA_FLAGS is set before the first jax import, which the parent (run.py)
process has long since done.  ``REPRO_SERVING_DEVICES`` sets the mesh size
(default 4; the CI smoke row uses 2).  On a TPU it runs in the calling
process, over the chips that process holds (a child could not get them).

Smoke mode (REPRO_BENCH_SMOKE=1): MobileNet-0.25@96 int8 only.  Full mode
adds the headline MobileNet-1.0@192 int8 deployment and, when the host
has at least ``replicas`` cores, asserts the >=2x scale-out bar.
"""
import json
import os
import subprocess
import sys
import time

_SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"
_ROW_TAG = "SERVINGROW "


# --------------------------------------------------------- subprocess side
def _print_row(name, us, derived, **meta):
    print(_ROW_TAG + json.dumps(
        {"name": name, "us": us, "derived": derived, "meta": meta}))


def _bench_case(case: str, graph, qmodel, *, replicas: int, lanes: int,
                n_requests: int, use_pallas: bool, emit=_print_row):
    import numpy as np

    import repro.deploy as deploy
    from repro.graphs import random_input
    from repro.serving import GraphServingEngine, ShardedServingEngine

    d = deploy.build(qmodel.graph if qmodel else graph,
                     use_pallas=use_pallas)
    reqs = [random_input(graph, seed=i) for i in range(n_requests)]
    if qmodel:
        reqs = [qmodel.quantize_inputs(r) for r in reqs]
    outs = graph.outputs if qmodel is None else qmodel.graph.outputs
    refs = [d.run(reqs[0]), d.run(reqs[-1])]   # bit-identity anchors

    def check(results):
        for got, ref in ((results[0], refs[0]), (results[-1], refs[1])):
            for t in outs:
                np.testing.assert_array_equal(ref[t], got[t])

    single = GraphServingEngine(deployment=d, micro_batch=lanes)
    single.serve(reqs[:2 * lanes])             # warm: compiles jit(vmap)
    check(single.serve(reqs))
    s = single.stats

    sharded = ShardedServingEngine(d, replicas=replicas, lanes=lanes)
    sharded.serve(reqs[:2 * sharded.capacity])  # warm: compiles pmap(vmap)
    check(sharded.serve(reqs))
    h = sharded.stats

    meta = dict(arena_bytes=d.arena_bytes, dtypes="int8")

    def row(name, us, derived, **extra):
        emit(name, us, derived, **meta, **extra)

    # expired/shed ride on the *_rps rows and are gated exactly zero by
    # compare.py: this is the no-fault configuration, so any nonzero count
    # is an admission-layer bug, not load (DESIGN.md §12)
    row(f"serving.{case}.single_rps", s.us_per_request,
        round(s.requests_per_s, 1), requests_per_s=round(s.requests_per_s, 2),
        p50_ms=round(s.p50_ms, 2), p99_ms=round(s.p99_ms, 2),
        expired=s.expired, shed=s.shed)
    row(f"serving.{case}.sharded_rps", h.us_per_request,
        round(h.requests_per_s, 1), requests_per_s=round(h.requests_per_s, 2),
        p50_ms=round(h.p50_ms, 2), p99_ms=round(h.p99_ms, 2),
        replicas=h.replicas, expired=h.expired, shed=h.shed)
    speedup = h.requests_per_s / s.requests_per_s if s.requests_per_s else 0.0
    row(f"serving.{case}.speedup_x", h.us_per_request, round(speedup, 2))
    return speedup


def _main(emit=_print_row, on_host: bool = True):
    replicas = int(os.environ.get("REPRO_SERVING_DEVICES", "4"))
    import jax

    from repro.graphs import mobilenet_v1_graph, quantize_graph, random_input

    have = jax.local_device_count()
    if not on_host:             # real chips: as many replicas as there are
        replicas = min(replicas, have)
    elif have < replicas:
        raise SystemExit(f"forced host mesh missing: {have} devices, "
                         f"wanted {replicas} (XLA_FLAGS not set pre-init?)")

    g = mobilenet_v1_graph()                  # 0.25@96
    q = quantize_graph(g, random_input(g))
    t0 = time.time()
    _bench_case("mobilenet_025_96_int8", g, q, replicas=replicas,
                lanes=2, n_requests=8 * replicas, use_pallas=True,
                emit=emit)
    print(f"# smoke case done in {time.time() - t0:.1f}s", file=sys.stderr)
    if _SMOKE:
        return
    g = mobilenet_v1_graph(alpha=1.0, resolution=192)
    q = quantize_graph(g, random_input(g))
    speedup = _bench_case("mobilenet_100_192_int8", g, q,
                          replicas=replicas, lanes=2,
                          n_requests=4 * replicas, use_pallas=True,
                          emit=emit)
    # the scale-out bar is physical: host replicas can only run
    # concurrently on >= that many cores.  Time-shared hosts report, but
    # don't gate; neither do chips, which this benchmark does not time.
    if on_host and (os.cpu_count() or 1) >= replicas:
        assert speedup >= 2.0, (
            f"sharded engine only {speedup:.2f}x over single-device "
            f"({replicas} replicas on {os.cpu_count()} cores)")


# ------------------------------------------------------------- parent side
def run(report):
    """On the CPU, spawn the benchmark in a fresh process with the replica
    mesh forced (2 devices in smoke mode, 4 otherwise) and re-report its
    rows; anywhere else run it here, on this process's devices."""
    from repro.serving import cpu_platform_requested
    if not cpu_platform_requested():
        _main(emit=report, on_host=False)
        return
    env = dict(os.environ)
    env.setdefault("REPRO_SERVING_DEVICES", "2" if _SMOKE else "4")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = (os.path.abspath(src) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-m", "benchmarks.bench_serving"],
                          capture_output=True, text=True, env=env)
    for line in proc.stdout.splitlines():
        if line.startswith(_ROW_TAG):
            r = json.loads(line[len(_ROW_TAG):])
            report(r["name"], r["us"], r["derived"], **r["meta"])
    if proc.returncode != 0:
        raise RuntimeError(
            f"serving subprocess failed:\n{proc.stdout}\n{proc.stderr}")


if __name__ == "__main__":
    # the mesh must be forced before jax initialises; repro.serving is
    # import-safe (lazy submodules) so this works pre-jax
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "src"))
    from repro.serving import force_host_devices

    force_host_devices(int(os.environ.get("REPRO_SERVING_DEVICES", "4")))
    _main()
