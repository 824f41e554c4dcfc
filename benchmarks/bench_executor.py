"""Compiled arena executor vs the Python-loop MicroInterpreter: us/call on
figure1 and MobileNet-{0.5,1.0}@192, reorder-only and reorder+pex, at both
element widths (float32 and post-training int8).

Two interpreter numbers are reported, because they answer different
questions on this (server-CPU) rig:

* ``interp_us`` — the interpreter's first call in this process: the Python
  schedule loop plus per-operator dispatch/tracing.  This is the cost a
  TFLM-style interpreted runtime pays per operator and what the compiled
  executor eliminates — the acceptance bar (>=5x on MobileNet-1.0@192) is
  asserted against it.
* ``interp_warm_us`` — a repeat call after jax's eager dispatch caches are
  hot.  At 192x192 resolution the convolutions dominate and XLA runs them
  the same way in both executors, so this ratio approaches the compute
  floor (~1.4x here); on MCU-class single-shot inference there is no warm
  process to amortise into.

Output rows (all byte figures are bytes; rows carry ``arena_bytes`` and
``dtypes`` metadata into the --json trajectory):
    executor.<case>.interp_us        first interpreter pass (per-op dispatch)
    executor.<case>.interp_warm_us   warm interpreter pass
    executor.<case>.compiled_us      one jitted arena-program call (warm)
    executor.<case>.speedup_x        interp_us / compiled_us (derived)
    executor.<case>.arena_B          the plan the program executes against
    executor.<case>.pallas_us        warm call with the fused int8 kernels
                                     (use_pallas=True; int8 graphs only)
    executor.<case>.pallas_speedup_x default-lowering warm / pallas warm

On the CPU (``JAX_PLATFORMS=cpu``) the MobileNet@192 cases run in a fresh
subprocess (``python -m benchmarks.bench_executor``): earlier benchmarks
in the same process warm jax's eager-dispatch caches for exactly these
shapes, which would silently turn the first-call measurement into a warm
one.  On a TPU they run in the calling process, which holds the chip.

Smoke mode (REPRO_BENCH_SMOKE=1, set by ``run.py --smoke``) keeps only the
small graphs so CI stays fast.
"""
import os
import subprocess
import sys
import time

import numpy as np

import repro.deploy as deploy
from repro.graphs import (figure1_executable_graph, figure1_int8_graph,
                          graph_dtypes, mobilenet_v1_graph, quantize_graph,
                          random_input)
from repro.mcu import MicroInterpreter, compile_schedule

KB = 1024
MB = 1024 * KB
_SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"


def _case(report, name, g, cap=None, repeats=3):
    # the facade runs schedule -> plan -> validate -> compile in one call
    d = deploy.build(g, arena_budget=cap)
    res, plan = d.schedule_result, d.plan
    gp = d.exec_graph
    x = random_input(g)
    dtypes = graph_dtypes(g)

    interp = MicroInterpreter(gp)
    t0 = time.perf_counter()
    rep = interp.run(x, schedule=res.schedule)
    interp_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    rep = interp.run(x, schedule=res.schedule)
    interp_warm_us = (time.perf_counter() - t0) * 1e6

    out = d.run(x)                       # warm-up: traces + compiles
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = d.run(x)
    compiled_us = (time.perf_counter() - t0) * 1e6 / repeats

    for o in g.outputs:                  # the executor must not drift
        np.testing.assert_array_equal(rep.outputs[o], out[o])
    speedup = interp_us / compiled_us
    meta = dict(arena_bytes=int(plan.arena_size), dtypes=dtypes)
    report(f"executor.{name}.interp_us", interp_us, res.peak, **meta)
    report(f"executor.{name}.interp_warm_us", interp_warm_us, res.peak,
           **meta)
    report(f"executor.{name}.compiled_us", compiled_us, plan.arena_size,
           **meta)
    report(f"executor.{name}.speedup_x", compiled_us, round(speedup, 1),
           **meta)
    report(f"executor.{name}.arena_B", compiled_us, plan.arena_size, **meta)
    return speedup


def _pallas_case(report, name, g, cap=None, repeats=3, base_repeats=1):
    """Fused int8 kernels (``use_pallas=True``, DESIGN.md §9) vs the default
    XLA-int32-conv lowering on the *same* schedule and arena plan: warm
    us/call both ways, bit-identity, and the arena-bytes-unchanged
    invariant (the kernels change lowering only, never placement).  The
    default side runs ``base_repeats`` times — it is the slow side by two
    orders of magnitude on conv-heavy int8 graphs."""
    d = deploy.build(g, arena_budget=cap)
    gp, plan = d.exec_graph, d.plan
    x = random_input(g)

    base = d.executor
    fused = compile_schedule(gp, d.schedule, plan, use_pallas=True)
    assert fused.arena_size == base.arena_size == plan.arena_size

    out_base = base.run(x)               # warm-up: traces + compiles
    t0 = time.perf_counter()
    for _ in range(base_repeats):
        out_base = base.run(x)
    base_us = (time.perf_counter() - t0) * 1e6 / base_repeats

    out_fused = fused.run(x)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out_fused = fused.run(x)
    fused_us = (time.perf_counter() - t0) * 1e6 / repeats

    for o in g.outputs:                  # fused kernels must not drift
        np.testing.assert_array_equal(out_base[o], out_fused[o])
    speedup = base_us / fused_us
    meta = dict(arena_bytes=int(plan.arena_size), dtypes=graph_dtypes(g))
    report(f"executor.{name}.pallas_us", fused_us, plan.arena_size, **meta)
    report(f"executor.{name}.pallas_speedup_x", fused_us,
           round(speedup, 1), **meta)
    return speedup


def _quantized_mobilenet(**kw):
    g = mobilenet_v1_graph(**kw)
    return quantize_graph(g, random_input(g)).graph


def _headline_cases(report):
    """The MobileNet@192 sweep; asserts the >=5x acceptance bar.  The f32
    builds carry 4 bytes per element since the byte-granular refactor, so
    the pex budgets are the old element budgets x4; the int8 build is the
    one that meets real MCU byte budgets (see bench_pex)."""
    _case(report, "mobilenet_050_192.reorder",
          mobilenet_v1_graph(alpha=0.5, resolution=192))
    _case(report, "mobilenet_050_192.pex",
          mobilenet_v1_graph(alpha=0.5, resolution=192), cap=1 * MB)
    _case(report, "mobilenet_100_192.reorder",
          mobilenet_v1_graph(alpha=1.0, resolution=192))
    s = _case(report, "mobilenet_100_192.pex",
              mobilenet_v1_graph(alpha=1.0, resolution=192), cap=2 * MB)
    assert s >= 5.0, f"compiled executor only {s:.1f}x over the interpreter"
    # the int8 deployment graph with the fused kernels: the §9 acceptance
    # bar is >=5x warm over the default lowering (measured ~300x)
    sp = _pallas_case(report, "mobilenet_100_192_int8.reorder",
                      _quantized_mobilenet(alpha=1.0, resolution=192))
    assert sp >= 5.0, f"fused int8 kernels only {sp:.1f}x over the lowering"


def _parse_derived(text):
    for cast in (int, float):
        try:
            return cast(text)
        except ValueError:
            continue
    return text


def run(report):
    _case(report, "figure1", figure1_executable_graph(), repeats=20)
    _case(report, "figure1_int8", figure1_int8_graph(), repeats=20)
    _case(report, "mobilenet_025_96", mobilenet_v1_graph())
    _case(report, "mobilenet_025_96_int8", _quantized_mobilenet())
    # fused int8 kernels vs the default lowering on the small int8 build —
    # runs in smoke mode too, so the CI gate always exercises the
    # use_pallas=True compile + bit-identity path
    _pallas_case(report, "mobilenet_025_96_int8", _quantized_mobilenet())
    if _SMOKE:
        return
    from repro.serving import cpu_platform_requested
    if not cpu_platform_requested():
        _headline_cases(report)
        return
    # fresh process: see module docstring
    proc = subprocess.run([sys.executable, "-m", "benchmarks.bench_executor"],
                          capture_output=True, text=True)
    for line in proc.stdout.splitlines():
        if line.startswith("executor."):
            parts = line.split(",")
            report(parts[0], float(parts[1]), _parse_derived(parts[2]),
                   dtypes=parts[3] if len(parts) > 3 else "float32")
    if proc.returncode != 0:
        raise RuntimeError(
            f"headline subprocess failed:\n{proc.stdout}\n{proc.stderr}")


if __name__ == "__main__":
    def _report(name, us_per_call, derived, **meta):
        print(f"{name},{us_per_call:.1f},{derived},"
              f"{meta.get('dtypes', 'float32')}")
    _headline_cases(_report)
