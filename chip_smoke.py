"""Smoke run of the deployment path on TPU chips.

One chip (the default): MobileNet-v1 1.0@192 int8 is deployed at two SRAM
rungs with the fused int8 Pallas kernels —
``deploy.build(graph, quantize=True, arena_budget=..., use_pallas=True)``
for the 224 KB 2-D tile rung and for the reorder-only rung — and seeded
requests are served through ``Deployment.engine(...)``.  Every int8 output
is checked bit-for-bit against the default lowering (XLA's own int32
convolutions) of the same schedule and plan on the chip, and the first and
last requests also against the interpret-mode program on the host CPU.
The lowered program must hold every fused kernel as a ``tpu_custom_call``
and no convolution left to XLA; no degradation note may appear.

``--replicas 4`` (four chips): the 224 KB rung through the replica engine
(``engine(replicas=4)``) against the one-chip engine, and nothing else.

Exits non-zero on any failure and when JAX finds no TPU.  The last line of
standard output is one JSON object naming the device::

    python chip_smoke.py
    python chip_smoke.py --replicas 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
BUDGET_2D = 224 * 1024        # the golden 2-D tile rung
ARENA_2D = 221696             # its exact arena (tests/test_golden.py)
# MobileNet's fused kernels: the 3x3 stem, the pointwise and the
# depthwise convs (kernels/conv_quant/kernel.py names them)
KERNELS = {"qconv", "qconv1x1", "qdwconv"}
N_REQUESTS = 17               # per rung: two full dispatches and a tail
LANES = 8                     # vmap lanes per dispatch
SEED = 0


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CacheEvents:
    """Counts JAX's persistent compilation cache hits and misses."""

    def __init__(self, jax):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def lowered_text(executor, lanes: int) -> str:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    spec = jax.ShapeDtypeStruct(
        (lanes, executor.arena_size), jnp.uint8,
        sharding=SingleDeviceSharding(executor.device))
    return executor.batched_fn().jitted.lower(spec).as_text()


def requests(d, n: int, seed: int):
    """``n`` seeded float images, quantized at the deployment's edge."""
    rng = np.random.default_rng(seed)
    name = next(c for c in d.graph.constants() if d.graph.consumers(c))
    shape = d.graph.tensors[name].shape
    return [d.quantize_inputs(
        {name: rng.standard_normal(shape).astype(np.float32)})
        for _ in range(n)]


@dataclasses.dataclass
class Served:
    outs: list
    engine: object
    dispatches: int = 0
    padded_lanes: int = 0
    per_replica: list = dataclasses.field(default_factory=list)
    first_s: float = 0.0      # compile + the first dispatch
    rest_s: float = 0.0


def serve(d, reqs, *, lanes: int, replicas: int = 1) -> Served:
    """Serve ``reqs`` through the sharded engine with no single-device
    fallback: one full dispatch first (it compiles), then the rest."""
    eng = d.engine(micro_batch=lanes, replicas=replicas,
                   fallback_single_device=False)
    res = Served([], eng, per_replica=[0] * eng.replicas)
    for part in (reqs[:eng.capacity], reqs[eng.capacity:]):
        t0 = time.perf_counter()
        res.outs += eng.serve(part)
        dt = time.perf_counter() - t0
        if res.first_s:
            res.rest_s += dt
        else:
            res.first_s = dt
        st = eng.stats
        check(st.requests == len(part),
              f"served {st.requests} of {len(part)} requests")
        check(not st.degraded, f"engine degraded: {st.degraded}")
        res.dispatches += st.dispatches
        res.padded_lanes += st.padded_lanes
        res.per_replica = [a + b for a, b in
                           zip(res.per_replica, st.replica_requests)]
    for i, o in enumerate(res.outs):
        check(isinstance(o, dict), f"request {i} failed: {o!r}")
    return res


def same(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(
        a[k].dtype == np.int8 and b[k].dtype == np.int8
        and np.array_equal(a[k], b[k]) for k in a)


def rung(graph, budget, *, cache: CacheEvents) -> None:
    import jax
    import repro.deploy as deploy
    from repro.mcu import compile_schedule

    tag = f"budget {budget // 1024} KB" if budget else "reorder-only"
    t0 = time.perf_counter()
    d = deploy.build(graph, quantize=True, arena_budget=budget,
                     use_pallas=True)
    t_search = time.perf_counter() - t0
    ex = d.executor
    check(not d.degraded, f"{tag}: deployment degraded: {d.degraded}")
    check(ex.device.platform == "tpu" and ex.use_pallas
          and not ex.interpret,
          f"{tag}: not a Mosaic program on the chip ({ex.device}, "
          f"interpret={ex.interpret})")
    if budget == BUDGET_2D:
        check(d.arena_bytes == ARENA_2D,
              f"{tag}: arena {d.arena_bytes} B, golden {ARENA_2D} B")
    text = lowered_text(ex, LANES)
    n_custom = text.count("@tpu_custom_call")
    found = set(re.findall(r'kernel_name = "(\w+)"', text))
    check(KERNELS <= found,
          f"{tag}: fused kernels {sorted(KERNELS - found)} missing from "
          f"the lowered program (found {sorted(found)})")
    check("stablehlo.convolution" not in text,
          f"{tag}: a convolution was left to XLA in the use_pallas program")

    reqs = requests(d, N_REQUESTS, SEED)
    hits0, misses0 = cache.hits, cache.misses
    got = serve(d, reqs, lanes=LANES)
    outs = got.outs

    # reference 1: the default lowering of the same schedule and plan
    ref = dataclasses.replace(
        d, executor=compile_schedule(d.exec_graph, d.schedule, d.plan))
    t1 = time.perf_counter()
    ref_outs = serve(ref, reqs, lanes=LANES).outs
    t_ref = time.perf_counter() - t1
    bad = [i for i, (a, b) in enumerate(zip(outs, ref_outs))
           if not same(a, b)]
    check(not bad, f"{tag}: requests {bad} differ from the XLA lowering")

    # reference 2: the interpret-mode program on the host CPU
    cpu_ex = compile_schedule(d.exec_graph, d.schedule, d.plan,
                              use_pallas=True,
                              device=jax.devices("cpu")[0])
    check(cpu_ex.interpret, f"{tag}: CPU reference is not interpret mode")
    t2 = time.perf_counter()
    cpu_ids = (0, len(reqs) - 1)
    for i in cpu_ids:
        check(same(outs[i], cpu_ex.run(reqs[i])),
              f"{tag}: request {i} differs from the host-CPU run")
    t_cpu = time.perf_counter() - t2

    log(f"[{tag}] method={d.schedule_result.method} "
        f"arena_bytes={d.arena_bytes} steps={len(d.schedule)} "
        f"search_s={t_search:.1f} compile_and_first_dispatch_s="
        f"{got.first_s:.1f} rest_s={got.rest_s:.2f} "
        f"tpu_custom_calls={n_custom} kernels={','.join(sorted(found))}")
    log(f"[{tag}] served={len(outs)} lanes={LANES} "
        f"dispatches={got.dispatches} padded_lanes="
        f"{got.padded_lanes} bit_identical_vs_xla_lowering="
        f"{len(outs)}/{len(outs)} (xla program s={t_ref:.1f}) "
        f"bit_identical_vs_host_cpu={len(cpu_ids)}/{len(cpu_ids)} "
        f"(cpu s={t_cpu:.1f}) degraded=none "
        f"cache_hits={cache.hits - hits0} cache_misses="
        f"{cache.misses - misses0}")


def replicas_smoke(graph, *, replicas: int) -> None:
    import jax
    import repro.deploy as deploy

    check(len(jax.devices()) >= replicas,
          f"--replicas {replicas} needs {replicas} chips, JAX sees "
          f"{len(jax.devices())}")
    d = deploy.build(graph, quantize=True, arena_budget=BUDGET_2D,
                     use_pallas=True)
    check(not d.degraded, f"deployment degraded: {d.degraded}")
    # two full dispatches plus a ragged one
    reqs = requests(d, 2 * replicas * LANES + 3, SEED)
    got = serve(d, reqs, lanes=LANES, replicas=replicas)
    check(got.engine.stats.replicas == replicas,
          f"engine runs {got.engine.stats.replicas} replicas, asked "
          f"{replicas}")
    devices = {dev.id for dev in got.engine.devices}
    check(len(devices) == replicas and all(got.per_replica),
          f"requests per replica {got.per_replica} on devices "
          f"{sorted(devices)}")
    one = serve(d, reqs, lanes=LANES)
    bad = [i for i, (a, b) in enumerate(zip(got.outs, one.outs))
           if not same(a, b)]
    check(not bad, f"requests {bad} differ from the one-chip engine")
    n = len(got.outs)
    log(f"[replicas] replicas={replicas} devices={sorted(devices)} "
        f"requests_per_replica={got.per_replica} served={n} "
        f"dispatches={got.dispatches} compile_and_first_dispatch_s="
        f"{got.first_s:.1f} rest_s={got.rest_s:.2f} "
        f"bit_identical_vs_one_chip={n}/{n} (one-chip "
        f"compile_and_first_dispatch_s={one.first_s:.1f})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--replicas", type=int, default=1,
                    help="> 1: run only the replica engine on that many "
                         "chips against the one-chip engine")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "deploy.py").is_file():
        print(f"chip_smoke: no repro sources under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default backend: "
              f"{dev.platform}); this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    cache = CacheEvents(jax)
    n_entries = (sum(1 for _ in Path(cache_dir).iterdir())
                 if Path(cache_dir).is_dir() else 0)
    log(f"device={dev.device_kind} count={len(jax.devices())} "
        f"jax={jax.__version__} compile_cache={cache_dir} "
        f"entries_before={n_entries}")

    from repro.graphs import mobilenet_v1_graph
    graph = mobilenet_v1_graph(1.0, 192)
    t0 = time.perf_counter()
    try:
        if args.replicas > 1:
            replicas_smoke(graph, replicas=args.replicas)
        else:
            for budget in (BUDGET_2D, None):
                rung(graph, budget, cache=cache)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total_s={time.perf_counter() - t0:.1f} cache_hits={cache.hits} "
        f"cache_misses={cache.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
