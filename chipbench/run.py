"""Chip benchmark of served int8 MCU deployments on TPU.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on: builds
the deployment of the configuration's model (``models/<model>.py``) through
``deploy.build``, warms its engine, drives it with the cell's traffic for
``--seconds``, checks every answer against that model's plain int8
reference (with ``lib/reference.py``), and prints one JSON object as
the last line of standard output.  With ``--trace 0`` the object holds the
cell's end-to-end metrics; with ``--trace 1`` the window is traced and it
holds the per-layer metrics, which the readers under ``metrics/`` take from
the trace and the harness's own spans.

It exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind that ``peaks.json`` lacks, when
the program's sources are not in the checkout, and (code 2) when the cell
or its configuration's model file cannot be loaded.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is timed from the process start

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def fail(msg: str, code: int = 1) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "deploy.py").is_file():
        return fail(f"no program sources under {ROOT / 'src'}", 2)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    from lib import harness

    try:
        cell = harness.load_cell(ROOT, args.workload)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load workload {args.workload!r}: {e}", 2)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # keep every program, however quick to compile, for the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"JAX found no TPU (default backend {dev.platform}); "
                    f"this benchmark runs on the chip only")
    if len(devices) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chips, JAX sees "
                    f"{len(devices)}")
    peaks = json.loads((HERE / "peaks.json").read_text())
    if dev.device_kind not in peaks:
        return fail(f"no peaks for device kind {dev.device_kind!r} in "
                    f"peaks.json")
    out = harness.run(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices[:cell.chips],
                      peaks=peaks[dev.device_kind], t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
