"""A cell cut to a size the CPU runs in seconds, for the tests."""
from __future__ import annotations

import time

from conftest import ROOT
from lib import harness

SMALL = dict(alpha=0.25, resolution=96, lanes=4, knee_rps=40.0)
PEAKS = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def small_cell(workload: str, **config) -> harness.Cell:
    cell = harness.load_cell(ROOT, workload)
    cell.config.update(SMALL, **config)
    cell.mix["pool"] = 8
    return cell


def run_small(workload: str, *, seconds: float = 1.0, trace: bool = False,
              seed: int = 2**31 + 5, **config) -> dict:
    import jax
    return harness.run(small_cell(workload, **config), seed=seed,
                       seconds=seconds, trace=trace,
                       devices=jax.devices("cpu")[:1], peaks=PEAKS,
                       t_start=time.perf_counter())
