"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any jax init).

Production target: TPU v5e, 256 chips/pod as a (16, 16) ("data", "model")
mesh; two pods as (2, 16, 16) ("pod", "data", "model").  Batch shards over
("pod", "data"); "model" carries TP/expert sharding.
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — the dry-run must set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            f"any jax import")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def make_local_mesh(model_parallel: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests)."""
    import jax
    devs = jax.devices()
    m = min(model_parallel, len(devs))
    d = len(devs) // m
    return jax.make_mesh((d, m), ("data", "model"), devices=devs[:d * m])
