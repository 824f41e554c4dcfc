"""Serving package: micro-batched and sharded continuous-batching engines.

Submodules are imported lazily (PEP 562) so that ``force_host_devices``
can be imported and called **before anything initialises jax** — the CPU
replica mesh only exists if ``--xla_force_host_platform_device_count=N``
is in ``XLA_FLAGS`` at first jax init (SNIPPETS.md Snippet 2)::

    from repro.serving import force_host_devices
    force_host_devices(4)           # must precede the first jax import
    import repro.deploy as deploy   # ... now jax sees 4 host devices
"""
from __future__ import annotations

import importlib
import os
import sys

_FLAG = "--xla_force_host_platform_device_count"


def force_host_devices(n: int) -> None:
    """Put ``--xla_force_host_platform_device_count=n`` into ``XLA_FLAGS``.

    Only effective before jax initialises its backends; raises if jax has
    already locked in fewer devices (re-exec with the flag set instead —
    ``benchmarks/bench_serving.py`` shows the subprocess recipe).
    """
    n = int(n)
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(_FLAG)]
    os.environ["XLA_FLAGS"] = " ".join(flags + [f"{_FLAG}={n}"])
    if "jax" in sys.modules:
        import jax
        have = jax.local_device_count()
        if have < n:
            raise RuntimeError(
                f"jax already initialised with {have} device(s); call "
                f"force_host_devices({n}) (or export XLA_FLAGS={_FLAG}={n}) "
                f"before the first jax import")


def cpu_platform_requested() -> bool:
    """Whether ``JAX_PLATFORMS`` puts JAX on the CPU, read from the
    environment without initialising JAX.  Only there are replica meshes
    made of virtual host devices, which need ``force_host_devices`` in a
    fresh process; on a TPU the chips belong to the first process that
    initialises JAX, so work that needs them runs in that process."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


_EXPORTS = {
    "GraphServingEngine": ".engine",
    "Request": ".engine",
    "RequestResult": ".engine",
    "ServingEngine": ".engine",
    "kv_block_bytes": ".engine",
    "ShardedServingEngine": ".sharded",
    "EngineStats": ".stats",
    "percentile_ms": ".stats",
    # failure layer (DESIGN.md §12)
    "AdmissionQueue": ".admission",
    "QueuedRequest": ".admission",
    "RequestError": ".admission",
    "FaultPlan": ".faults",
    "FaultInjector": ".faults",
    "dispatch_with_retry": ".faults",
}

__all__ = ["cpu_platform_requested", "force_host_devices"] + sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(mod, __name__), name)


def __dir__():
    return sorted(set(globals()) | set(__all__))
