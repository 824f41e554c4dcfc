"""Code files that the harness finds by a name in ``BENCHMARK.json`` or a
configuration: a model (``models/``), the rule of an operator kind
(``kernels/``), a metric's reader (``metrics/``)."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import ModuleType


def load(path: Path, prefix: str) -> ModuleType:
    """Import the file at ``path`` as a module of its own, registered in
    ``sys.modules`` (which ``dataclasses`` looks its module up in)."""
    name = f"chipbench_{prefix}_{path.stem.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod
