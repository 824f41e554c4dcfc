"""Operations and bytes of the kernels a dispatch calls, from the deployed
schedule's operator shapes, and the least time they can take.

Every scheduled operator whose kind has a rule file,
``chipbench/kernels/<op kind>.py``, is one kernel call per dispatch, and a
call covers all ``L`` lanes of the dispatch.  The rule file says which
kernel serves the operator, ``kernel_of(attrs)``, and what one call costs,
``call_cost(attrs, in_shapes, out_shape, lanes) -> (operations, bytes)``,
where ``in_shapes`` holds the shape of every activation input.  An
operator whose kind has no rule file calls no counted kernel and is
skipped.
"""
from __future__ import annotations

from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional, Tuple

from . import loader

KERNELS = Path(__file__).resolve().parents[1] / "kernels"

Call = Tuple[int, int]                   # (operations, bytes) of one call


def rule(kind: str) -> Optional[ModuleType]:
    """The rule file of an operator kind, or ``None`` where it has none."""
    path = KERNELS / f"{kind}.py"
    if not path.is_file():
        return None
    return loader.load(path, "kernel")


def kernel_calls(graph, schedule, lanes: int) -> Dict[str, List[Call]]:
    """Kernel name -> the calls one dispatch makes, in schedule order."""
    rules: Dict[str, Optional[ModuleType]] = {}
    out: Dict[str, List[Call]] = {}
    for op in schedule:
        if op.kind not in rules:
            rules[op.kind] = rule(op.kind)
        r = rules[op.kind]
        if r is None:
            continue
        in_shapes = tuple(tuple(graph.tensors[t].shape) for t in op.inputs)
        out.setdefault(r.kernel_of(op.attrs), []).append(r.call_cost(
            op.attrs, in_shapes, tuple(graph.tensors[op.output].shape),
            lanes))
    return out


def least_time_s(calls: List[Call], peak_ops: float, peak_bw: float
                 ) -> Tuple[float, str]:
    """Least seconds the calls can take on a chip with these peaks, and
    which bound sets most of it (``compute`` or ``memory``)."""
    t_c = sum(max(o / peak_ops, b / peak_bw) for o, b in calls
              if o / peak_ops >= b / peak_bw)
    t_m = sum(max(o / peak_ops, b / peak_bw) for o, b in calls
              if o / peak_ops < b / peak_bw)
    return t_c + t_m, "compute" if t_c >= t_m else "memory"
