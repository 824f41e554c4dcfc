"""Operations and bytes of the fused int8 kernels, from the deployed
schedule's operator shapes.

Every scheduled ``qconv``/``qdwconv`` is one kernel call per dispatch, and
a call covers all ``L`` lanes of the dispatch.  Its operations are
2 x multiply-accumulates x L; its least bytes are each lane's input and
output activations plus the int8 weights once.  The kernel that serves an
operator follows the lowering's rule: a 1x1, stride-1 ``qconv`` with no
explicit padding is ``qconv1x1``, any other ``qconv`` is ``qconv``, and a
``qdwconv`` is ``qdwconv``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

Call = Tuple[int, int]                   # (operations, bytes) of one call


def _unpadded(pads) -> bool:
    return pads is None or tuple(pads) == (0, 0)


def kernel_of(kind: str, attrs: dict) -> str:
    if kind == "qdwconv":
        return "qdwconv"
    if (attrs["k"] == 1 and attrs["stride"] == 1
            and _unpadded(attrs.get("pex_pads"))
            and _unpadded(attrs.get("pex_wpads"))):
        return "qconv1x1"
    return "qconv"


def call_cost(kind: str, attrs: dict, in_shape, out_shape, lanes: int
              ) -> Call:
    """(operations, least bytes) of one kernel call over ``lanes``."""
    oh, ow, cout = out_shape
    k = attrs["k"]
    if kind == "qdwconv":
        macs = oh * ow * cout * k * k
    else:
        macs = oh * ow * cout * k * k * in_shape[-1]
    act = math.prod(in_shape) + math.prod(out_shape)      # int8: 1 B each
    return 2 * macs * lanes, act * lanes + int(attrs["weight_q"].nbytes)


def kernel_calls(graph, schedule, lanes: int) -> Dict[str, List[Call]]:
    """Kernel name -> the calls one dispatch makes, in schedule order."""
    out: Dict[str, List[Call]] = {}
    for op in schedule:
        if op.kind not in ("qconv", "qdwconv"):
            continue
        name = kernel_of(op.kind, op.attrs)
        out.setdefault(name, []).append(call_cost(
            op.kind, op.attrs, tuple(graph.tensors[op.inputs[0]].shape),
            tuple(graph.tensors[op.output].shape), lanes))
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
