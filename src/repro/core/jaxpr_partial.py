"""Partial execution (Pex-style) for jaxprs.

``jaxpr_reorder`` applies the paper's operator reordering to jaxpr
equations; this module applies its sequel's transform: a chain of eligible
equations is split into K row-slices so the chain's interior tensors only
ever exist one slice at a time.  The rewritten jaxpr computes each output
slice with ``slice_p`` extracts + cloned equations, then writes it into a
shared accumulator with ``dynamic_update_slice`` — which XLA updates in
place when safe, and which ``jaxpr_to_graph`` marks ``inplace`` so the
liveness model charges the output buffer exactly once.

Eligible equations (split along the leading axis of the output):

* shape-preserving **elementwise** primitives (every non-scalar operand
  shares the output shape);
* **dot_general** whose lhs leading axis is a free (non-contracted,
  non-batch) dimension — slicing lhs rows slices output rows, the rhs is
  consumed whole (weights);
* **reduce_{sum,max,min,prod}** over axes not containing the leading axis.

All three have identity row-maps (no halo), so slicing costs no recompute.
Numerics: elementwise and reduce clones are bit-identical (slices copy bits
and per-element reduction order is unchanged); a sliced ``dot_general`` may
differ from the whole op within float accumulation tolerance (~1 ulp per
contraction step), because XLA's GEMM kernel selection — and with it the
K-dimension blocking order — depends on the row count.  The MCU graph path
(``core/partition.py``) keeps strict bit-identity; this jaxpr pass trades it
for the liveness win on matmul chains, which is the right call on TPU-class
backends where reductions are never bit-stable across tilings anyway.

The transform is conservative: anything it does not recognise leaves the
jaxpr unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.extend import core as jcore
from jax._src.core import (ShapedArray, Var, check_jaxpr, new_jaxpr_eqn,
                           no_effects)
from jax._src import source_info_util
from jax._src.lax import lax as lax_internal
from jax._src.lax import slicing as lax_slicing

from .graph import linear_chains
from .jaxpr_reorder import aval_bytes, jaxpr_to_graph

Literal = jcore.Literal

ELEMENTWISE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "integer_pow", "rem",
    "neg", "abs", "sign", "exp", "expm1", "log", "log1p", "sqrt", "rsqrt",
    "cbrt", "tanh", "logistic", "erf", "sin", "cos", "tan", "sinh", "cosh",
    "floor", "ceil", "round", "convert_element_type", "select_n", "square",
    "and", "or", "xor", "not", "gt", "lt", "ge", "le", "eq", "ne",
})
REDUCE_PRIMS = frozenset({"reduce_sum", "reduce_max", "reduce_min",
                          "reduce_prod"})


@dataclasses.dataclass(frozen=True)
class EqnSlice:
    sliced: Tuple[int, ...]   # invar positions sliced along their leading axis


def eqn_sliceable(eqn) -> Optional[EqnSlice]:
    """Row-slice policy of an equation, or None when it cannot be split."""
    if eqn.effects or len(eqn.outvars) != 1:
        return None
    out = eqn.outvars[0]
    aval = getattr(out, "aval", None)
    shape = tuple(getattr(aval, "shape", ()) or ())
    if len(shape) < 1 or shape[0] < 2:
        return None
    name = eqn.primitive.name
    if name in ELEMENTWISE_PRIMS:
        sliced = []
        for pos, v in enumerate(eqn.invars):
            if isinstance(v, Literal):
                if np.shape(v.val) == ():
                    continue                      # scalar literal: keep as-is
                return None
            vshape = tuple(v.aval.shape)
            if vshape == shape:
                sliced.append(pos)
            elif vshape == ():
                continue
            else:
                return None                       # implicit broadcast: skip
        return EqnSlice(tuple(sliced)) if sliced else None
    if name == "dot_general":
        v = eqn.invars[0]
        if isinstance(v, Literal):
            return None
        (lc, _), (lb, rb) = eqn.params["dimension_numbers"]
        # out dim 0 is the lhs leading axis only when there are no batch
        # dims and that axis is free
        if lb or rb or 0 in lc or v.aval.shape[0] != shape[0]:
            return None
        return EqnSlice((0,))
    if name in REDUCE_PRIMS:
        v = eqn.invars[0]
        if isinstance(v, Literal) or 0 in eqn.params.get("axes", ()):
            return None
        if v.aval.shape[0] != shape[0]:
            return None
        return EqnSlice((0,))
    return None


def _find_runs(jaxpr) -> List[List[int]]:
    """Maximal runs (length >= 2) of sliceable equations along the linear
    chains of the jaxpr's scheduling graph, where each link enters its
    consumer only at sliced positions."""
    g, eqn_index = jaxpr_to_graph(jaxpr)
    runs: List[List[int]] = []
    for chain in linear_chains(g):
        cur: List[int] = []
        for node in chain:
            if node.name not in eqn_index:
                if len(cur) >= 2:
                    runs.append(cur)
                cur = []
                continue
            k = eqn_index[node.name]
            eqn = jaxpr.eqns[k]
            spec = eqn_sliceable(eqn)
            ok = spec is not None
            if ok and cur:
                prev_out = jaxpr.eqns[cur[-1]].outvars[0]
                positions = [p for p, v in enumerate(eqn.invars)
                             if v is prev_out]
                ok = bool(positions) and all(p in spec.sliced
                                             for p in positions)
            if ok:
                cur.append(k)
            else:
                if len(cur) >= 2:
                    runs.append(cur)
                cur = [k] if spec is not None else []
        if len(cur) >= 2:
            runs.append(cur)
    return runs


def _estimate_run(eqns: Sequence, k: int,
                  shard_divisor: int = 1) -> Tuple[int, int]:
    """(estimated local peak after splitting into k slices, before) — in the
    same per-device units the caller's budget uses."""
    def nbytes(aval):
        return aval_bytes(aval, shard_divisor)

    internal = {id(e.outvars[0]) for e in eqns}
    ext, seen = 0, set()
    for e in eqns:
        for v in e.invars:
            if isinstance(v, Literal) or id(v) in internal or id(v) in seen:
                continue
            seen.add(id(v))
            ext += nbytes(v.aval)
    out_b = nbytes(eqns[-1].outvars[0].aval)
    slice_live = before = 0
    for e in eqns:
        spec = eqn_sliceable(e)
        assert spec is not None
        whole = nbytes(e.outvars[0].aval) + sum(
            nbytes(v.aval) for v in e.invars
            if not isinstance(v, Literal))
        before = max(before, whole)
        step = -(-nbytes(e.outvars[0].aval) // k)
        for pos in spec.sliced:
            step += -(-nbytes(e.invars[pos].aval) // k)
        slice_live = max(slice_live, step)
    return ext + out_b + slice_live, before


def _src():
    return source_info_util.new_source_info()


def _expand_run(eqns: Sequence, k: int) -> List:
    """Replacement equations: zeros accumulator + per-slice extracts, clones
    and a dynamic_update_slice writing the slice into the accumulator.  The
    final update's outvar is the original output var, so consumers are
    untouched."""
    out = eqns[-1].outvars[0]
    oshape, odtype = tuple(out.aval.shape), out.aval.dtype
    h = oshape[0]
    bounds = [(s * h) // k for s in range(k + 1)]
    acc_aval = ShapedArray(oshape, odtype)
    idx_aval = ShapedArray((), np.dtype("int32"))
    res: List = []
    zero = Literal(np.zeros((), odtype), ShapedArray((), odtype))
    acc: object = Var(acc_aval)
    res.append(new_jaxpr_eqn(
        [zero], [acc], lax_internal.broadcast_in_dim_p,
        dict(shape=oshape, broadcast_dimensions=(), sharding=None),
        no_effects, _src()))
    ext_slices: Dict[Tuple[int, int, int], Var] = {}
    for s in range(k):
        a, b = bounds[s], bounds[s + 1]
        clone_out: Dict[int, Var] = {}
        for d, eqn in enumerate(eqns):
            spec = eqn_sliceable(eqn)
            assert spec is not None
            ins = []
            for pos, v in enumerate(eqn.invars):
                if pos not in spec.sliced or isinstance(v, Literal):
                    ins.append(v)
                    continue
                if d > 0 and v is eqns[d - 1].outvars[0]:
                    ins.append(clone_out[d - 1])
                    continue
                key = (id(v), a, b)
                if key not in ext_slices:
                    vshape = tuple(v.aval.shape)
                    sv = Var(ShapedArray((b - a,) + vshape[1:],
                                         v.aval.dtype))
                    res.append(new_jaxpr_eqn(
                        [v], [sv], lax_slicing.slice_p,
                        dict(start_indices=(a,) + (0,) * (len(vshape) - 1),
                             limit_indices=(b,) + vshape[1:], strides=None),
                        no_effects, _src()))
                    ext_slices[key] = sv
                ins.append(ext_slices[key])
            o = eqn.outvars[0]
            co = Var(ShapedArray((b - a,) + tuple(o.aval.shape)[1:],
                                 o.aval.dtype))
            res.append(new_jaxpr_eqn(ins, [co], eqn.primitive,
                                     dict(eqn.params), no_effects, _src()))
            clone_out[d] = co
        nxt = out if s == k - 1 else Var(acc_aval)
        idx = [Literal(np.int32(a), idx_aval)] + [
            Literal(np.int32(0), idx_aval)] * (len(oshape) - 1)
        res.append(new_jaxpr_eqn(
            [acc, clone_out[len(eqns) - 1], *idx], [nxt],
            lax_slicing.dynamic_update_slice_p, {}, no_effects, _src()))
        acc = nxt
    return res


def partial_execute_jaxpr(jaxpr, budget: Optional[int] = None,
                          k_choices: Sequence[int] = (2, 4, 8, 16),
                          shard_divisor: int = 1) -> Tuple[object, int]:
    """Split beneficial equation runs.  Returns (jaxpr, #runs split).
    ``budget`` is in the same per-device units as ``shard_divisor`` scales
    to (matching ``jaxpr_to_graph``'s liveness accounting)."""
    if jaxpr.effects:
        return jaxpr, 0
    chosen: Dict[int, Tuple[List[int], int]] = {}
    for run in _find_runs(jaxpr):
        eqns = [jaxpr.eqns[i] for i in run]
        h = tuple(eqns[-1].outvars[0].aval.shape)[0]
        best: Optional[Tuple[Tuple, int]] = None
        _, before = _estimate_run(eqns, 2, shard_divisor)
        for k in k_choices:
            if k > h:
                continue
            est, _ = _estimate_run(eqns, k, shard_divisor)
            if est >= before:
                continue
            meets = budget is not None and est <= budget
            key = (0 if meets else 1, est, k)
            if best is None or key < best[0]:
                best = (key, k)
        if best is not None:
            chosen[run[0]] = (run, best[1])
    if not chosen:
        return jaxpr, 0
    member = {i for run, _ in chosen.values() for i in run}
    new_eqns: List = []
    for i, eqn in enumerate(jaxpr.eqns):
        if i in chosen:
            run, k = chosen[i]
            new_eqns.extend(_expand_run([jaxpr.eqns[j] for j in run], k))
        elif i in member:
            continue
        else:
            new_eqns.append(eqn)
    new_jaxpr = jaxpr.replace(eqns=new_eqns)
    check_jaxpr(new_jaxpr)
    return new_jaxpr, len(chosen)


def partial_execute_closed_jaxpr(closed: jcore.ClosedJaxpr,
                                 budget: Optional[int] = None,
                                 k_choices: Sequence[int] = (2, 4, 8, 16),
                                 shard_divisor: int = 1
                                 ) -> Tuple[jcore.ClosedJaxpr, int]:
    new_jaxpr, n = partial_execute_jaxpr(closed.jaxpr, budget, k_choices,
                                         shard_divisor)
    if n == 0:
        return closed, 0
    return jcore.ClosedJaxpr(new_jaxpr, closed.consts), n
