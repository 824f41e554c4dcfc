"""Compiled arena executor: lower a scheduled graph + arena plan to ONE
jitted JAX program.

The paper's offline artefacts — a schedule (the operator order) and an
``ArenaPlan`` (a byte offset per tensor) — fully determine the runtime: what
ships to the device is a straight-line program over a single SRAM arena.
``MicroInterpreter`` executes that program as a Python loop with per-op
dispatch, which validates the memory model but is orders of magnitude slower
than the hardware.  ``compile_schedule`` closes the gap the way Pex and
MCUNet pair their planners with a compiled runtime:

* the whole arena is **one uint8 buffer** of ``plan.arena_size`` bytes —
  exactly the byte-addressed SRAM arena of TFLite-Micro.  The jitted
  program takes the arena and returns the arena, and is jitted with
  ``donate_argnums=0`` so XLA updates it in place — the jit-level
  equivalent of a Pallas kernel's ``input_output_aliases``;
* each operator becomes a static byte-slice read of its inputs at their
  ``Placement`` offsets **bitcast to the tensor's dtype** (f32 tensors view
  4 bytes per element, int8 tensors 1 — mixed f32/int8 graphs coexist in
  the one arena), a lowering rule (see the registry below), and a bitcast
  back to bytes + ``dynamic_update_slice`` at the output's offset.  The
  plan's disjointness invariant (overlapping lifetimes ⇒ disjoint ranges)
  plus its alignment policy (offsets aligned to the itemsize, so every
  bitcast view is naturally aligned — enforced here at compile time) are
  what make this sound;
* inplace chains (partial execution's incremental ``pex_concat``) alias to
  one offset in the plan, so the read-modify-write at that offset **is** the
  shared accumulator buffer — no copies materialise after XLA's donation;
* runs of uniform Pex slices are rolled into a ``lax.fori_loop`` whose body
  indexes per-iteration offsets/row-starts from closed-over arrays — the
  compiled program stays O(segment) in code size instead of O(K · segment);
* ``pex_ring_read`` windows with a single integer-exact consumer are
  **zero-copy**: the modular gather fuses into the consumer's computation
  as an SSA value and the window is never re-materialised in the arena
  (``zero_copy_rings=True``, see ``_zero_copy_reads`` for the eligibility
  proof obligations) — in both the straight-line and rolled-loop paths;
* each operator is lowered under ``jax.named_scope(op.name)`` and each
  rolled loop under ``jax.named_scope(f"loop{k}")`` (k counts the loops in
  schedule order), so a device op in a profile carries the operator or
  loop it came from in its ``op_name`` metadata.  Metadata only: the
  computation is the same.

Lowering rules are registered per operator ``kind`` next to the semantics
(``graphs/cnn_ops.py`` registers conv/dwconv/maxpool/add, optionally routing
the MCU-shaped NHWC pointwise conv through the Pallas fused kernel under
``kernels/``); ``pex_slice``/``pex_concat`` are lowered here from the
structured attrs the partition rewrite records, because their simulator
closures are numpy and cannot be traced.  Any kind without a rule falls back
to tracing ``op.fn`` — every jnp-based simulator semantic is jit-compatible.

Numerics contract: with ``use_pallas=False`` (default) the lowering traces
the same jnp/lax computations the interpreter runs eagerly, so outputs are
bit-identical (property-tested in ``tests/test_executor_diff.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.allocator import ArenaPlan, ArenaPlanner
from repro.core.graph import Graph, Operator


# ------------------------------------------------------------- dtype bitcasts
# The arena is bytes; tensors are typed views of byte ranges.  These two
# helpers are the only place the executor crosses that boundary, and both
# are exact bit-level reinterpretations (no rounding, no canonicalisation),
# so they cannot perturb the bit-identity contract.
_JNP_DTYPES = {
    "int8": jnp.int8, "uint8": jnp.uint8,
    "int16": jnp.int16, "float16": jnp.float16, "bfloat16": jnp.bfloat16,
    "int32": jnp.int32, "float32": jnp.float32,
}

# Guard-byte debug mode (DESIGN.md §12): never-placed arena gaps (see
# ``ArenaPlan.guard_regions``) are filled with this canary at arena build
# and verified untouched after execution.  0xA5 = 1010_0101 — asymmetric
# under bit rotation and distinct from 0x00/0xFF, so zero-fills, one-fills
# and shifted writes all trip it.
CANARY_BYTE = 0xA5


def _view_bytes(raw, dtype: str, shape: Tuple[int, ...]):
    """uint8 [nbytes] -> ``dtype`` array of ``shape``."""
    dt = jnp.dtype(_JNP_DTYPES[dtype])
    if dt.itemsize == 1:
        v = raw if dt == jnp.uint8 else lax.bitcast_convert_type(raw, dt)
    else:
        v = lax.bitcast_convert_type(raw.reshape(-1, dt.itemsize), dt)
    return v.reshape(shape)


def _as_bytes(val):
    """Any array -> flat uint8 [nbytes]."""
    flat = jnp.ravel(val)
    if flat.dtype == jnp.uint8:
        return flat
    return jnp.ravel(lax.bitcast_convert_type(flat, jnp.uint8))


# ----------------------------------------------------------- lowering registry
@dataclasses.dataclass
class LoweringCtx:
    """What a lowering rule may ask about the graph being compiled."""

    graph: Graph
    use_pallas: bool = False
    interpret: bool = False     # Pallas interpreter instead of Mosaic

    def shape(self, tensor: str) -> Tuple[int, ...]:
        t = self.graph.tensors[tensor]
        return tuple(t.shape) if t.shape else (t.elements,)

    def dtype(self, tensor: str) -> str:
        return self.graph.tensors[tensor].dtype


_RULES: Dict[str, Callable[..., Any]] = {}


def register_lowering(kind: str):
    """Register ``fn(ctx, op, *inputs) -> output`` as the compiled lowering
    for operators of ``kind``.  Rules live next to the op semantics."""
    def deco(fn):
        _RULES[kind] = fn
        return fn
    return deco


def _fallback(ctx: LoweringCtx, op: Operator, *args):
    if op.fn is None:
        raise ValueError(
            f"operator {op.name!r} (kind={op.kind!r}) has neither a lowering "
            f"rule nor executable semantics")
    return op.fn(*args)


def lower_op(ctx: LoweringCtx, op: Operator, *args):
    return _RULES.get(op.kind, _fallback)(ctx, op, *args)


@register_lowering("pex_slice")
def _lower_pex_slice(ctx: LoweringCtx, op: Operator, x):
    rows = op.attrs.get("pex_rows")
    if rows is None:                    # pre-metadata graph: trace the closure
        return _fallback(ctx, op, x)
    lo, hi = rows
    x = lax.slice_in_dim(x, lo, hi, axis=0)
    cols = op.attrs.get("pex_cols")     # 2-D tile extract: columns too
    if cols is not None:
        x = lax.slice_in_dim(x, cols[0], cols[1], axis=1)
    return x


@register_lowering("pex_concat")
def _lower_pex_concat(ctx: LoweringCtx, op: Operator, *args):
    start = op.attrs.get("pex_start")
    if start is None:
        return _fallback(ctx, op, *args)
    if op.attrs.get("pex_first"):
        (part,) = args
        acc = jnp.zeros(ctx.shape(op.output), part.dtype)
    else:
        acc, part = args
    # 2-D tiles scatter at (row, column) — pex_cstart is 0 for row cascades
    idx = (start, op.attrs.get("pex_cstart", 0)) + (0,) * (np.ndim(part) - 2)
    idx = idx[:np.ndim(part)]
    return lax.dynamic_update_slice(acc, part, idx)


# Cascaded-streaming ring ops (core/partition.py cascade rewrite): boundary
# tensors between cascaded segments never exist whole — row ``r`` of the
# boundary lives at ring position ``r % ring_rows``.  A push is a rolling
# scatter of the producer's new delta rows (the SSA chain of ring states
# aliases to one arena offset through the inplace accounting, so the
# compiled read-modify-write at that offset IS the rolling buffer); a read
# gathers the consumer's halo'd window back into row order.  Both are pure
# row copies at static indices — bit-identity is structural.
@register_lowering("pex_ring_push")
def _lower_pex_ring_push(ctx: LoweringCtx, op: Operator, *args):
    a = op.attrs
    rows, dst = a.get("pex_ring_rows"), a.get("pex_ring_dst")
    if rows is None or dst is None:
        return _fallback(ctx, op, *args)
    if a.get("pex_first"):
        (part,) = args
        ring = jnp.zeros(ctx.shape(op.output), part.dtype)
    else:
        ring, part = args
    idx = (dst + jnp.arange(part.shape[0])) % rows
    return ring.at[idx].set(part)


@register_lowering("pex_ring_read")
def _lower_pex_ring_read(ctx: LoweringCtx, op: Operator, ring):
    a = op.attrs
    rows, src = a.get("pex_ring_rows"), a.get("pex_ring_src")
    if rows is None or src is None:
        return _fallback(ctx, op, ring)
    n = ctx.shape(op.output)[0]
    idx = (src + jnp.arange(n)) % rows
    return jnp.take(ring, idx, axis=0)


# ------------------------------------------------------- zero-copy ring reads
# A ``pex_ring_read`` gathers a halo'd window out of the ring in row order.
# Materialising that window into the arena is a pure copy the MCU never
# needs: the consumer can index the ring directly (the Helium ping-pong
# buffering model — never re-materialise what the arena already holds).
# The compiled program fuses the gather into the consumer by keeping the
# gathered window as an SSA value — no arena write, no barrier between the
# read and its consumer — when that is provably bit-safe:
#
# * the window is integer-typed and the consumer is an integer-exact kind
#   (int32 accumulation is order-independent and element-wise f32 requant
#   ops are deterministic under any fusion context, so removing the module
#   boundary cannot perturb results — unlike f32 convs, which XLA CPU
#   compiles context-sensitively);
# * the read's output has exactly one consumer, scheduled immediately after
#   it in the same Pex slice group (true by construction for the cascade
#   rewrite's ``cpexrd__*`` reads), and is not a graph output.
#
# The arena plan is untouched: the window keeps its placement (the memory
# model still charges it — the liveness story is unchanged), the compiled
# program just never writes it.
_ZERO_COPY_KINDS = frozenset({"qconv", "qdwconv", "qmaxpool"})
_INT_DTYPES = frozenset({"int8", "uint8", "int16", "int32"})


def _zero_copy_reads(graph: Graph, sched: Sequence[Operator]) -> set:
    """Tensor names of ring-read windows to keep as SSA values."""
    outs = set(graph.outputs)
    fused = set()
    for idx in range(len(sched) - 1):
        op, nxt = sched[idx], sched[idx + 1]
        if op.kind != "pex_ring_read" or "pex_ring_src" not in op.attrs:
            continue
        name = op.output
        if name in outs or graph.tensors[name].dtype not in _INT_DTYPES:
            continue
        cons = graph.consumers(name)
        if (len(cons) == 1 and cons[0].name == nxt.name
                and nxt.kind in _ZERO_COPY_KINDS
                and op.attrs.get("pex_seg") == nxt.attrs.get("pex_seg")
                and op.attrs.get("pex_slice_idx")
                == nxt.attrs.get("pex_slice_idx")):
            fused.add(name)
    return fused


# ------------------------------------------------------- pex fori_loop rolling
def _roll_key(ctx: LoweringCtx, op: Operator):
    """Hashable description of what an op *computes* (not where its tensors
    live).  Two ops with equal keys run the same program on same-shaped data,
    so consecutive slices whose keys match position-for-position can share
    one fori_loop body.  ``None`` = not rollable."""
    ins = tuple((ctx.shape(i), ctx.dtype(i)) for i in op.inputs)
    outs = (ctx.shape(op.output), ctx.dtype(op.output))
    a = op.attrs
    if op.kind == "pex_slice":
        if "pex_rows" not in a:
            return None
        lo, hi = a["pex_rows"]
        # the column window is traced statically into the body, so it must
        # match across rolled iterations (constant within a W-strip)
        return ("pex_slice", hi - lo, a.get("pex_cols"), ins, outs)
    if op.kind == "pex_concat":
        if "pex_start" not in a:
            return None
        return ("pex_concat", bool(a.get("pex_first")),
                a.get("pex_cstart"), ins, outs)
    if op.kind == "pex_ring_push":
        if "pex_ring_dst" not in a:
            return None
        return ("pex_ring_push", bool(a.get("pex_first")),
                a["pex_ring_rows"], ins, outs)
    if op.kind == "pex_ring_read":
        if "pex_ring_src" not in a:
            return None
        return ("pex_ring_read", a["pex_ring_rows"], ins, outs)
    if "pex_of" in a and "pex_pads" in a:
        wpads = a.get("pex_wpads")
        return (op.kind, a["pex_of"], tuple(a["pex_pads"]),
                None if wpads is None else tuple(wpads), ins, outs)
    return None


@dataclasses.dataclass
class _Slot:
    """Where one operand lives, across the iterations of a rolled loop."""

    offset: Any                 # int (static) or jnp int32 array [n] (param)
    size: int                   # bytes
    shape: Tuple[int, ...]
    dtype: str

    @property
    def static(self) -> bool:
        return isinstance(self.offset, int)


@dataclasses.dataclass
class _Template:
    op: Operator                       # representative (first iteration's op)
    in_slots: List[_Slot]
    out_slot: _Slot
    lo: Optional[Any] = None           # pex_slice: row start per iteration
    col: int = 0                      # pex_slice: static column start (2-D)
    start: Optional[Any] = None       # pex_concat: write start per iteration
    cstart: int = 0                   # pex_concat: static column start (2-D)
    ring_dst: Optional[Any] = None    # pex_ring_push: dst row per iteration
    ring_src: Optional[Any] = None    # pex_ring_read: src row per iteration
    ring_rows: int = 0                # ring size (rows); static per template
    defer: bool = False               # zero-copy: keep output as SSA value
    fused_in: Optional[Tuple[int, int]] = None   # (input j, source template)


@dataclasses.dataclass
class _RolledLoop:
    templates: List[_Template]
    n: int


def _slice_groups(sched: Sequence[Operator]):
    """Split the schedule into maximal runs of ops tagged with the same
    (segment, slice index); untagged ops stand alone."""
    groups: List[Tuple[Optional[str], Optional[int], List[Operator]]] = []
    for op in sched:
        seg = op.attrs.get("pex_seg")
        s = op.attrs.get("pex_slice_idx")
        if (seg is not None and groups and groups[-1][0] == seg
                and groups[-1][1] == s):
            groups[-1][2].append(op)
        else:
            groups.append((seg, s, [op]))
    return groups


def _build_loop(ctx: LoweringCtx, offsets: Dict[str, Tuple[int, int]],
                run: List[List[Operator]],
                zero_copy: frozenset = frozenset()
                ) -> Optional[_RolledLoop]:
    """Merge ≥2 structurally-identical slice groups into one fori_loop.
    Returns None when any operand breaks the uniformity conditions."""
    n = len(run)
    templates: List[_Template] = []
    out_names: List[List[str]] = []
    for d in range(len(run[0])):
        ops = [g[d] for g in run]
        rep = ops[0]
        in_slots: List[_Slot] = []
        for j in range(len(rep.inputs)):
            names = [o.inputs[j] for o in ops]
            shape = ctx.shape(names[0])
            dtype = ctx.dtype(names[0])
            sizes = {offsets[nm][1] for nm in names}
            if len(sizes) != 1:
                return None
            size = sizes.pop()
            if all(nm == names[0] for nm in names):
                in_slots.append(_Slot(offsets[names[0]][0], size, shape,
                                      dtype))
            else:
                offs = jnp.asarray([offsets[nm][0] for nm in names],
                                   jnp.int32)
                in_slots.append(_Slot(offs, size, shape, dtype))
        onames = [o.output for o in ops]
        osizes = {offsets[nm][1] for nm in onames}
        if len(osizes) != 1:
            return None
        tpl = _Template(rep, in_slots,
                        _Slot(jnp.asarray([offsets[nm][0] for nm in onames],
                                          jnp.int32),
                              osizes.pop(), ctx.shape(onames[0]),
                              ctx.dtype(onames[0])))
        if rep.kind == "pex_slice":
            tpl.lo = jnp.asarray([o.attrs["pex_rows"][0] for o in ops],
                                 jnp.int32)
            tpl.col = rep.attrs.get("pex_cols", (0, 0))[0]
        elif rep.kind == "pex_concat":
            tpl.start = jnp.asarray([o.attrs["pex_start"] for o in ops],
                                    jnp.int32)
            tpl.cstart = rep.attrs.get("pex_cstart", 0)
        elif rep.kind == "pex_ring_push":
            tpl.ring_dst = jnp.asarray([o.attrs["pex_ring_dst"]
                                        for o in ops], jnp.int32)
            tpl.ring_rows = rep.attrs["pex_ring_rows"]
        elif rep.kind == "pex_ring_read":
            tpl.ring_src = jnp.asarray([o.attrs["pex_ring_src"]
                                        for o in ops], jnp.int32)
            tpl.ring_rows = rep.attrs["pex_ring_rows"]
        templates.append(tpl)
        out_names.append(onames)
    # zero-copy ring reads inside the rolled body: a deferred template's
    # per-iteration outputs flow straight into the next template's matching
    # input instead of round-tripping through the arena
    for d in range(len(templates) - 1):
        if templates[d].op.kind != "pex_ring_read":
            continue
        if not all(nm in zero_copy for nm in out_names[d]):
            continue
        nxt_ops = [g[d + 1] for g in run]
        for j in range(len(nxt_ops[0].inputs)):
            if [o.inputs[j] for o in nxt_ops] == out_names[d]:
                templates[d].defer = True
                templates[d + 1].fused_in = (j, d)
                break
    return _RolledLoop(templates, n)


def _plan_items(ctx: LoweringCtx, offsets: Dict[str, Tuple[int, int]],
                sched: Sequence[Operator], roll_loops: bool,
                zero_copy: frozenset = frozenset()) -> List[Any]:
    """The compiled program structure: a list of Operators (straight-line
    steps) and _RolledLoops."""
    if not roll_loops:
        return list(sched)
    items: List[Any] = []
    groups = _slice_groups(sched)
    i = 0
    while i < len(groups):
        seg, s, ops = groups[i]
        key = (None if seg is None
               else tuple(_roll_key(ctx, op) for op in ops))
        if seg is None or key is None or any(k is None for k in key):
            items.extend(ops)
            i += 1
            continue
        run = [ops]
        j = i + 1
        while j < len(groups):
            seg2, s2, ops2 = groups[j]
            if (seg2 != seg or s2 != s + (j - i)
                    or len(ops2) != len(ops)
                    or tuple(_roll_key(ctx, op) for op in ops2) != key):
                break
            run.append(ops2)
            j += 1
        loop = (_build_loop(ctx, offsets, run, zero_copy)
                if len(run) >= 2 else None)
        if loop is None:
            items.extend(ops)
            i += 1
        else:
            items.append(loop)
            i = j
    return items


# ------------------------------------------------------------------- executor
def _placed(f: Callable, device) -> Callable:
    """``f`` with its arena argument moved to ``device`` first (a no-op
    when it already lives there), so the jitted program runs there.
    ``.jitted`` is ``f`` itself, for ``lower``/``compile``."""
    def run(arena):
        return f(jax.device_put(arena, device))
    run.jitted = f
    return run


@dataclasses.dataclass
class CompiledExecutor:
    """A scheduled graph lowered to one jitted arena program.

    ``raw_fn(arena) -> arena`` is the pure staged program (composable under
    ``jax.vmap`` for micro-batched serving); ``fn`` is its jitted,
    donated-argument form.  The arena is **uint8**: ``arena_size`` equals
    ``plan.arena_size`` bytes, and the program never reads or writes past
    it.  Tensors are typed bitcast views of their placements.

    ``device`` is where the program runs: ``fn`` and ``batched_fn`` place
    their (host-built) arenas there before executing.  ``interpret``
    records whether its Pallas kernels (``use_pallas=True``) run through
    the Pallas interpreter — never on a TPU device.
    """

    graph: Graph
    schedule: List[Operator]
    plan: ArenaPlan
    arena_size: int              # bytes
    dtype: Any                   # arena element type: always uint8
    raw_fn: Callable[[Any], Any]
    fn: Callable[[Any], Any]
    rolled_loops: int
    rolled_ops: int
    steps: int
    offsets: Dict[str, Tuple[int, int]]    # tensor -> (byte offset, bytes)
    zero_copy_reads: int = 0    # ring windows fused into their consumers
    device: Any = None           # jax.Device the arenas are placed on
    use_pallas: bool = False
    interpret: bool = False      # Pallas interpreter (never on a TPU)
    # guard-byte debug mode: (offset, size) arena ranges no placement ever
    # covers; () in production (guard_bytes=0 plans) — the arena is then
    # byte-identical to the un-guarded executor
    guard_regions: Tuple[Tuple[int, int], ...] = ()
    # jit/pmap wrappers are built lazily and cached per geometry: engines
    # ask for the same batched program every dispatch, and an XLA compile
    # per call would dwarf the work
    _fn_cache: Dict[Any, Callable] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    def _offsets(self, tensor: str) -> Tuple[int, int]:
        return self.offsets[tensor]

    # ------------------------------------------------- serving entry points
    # ``raw_fn`` is a pure [arena] -> [arena] program, so batching and
    # replication are plain jax transforms of it: one jit(vmap) for
    # micro-batched single-device serving, one pmap(vmap) to shard replica
    # batches across host/accelerator devices (the engines in
    # ``serving/`` own the queueing; the executor owns the compiled forms).
    def batched_fn(self, *, donate: bool = True) -> Callable:
        """``[B, arena] -> [B, arena]``: one jitted vmap dispatch over a
        stack of B arenas (B inferences amortise one XLA dispatch)."""
        key = ("batched", donate)
        if key not in self._fn_cache:
            f = jax.vmap(self.raw_fn)
            self._fn_cache[key] = _placed(
                jax.jit(f, donate_argnums=0) if donate else jax.jit(f),
                self.device)
        return self._fn_cache[key]

    def replicated_fn(self, replicas: int) -> Callable:
        """``[R, B, arena] -> [R, B, arena]``: the vmapped arena program
        pmapped over the first ``replicas`` devices of this executor's
        platform — each replica executes its lane batch independently (no
        collectives; requests are embarrassingly parallel), so per-replica
        results are bit-identical to the single-device ``batched_fn``."""
        key = ("replicated", replicas)
        if key not in self._fn_cache:
            self._fn_cache[key] = jax.pmap(
                jax.vmap(self.raw_fn), devices=self._replica_devices(replicas))
        return self._fn_cache[key]

    def _replica_devices(self, replicas: int) -> list:
        devices = jax.devices(self.device.platform)
        if replicas > len(devices):
            raise ValueError(
                f"replicas={replicas} but only {len(devices)} devices "
                f"visible; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={replicas} "
                f"before the first jax import (serving.force_host_devices)")
        return devices[:replicas]

    def _output_specs(self):
        """(name, arena offset, bytes, dtype, shape) of every graph output,
        in ``graph.outputs`` order."""
        for o in self.graph.outputs:
            off, size = self._offsets(o)
            t = self.graph.tensors[o]
            yield o, off, size, t.dtype, (tuple(t.shape) if t.shape
                                          else (t.elements,))

    def output_block_fn(self, replicas: Optional[int] = None) -> Callable:
        """``[R, L, arena] -> [R, L, out_bytes]``: the byte ranges of every
        graph output, concatenated in ``graph.outputs`` order, of every
        lane, in one device op, so one transfer reads a whole dispatch's
        outputs (``outputs_from_block`` cuts them per lane).  With
        ``replicas`` it is pmapped over the devices of ``replicated_fn
        (replicas)`` and reads that program's output where it lies; with
        None it is jitted, for ``batched_fn``'s output shaped ``[1, L,
        arena]``."""
        key = ("outputs", replicas)
        if key not in self._fn_cache:
            ranges = [(off, size) for _, off, size, _, _
                      in self._output_specs()]

            def pick(arena):
                return jnp.concatenate([arena[off:off + size]
                                        for off, size in ranges])
            lanes = jax.vmap(pick)
            self._fn_cache[key] = (
                jax.jit(jax.vmap(lanes)) if replicas is None else
                jax.pmap(lanes, devices=self._replica_devices(replicas)))
        return self._fn_cache[key]

    def outputs_from_block(self, block, lanes: int
                           ) -> List[Dict[str, np.ndarray]]:
        """The output dicts of the first ``lanes`` lanes, in ``[R, L]``
        row-major order, of an ``output_block_fn`` block: one transfer
        (``np.asarray``) of the whole block, then per output a dtype view
        of its byte range.  Each value is a read-only view of that host
        copy, bit-identical to ``outputs_from`` of the lane's arena (the
        arena's bytes are little-endian, as ``lax.bitcast_convert_type``
        reads them)."""
        host = np.asarray(block)
        host = host.reshape(-1, host.shape[-1])[:lanes]
        host.flags.writeable = False
        views, start = [], 0
        for name, _, size, dtype, shape in self._output_specs():
            col = host[:, start:start + size].view(
                jnp.dtype(_JNP_DTYPES[dtype]))
            views.append((name, col.reshape((lanes,) + shape)))
            start += size
        return [{name: v[i] for name, v in views} for i in range(lanes)]

    def pad_arena(self) -> np.ndarray:
        """An all-zeros arena for pad lanes (ragged tails): executed but
        never read back, and visibly not a duplicated request."""
        return np.zeros((self.arena_size,), np.uint8)

    def make_arena(self, inputs: Dict[str, Any]) -> np.ndarray:
        """Fresh host arena with the graph inputs written (as bytes) at
        their offsets; ``fn``/``batched_fn`` move it to ``device`` in one
        transfer.  Input values must already be in the tensor's declared
        dtype — an int8 graph takes quantized int8 inputs."""
        g = self.graph
        needed = {c for c in g.constants() if g.consumers(c)}
        missing = needed - set(inputs)
        if missing:
            raise ValueError(f"missing graph inputs: {sorted(missing)}")
        arena = np.zeros((self.arena_size,), np.uint8)
        for off, size in self.guard_regions:   # () in production plans
            arena[off:off + size] = CANARY_BYTE
        for name, value in inputs.items():
            if name not in g.tensors:
                raise ValueError(f"unknown tensor {name!r}")
            if g.producer(name) is not None:
                raise ValueError(f"{name!r} is not a graph input")
            if not g.consumers(name):
                continue       # unused input: not arena-resident in the plan
            off, size = self._offsets(name)
            t = g.tensors[name]
            want = jnp.dtype(_JNP_DTYPES[t.dtype])
            val = np.asarray(value)
            # the dtype jnp.asarray would give (float64 -> float32 unless
            # x64 is on): same contract as MicroInterpreter
            have = jax.dtypes.canonicalize_dtype(val.dtype)
            if have != want:
                raise ValueError(
                    f"input {name!r} is {have}, graph declares "
                    f"{t.dtype} (quantize inputs for int8 graphs)")
            flat = np.ascontiguousarray(val.astype(have, copy=False)
                                        ).reshape(-1)
            if flat.shape[0] != t.elements:
                raise ValueError(
                    f"input {name!r}: got {flat.shape[0]} elements, "
                    f"plan expects {t.elements} ({size} bytes as {t.dtype})")
            arena[off:off + size] = flat.view(np.uint8)
        return arena

    def outputs_from(self, arena, as_numpy: bool = True) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for o, off, size, dtype, shape in self._output_specs():
            val = _view_bytes(arena[off:off + size], dtype, shape)
            out[o] = np.asarray(val) if as_numpy else val
        return out

    def verify_guards(self, arena) -> None:
        """Guard-byte debug mode: assert every canary region still holds
        ``CANARY_BYTE`` after execution; a stomped byte is a genuine
        out-of-bounds write by a lowering or a planner bug and raises
        ``GuardViolation`` naming the first bad offset.  No-op (and free)
        when the plan carries no guard regions."""
        if not self.guard_regions:
            return
        from repro.errors import GuardViolation
        a = np.asarray(arena)
        for off, size in self.guard_regions:
            region = a[off:off + size]
            bad = np.nonzero(region != CANARY_BYTE)[0]
            if bad.size:
                at = off + int(bad[0])
                raise GuardViolation(
                    f"guard canary stomped at arena byte {at} (region "
                    f"[{off},{off + size}), found 0x{int(a[at]):02x}, "
                    f"expected 0x{CANARY_BYTE:02x}) — out-of-bounds write "
                    f"by a lowering or an arena-plan bug")

    def run(self, inputs: Dict[str, Any], as_numpy: bool = True
            ) -> Dict[str, Any]:
        arena = self.fn(self.make_arena(inputs))
        self.verify_guards(arena)
        return self.outputs_from(arena, as_numpy)


def resolve_interpret(device, interpret: Optional[bool] = None) -> bool:
    """Whether Pallas kernels of a program for ``device`` run through the
    Pallas interpreter.  ``None`` decides from the device: Mosaic on a
    TPU, the interpreter anywhere else.  Interpret mode on a TPU device is
    refused — a chip program never falls back to the interpreter."""
    on_tpu = device.platform == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            f"interpret-mode Pallas kernels requested for a program on "
            f"{device} ({device.device_kind}); a TPU program compiles its "
            f"kernels with Mosaic — place interpret-mode programs on the "
            f"CPU (device=jax.devices('cpu')[0])")
    return bool(interpret)


def compile_schedule(graph: Graph,
                     schedule: Optional[Sequence[Operator]] = None,
                     plan: Optional[ArenaPlan] = None, *,
                     use_pallas: bool = False,
                     interpret: Optional[bool] = None,
                     device=None,
                     roll_loops: bool = True,
                     zero_copy_rings: bool = True,
                     fuse: bool = False,
                     donate: bool = True) -> CompiledExecutor:
    """Lower ``schedule`` (default: the graph's embedded order) against
    ``plan`` (default: ``ArenaPlanner.plan``) into a single jitted arena
    program over one uint8 byte buffer.  See the module docstring for the
    lowering model.

    ``fuse=False`` (default) pins an ``optimization_barrier`` after every
    operator, reproducing the per-operator module boundaries of eager
    dispatch — an MCU runtime materialises each output into the arena the
    same way — which keeps compiled outputs bit-identical to the
    interpreter.  ``fuse=True`` lets XLA fuse across operators: fastest,
    but float results may drift within accumulation tolerance.

    ``zero_copy_rings=True`` (default) fuses each eligible
    ``pex_ring_read``'s window gather into its consumer instead of
    materialising the window in the arena — bit-safe by construction (only
    integer-exact consumers qualify; see ``_zero_copy_reads``) and a pure
    win: one fewer copy and barrier per streamed slice.

    ``device`` (default: the first of ``jax.devices()``) is where the
    program runs; ``interpret`` is resolved once against it
    (``resolve_interpret``) and recorded on the executor."""
    sched = list(schedule) if schedule is not None else graph.default_schedule()
    if not graph.is_valid_schedule(sched):
        raise ValueError("invalid schedule for this graph")
    if plan is None:
        plan = ArenaPlanner.plan(graph, sched)
    offsets = {p.tensor: (p.offset, p.size) for p in plan.placements}
    for op in sched:
        for t in list(op.inputs) + [op.output]:
            if t not in offsets:
                raise KeyError(f"tensor {t!r} missing from the arena plan")
            isz = graph.itemsize(t)
            if offsets[t][0] % isz:
                raise ValueError(
                    f"tensor {t!r} ({graph.tensors[t].dtype}) placed at "
                    f"misaligned byte offset {offsets[t][0]}; plan with "
                    f"ArenaPlanner.plan(..., alignment=None) so offsets "
                    f"are aligned to the widest itemsize")
    if device is None:
        device = jax.devices()[0]
    interpret = resolve_interpret(device, interpret)
    ctx = LoweringCtx(graph, use_pallas=use_pallas, interpret=interpret)
    zc = (frozenset(_zero_copy_reads(graph, sched)) if zero_copy_rings
          else frozenset())
    items = _plan_items(ctx, offsets, sched, roll_loops, zc)

    def read(arena, name: str):
        off, size = offsets[name]
        return _view_bytes(arena[off:off + size], ctx.dtype(name),
                           ctx.shape(name))

    def write(arena, name: str, val):
        off, size = offsets[name]
        want = jnp.dtype(_JNP_DTYPES[ctx.dtype(name)])
        if jnp.asarray(val).dtype != want:   # checked once, at trace time
            raise ValueError(
                f"{name}: lowered output is {jnp.asarray(val).dtype}, "
                f"graph declares {ctx.dtype(name)} — quantized semantics "
                f"must requantize before writing to the arena")
        flat = _as_bytes(val)
        if flat.shape[0] != size:     # static shape: checked at trace time
            raise ValueError(
                f"{name}: lowered output has {flat.shape[0]} bytes, "
                f"plan expects {size}")
        return lax.dynamic_update_slice(arena, flat, (off,))

    def barrier(arena):
        return arena if fuse else lax.optimization_barrier(arena)

    def step(arena, op: Operator, pending: Dict[str, Any]):
        with jax.named_scope(op.name):
            args = [pending.pop(i) if i in pending else read(arena, i)
                    for i in op.inputs]
            val = lower_op(ctx, op, *args)
            if op.output in zc:   # zero-copy: flows straight to the consumer
                pending[op.output] = val
                return arena
            return barrier(write(arena, op.output, val))

    def loop_step(arena, loop: _RolledLoop, k: int):
        def body(i, arena):
            deferred: Dict[int, Any] = {}
            for t_i, tpl in enumerate(loop.templates):
                args = []
                for j, slot in enumerate(tpl.in_slots):
                    if tpl.fused_in is not None and j == tpl.fused_in[0]:
                        args.append(deferred.pop(tpl.fused_in[1]))
                        continue
                    if slot.static:
                        raw = arena[slot.offset:slot.offset + slot.size]
                    else:
                        raw = lax.dynamic_slice(arena, (slot.offset[i],),
                                                (slot.size,))
                    args.append(_view_bytes(raw, slot.dtype, slot.shape))
                op = tpl.op
                if tpl.lo is not None:            # pex_slice, dynamic rows
                    x = args[0]
                    # sizes come from the out slot so 2-D tile extracts
                    # (static column window, dynamic row start) roll too;
                    # for row extracts out shape == (rows,) + x.shape[1:]
                    idx = (tpl.lo[i], tpl.col) + (0,) * (x.ndim - 2)
                    out = lax.dynamic_slice(x, idx[:x.ndim],
                                            tpl.out_slot.shape)
                elif tpl.start is not None:       # pex_concat, dynamic start
                    acc, part = args
                    idx = (tpl.start[i], tpl.cstart) + (0,) * (part.ndim - 2)
                    out = lax.dynamic_update_slice(acc, part, idx[:part.ndim])
                elif tpl.ring_dst is not None:    # pex_ring_push, dyn. dst
                    if op.attrs.get("pex_first"):
                        (part,) = args
                        ring = jnp.zeros(tpl.out_slot.shape, part.dtype)
                    else:
                        ring, part = args
                    rows = (tpl.ring_dst[i]
                            + jnp.arange(part.shape[0])) % tpl.ring_rows
                    out = ring.at[rows].set(part)
                elif tpl.ring_src is not None:    # pex_ring_read, dyn. src
                    (ring,) = args
                    rows = (tpl.ring_src[i]
                            + jnp.arange(tpl.out_slot.shape[0])
                            ) % tpl.ring_rows
                    out = jnp.take(ring, rows, axis=0)
                else:
                    out = lower_op(ctx, op, *args)
                if tpl.defer:     # zero-copy: no arena write, no barrier
                    deferred[t_i] = out
                    continue
                want = jnp.dtype(_JNP_DTYPES[tpl.out_slot.dtype])
                if jnp.asarray(out).dtype != want:
                    raise ValueError(
                        f"{op.name}: lowered output is "
                        f"{jnp.asarray(out).dtype}, graph declares "
                        f"{tpl.out_slot.dtype}")
                flat = _as_bytes(out)
                if tpl.out_slot.static:
                    arena = lax.dynamic_update_slice(
                        arena, flat, (tpl.out_slot.offset,))
                else:
                    arena = lax.dynamic_update_slice(
                        arena, flat, (tpl.out_slot.offset[i],))
                arena = barrier(arena)
            return arena
        with jax.named_scope(f"loop{k}"):
            return lax.fori_loop(0, loop.n, body, arena)

    def raw_fn(arena):
        pending: Dict[str, Any] = {}
        loops = 0
        for item in items:
            if isinstance(item, _RolledLoop):
                arena = loop_step(arena, item, loops)
                loops += 1
            else:
                arena = step(arena, item, pending)
        return arena

    fn = _placed(jax.jit(raw_fn, donate_argnums=0) if donate
                 else jax.jit(raw_fn), device)
    loops = [it for it in items if isinstance(it, _RolledLoop)]
    return CompiledExecutor(
        graph=graph, schedule=sched, plan=plan,
        arena_size=int(plan.arena_size), dtype=jnp.uint8,
        raw_fn=raw_fn, fn=fn,
        rolled_loops=len(loops),
        rolled_ops=sum(lp.n * len(lp.templates) for lp in loops),
        steps=len(sched), offsets=offsets, zero_copy_reads=len(zc),
        device=device, use_pallas=use_pallas, interpret=interpret,
        guard_regions=tuple(plan.guard_regions())
        if getattr(plan, "guard_bytes", 0) else ())
