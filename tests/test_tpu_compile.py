"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The fused int8 kernels are compiled with Mosaic (``interpret=False``) at
MobileNet-v1 1.0@192's real layer shapes and at the row/column-slice shapes
the 224 KB 2-D cascade emits, and one batched arena program with
``use_pallas=True`` is compiled for 0.25@96.  Interpret-mode tests cannot
see what the chip's compiler refuses (int32 MXU operands, strided value
slices, VMEM overflows); these compiles can, at no chip time.  Nothing
runs: results are checked by the interpret-mode grids in
``tests/test_qkernels.py`` and on the chip by ``chip_smoke.py``.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ArenaPlanner, schedule
from repro.graphs import (mobilenet_v1_graph, mobilenet_v2_graph,
                          quantize_graph, random_input)
from repro.kernels import (qadd_fused, qconv_add_fused, qconv_fused,
                           qdwconv_fused)
from repro.mcu import compile_schedule

_QP = dict(mult=0.0123, zp_in=3, zp_out=-5)
_ADDP = (0.71, 0.39, -5, 2, -7)


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip; the persistent compile cache is off around
    these compiles (entries for a chip that is not attached cannot be read
    back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _int8(shape, device):
    return jax.ShapeDtypeStruct(shape, jnp.int8,
                                sharding=SingleDeviceSharding(device))


# name, op, operand shapes, static options.  Whole layers of 1.0@192, then
# slices the 224 KB 2-D cascade emits (rows x W-strip columns, halo pads).
_KERNELS = [
    ("stem_192x3_s2", qconv_fused, [(192, 192, 3), (3, 3, 3, 32)],
     dict(stride=2)),
    ("dw_96x32_s1", qdwconv_fused, [(96, 96, 32), (3, 3, 32, 1)],
     dict(stride=1)),
    ("dw_96x64_s2", qdwconv_fused, [(96, 96, 64), (3, 3, 64, 1)],
     dict(stride=2)),
    ("dw_12x512_s1", qdwconv_fused, [(12, 12, 512), (3, 3, 512, 1)],
     dict(stride=1)),
    ("dw_12x512_s2", qdwconv_fused, [(12, 12, 512), (3, 3, 512, 1)],
     dict(stride=2)),
    ("pw_96x32_64", qconv_fused, [(96, 96, 32), (1, 1, 32, 64)],
     dict(stride=1)),
    ("pw_6x1024_1024", qconv_fused, [(6, 6, 1024), (1, 1, 1024, 1024)],
     dict(stride=1)),
    ("add_pw_12x512", qconv_add_fused,
     [(12, 12, 512), (1, 1, 512, 512), (12, 12, 512)],
     dict(stride=1, add_params=_ADDP)),
    ("add_3x3_24x64", qconv_add_fused,
     [(24, 24, 64), (3, 3, 64, 64), (24, 24, 64)],
     dict(stride=1, add_params=_ADDP)),
    ("slice_stem_9x110x3", qconv_fused, [(9, 110, 3), (3, 3, 3, 32)],
     dict(stride=2, hpad=(0, 0), wpad=(0, 1))),
    ("slice_dw_4x62x32_s1", qdwconv_fused, [(4, 62, 32), (3, 3, 32, 1)],
     dict(stride=1, hpad=(0, 0), wpad=(1, 0))),
    ("slice_dw_3x61x64_s2", qdwconv_fused, [(3, 61, 64), (3, 3, 64, 1)],
     dict(stride=2, hpad=(0, 0), wpad=(0, 0))),
    ("slice_pw_2x61x32_64", qconv_fused, [(2, 61, 32), (1, 1, 32, 64)],
     dict(stride=1, hpad=(0, 0), wpad=(0, 0))),
    ("slice_pw_1x30x64_128", qconv_fused, [(1, 30, 64), (1, 1, 64, 128)],
     dict(stride=1, hpad=(0, 0), wpad=(0, 0))),
    # MobileNet-v2 1.0@224 at the 512 KB rung: linear projections (lo =
    # -128) and ReLU6 layers at its channel counts, whole and sliced
    ("v2_project_56x144_24", qconv_fused, [(56, 56, 144), (1, 1, 144, 24)],
     dict(stride=1, lo=-128)),
    ("v2_project_7x960_320", qconv_fused, [(7, 7, 960), (1, 1, 960, 320)],
     dict(stride=1, lo=-128)),
    ("v2_expand_slice_3x112x16_96", qconv_fused,
     [(3, 112, 16), (1, 1, 16, 96)], dict(stride=1, hpad=(0, 0))),
    ("v2_dw_slice_5x112x96_s2", qdwconv_fused, [(5, 112, 96), (3, 3, 96, 1)],
     dict(stride=2, hpad=(0, 0))),
    ("v2_dw_14x576_s1", qdwconv_fused, [(14, 14, 576), (3, 3, 576, 1)],
     dict(stride=1)),
]


@pytest.mark.parametrize("name,op,shapes,opts", _KERNELS,
                         ids=[k[0] for k in _KERNELS])
def test_fused_kernel_compiles_for_v5e(v5e, name, op, shapes, opts):
    fn = jax.jit(functools.partial(op, interpret=False, **_QP, **opts))
    compiled = fn.lower(*[_int8(s, v5e) for s in shapes]).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


# MobileNet-v2 1.0@224's residual adds: whole (56x56x24 is lane-dense rows
# of 128, 7x7x160 is not) and a row slice of the 512 KB rung
@pytest.mark.parametrize("shape", [(56, 56, 24), (7, 7, 160), (2, 56, 24)],
                         ids=lambda s: "x".join(map(str, s)))
def test_qadd_compiles_for_v5e(v5e, shape):
    fn = jax.jit(functools.partial(qadd_fused, add_params=_ADDP,
                                   interpret=False))
    compiled = fn.lower(_int8(shape, v5e), _int8(shape, v5e)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _mobilenet_025_int8():
    g = mobilenet_v1_graph()                          # 0.25 @ 96
    gq = quantize_graph(g, random_input(g)).graph
    sched = schedule(gq).schedule
    return gq, sched, ArenaPlanner.plan(gq, sched)


def test_batched_arena_program_compiles_for_v5e(v5e):
    """The served form of a deployment — ``vmap`` of the arena program
    over lanes — with every int8 conv a Mosaic kernel."""
    gq, sched, plan = _mobilenet_025_int8()
    ex = compile_schedule(gq, sched, plan, use_pallas=True, device=v5e)
    assert ex.interpret is False and ex.device == v5e
    lanes = jax.ShapeDtypeStruct((8, ex.arena_size), jnp.uint8,
                                 sharding=SingleDeviceSharding(v5e))
    lowered = ex.batched_fn().jitted.lower(lanes)
    assert "stablehlo.convolution" not in lowered.as_text()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_interpret_mode_refused_for_a_tpu_program(v5e):
    gq, sched, plan = _mobilenet_025_int8()
    with pytest.raises(ValueError, match="interpret-mode"):
        compile_schedule(gq, sched, plan, use_pallas=True, interpret=True,
                         device=v5e)


def test_batched_v2_arena_program_compiles_for_v5e(v5e):
    """MobileNet-v2 0.35@32 sliced under 7,680 B: linear projections,
    ReLU6 and residual adds, every one a Mosaic kernel in the vmapped
    program."""
    g = mobilenet_v2_graph(0.35, 32, 10)
    gq = quantize_graph(g, random_input(g)).graph
    res = schedule(gq, arena_budget=7680)
    gp = res.graph
    plan = ArenaPlanner.plan(gp, res.schedule)
    ex = compile_schedule(gp, res.schedule, plan, use_pallas=True,
                          device=v5e)
    lanes = jax.ShapeDtypeStruct((8, ex.arena_size), jnp.uint8,
                                 sharding=SingleDeviceSharding(v5e))
    compiled = ex.batched_fn().jitted.lower(lanes).compile()
    assert "tpu_custom_call" in compiled.as_text()
