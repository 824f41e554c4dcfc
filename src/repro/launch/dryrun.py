import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  Everything below may touch jax.

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes and extract memory/cost/collective statistics.

    PYTHONPATH=src python -m repro.launch.dryrun \
        [--arch <id>] [--shape <name>] [--mesh single|multi|both] \
        [--out benchmarks/results/dryrun]

Each combo writes one JSON with:
  - memory_analysis (bytes per device: arguments/outputs/temps/peak)
  - cost_analysis   (per-device FLOPs and bytes accessed)
  - collective bytes by kind (parsed from the optimized HLO)
  - the §Roofline three-term report

A failure to lower/compile any combo is a bug in the distribution config —
the process exits non-zero listing the failures.
"""
import argparse
import json
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.roofline import (collective_bytes_from_hlo,
                                     model_flops, roofline_report)
from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import (SHAPES, batch_specs, config_for_shape,
                                 shape_applicable)
from repro.models.model import (Model, cache_specs, init_cache, init_params,
                                param_specs)
from repro.training.optimizer import adamw_init
from repro.training.train import TrainState, make_train_step


def _named(mesh, spec_tree, template):
    """PartitionSpec pytree -> NamedSharding pytree shaped like template."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _batch_shardings(mesh, specs):
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    out = {}
    for k, v in specs.items():
        sh = dp if v.shape[0] % dp_size == 0 else None
        out[k] = NamedSharding(mesh, P(sh, *([None] * (len(v.shape) - 1))))
    return out


def lower_combo(arch: str, shape_name: str, mesh, *, donate: bool = True,
                overrides=None):
    """Build and lower the right step function.  Returns (lowered, meta)."""
    from repro.models import runtime
    shape = SHAPES[shape_name]
    cfg = config_for_shape(get_config(arch), shape_name)
    if overrides:
        cfg = cfg.replace(**overrides)
    if runtime.UNROLL_SCANS:
        # analysis pass: fewer, larger chunk steps (identical FLOP totals,
        # far fewer unrolled bodies -> tractable compile times; 54-layer
        # zamba at 32k needs the ssm chunk at 4096 or XLA chokes on ~1.7k
        # unrolled bodies)
        cfg = cfg.replace(attn_chunk=min(4096, shape.seq_len),
                          ssm_chunk=min(4096, shape.seq_len))
    model = Model(cfg, mesh)
    pspecs = param_specs(cfg, mesh)
    params_shape = jax.eval_shape(partial(init_params, cfg),
                                  jax.random.PRNGKey(0))
    params_sh = _named(mesh, pspecs, params_shape)

    if shape.kind == "train":
        specs = batch_specs(cfg, shape)
        step = make_train_step(model, remat=True)
        state_shape = jax.eval_shape(
            lambda: TrainState(params=params_shape,
                               opt=adamw_init(params_shape)))
        # optimizer states ALWAYS keep the fsdp sharding — ZeRO-1 variants
        # change only where the bf16 params live (moe_fsdp=False drops the
        # experts' data axis from params, not from mu/nu)
        opt_pspecs = param_specs(cfg.replace(moe_fsdp=True), mesh)
        opt_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            {"mu": opt_pspecs, "nu": opt_pspecs},
            is_leaf=lambda x: isinstance(x, P))
        state_sh = TrainState(
            params=params_sh,
            opt=type(state_shape.opt)(
                step=NamedSharding(mesh, P()),
                mu=opt_sh["mu"], nu=opt_sh["nu"]))
        state_in = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            state_shape, state_sh)
        batch_in = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=s)
            for (k, v), s in zip(specs.items(),
                                 _batch_shardings(mesh, specs).values())}
        fn = jax.jit(step, donate_argnums=(0,) if donate else ())
        lowered = fn.lower(state_in, batch_in)
        tokens = shape.global_batch * shape.seq_len

    elif shape.kind == "prefill":
        specs = batch_specs(cfg, shape)
        batch_sh = _batch_shardings(mesh, specs)
        batch_in = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                            sharding=batch_sh[k])
                    for k, v in specs.items()}
        params_in = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            params_shape, params_sh)
        fn = jax.jit(lambda p, b: model.prefill(p, b,
                                                cache_len=shape.seq_len))
        lowered = fn.lower(params_in, batch_in)
        tokens = shape.global_batch * shape.seq_len

    else:  # decode
        csp = cache_specs(cfg, mesh, batch_size=shape.global_batch)
        cache_shape = jax.eval_shape(
            partial(init_cache, cfg, shape.global_batch, shape.seq_len))
        cache_sh = _named(mesh, csp, cache_shape)
        cache_in = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            cache_shape, cache_sh)
        params_in = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            params_shape, params_sh)
        dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        dp_size = int(np.prod([mesh.shape[a] for a in dp]))
        tok_sh = dp if shape.global_batch % dp_size == 0 else None
        tok_in = jax.ShapeDtypeStruct(
            (shape.global_batch,), jnp.int32,
            sharding=NamedSharding(mesh, P(tok_sh)))
        fn = jax.jit(model.decode_step,
                     donate_argnums=(1,) if donate else ())
        lowered = fn.lower(params_in, cache_in, tok_in)
        tokens = shape.global_batch          # one token per sequence

    return lowered, {"cfg": cfg, "tokens": tokens, "kind": shape.kind}


VARIANTS = {
    "": {},
    "cp": {"act_shard": "cp"},          # context-parallel prefill (§Perf)
    "zero1": {"moe_fsdp": False},       # ZeRO-1 expert weights (§Perf)
    "kvheads": {"kv_mode": "heads"},    # naive replicated-KV baseline
}


def run_combo(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
              keep_hlo: bool = False, analysis_unroll: bool = True,
              variant: str = ""):
    from repro.models import runtime

    overrides = VARIANTS[variant]
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    lowered, meta = lower_combo(arch, shape_name, mesh, overrides=overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_d = {}
    if mem is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem_d[f] = int(getattr(mem, f))
        # XLA reports no peak; the standard proxy keeps the schema stable
        mem_d["peak_memory_in_bytes"] = max(
            0, mem_d["argument_size_in_bytes"]
            + mem_d["output_size_in_bytes"] + mem_d["temp_size_in_bytes"]
            - mem_d["alias_size_in_bytes"])
        mem_d["peak_memory_is_derived"] = 1

    # ---- analysis pass: re-lower with layer/chunk scans UNROLLED so that
    # cost_analysis and the HLO collective census count every iteration
    # (HloCostAnalysis visits a while body once; see models/runtime.py).
    analysis_mode = "scan"
    a_compiled = compiled
    if analysis_unroll:
        try:
            runtime.UNROLL_SCANS = True
            a_lowered, _ = lower_combo(arch, shape_name, mesh,
                                       overrides=overrides)
            a_compiled = a_lowered.compile()
            analysis_mode = "unrolled"
        except Exception as e:          # fall back to rolled numbers
            print(f"  (unrolled analysis failed: {e!r} - using scan counts)")
        finally:
            runtime.UNROLL_SCANS = False
    cost = a_compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    hlo = a_compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)

    cfg = meta["cfg"]
    mf = model_flops(cfg, meta["kind"], meta["tokens"])
    roof = roofline_report(flops=flops, bytes_accessed=bytes_acc,
                           collective_bytes=coll["total"],
                           model_flops_global=mf, chips=chips)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_d,
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collectives": coll,
        "model_flops_global": mf,
        "roofline": roof,
        "sliding_window": cfg.sliding_window,
        "analysis_mode": analysis_mode,
        "variant": variant,
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{variant}" if variant else ""
    fname = os.path.join(out_dir,
                         f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    if keep_hlo:
        with open(fname.replace(".json", ".hlo.txt"), "w") as f:
            f.write(hlo)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch id (default all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-unroll", action="store_true",
                    help="skip the unrolled analysis pass (compile check "
                         "only; used for the multi-pod sweep)")
    ap.add_argument("--variant", default="", choices=list(VARIANTS),
                    help="sharding variant for §Perf A/B runs")
    args = ap.parse_args(argv)

    arches = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in arches:
        for shape in shapes:
            if not shape_applicable(get_config(arch), shape):
                print(f"SKIP  {arch} x {shape} (documented inapplicable)")
                continue
            for mk in meshes:
                fname = os.path.join(args.out,
                                     f"{arch}__{shape}__{mk}.json")
                if args.skip_existing and os.path.exists(fname):
                    print(f"SKIP  {arch} x {shape} x {mk} (exists)")
                    continue
                try:
                    rec = run_combo(arch, shape, mk, args.out,
                                    keep_hlo=args.keep_hlo,
                                    analysis_unroll=not args.no_unroll,
                                    variant=args.variant)
                    r = rec["roofline"]
                    print(f"OK    {arch:24s} {shape:12s} {mk:6s} "
                          f"compile={rec['compile_s']:6.1f}s "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"dom={r['dominant']:12s} "
                          f"bound={r['step_time_lb_s']*1e3:8.2f}ms",
                          flush=True)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape, mk, repr(e)))
                    print(f"FAIL  {arch} x {shape} x {mk}: {e}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("\nall combos lowered + compiled OK")


if __name__ == "__main__":
    main()
