import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent on the
production mesh (16x16 single pod / 2x16x16 multi-pod): sharding rules apply,
the collective schedule exists, and memory_analysis shows the step fits.
cost_analysis + the optimized-HLO collective parse feed EXPERIMENTS.md
SS Dry-run / SS Roofline.

Usage:
  python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k
  python -m repro.launch.dryrun --all --json results.json
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
"""
import argparse        # noqa: E402
import json            # noqa: E402
import sys             # noqa: E402
import time            # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from .. import configs                                      # noqa: E402
from ..configs.base import (SHAPES, ModelConfig, ShapeSpec,  # noqa: E402
                            cell_supported, input_specs)
from ..models import decode as dec                          # noqa: E402
from ..models import transformer as tfm                     # noqa: E402
from ..models.layers import abstract_params, axes_tree      # noqa: E402
from ..sharding import rules                                # noqa: E402
from ..train.optimizer import AdamWConfig                   # noqa: E402
from .mesh import make_production_mesh                      # noqa: E402
from .roofline import from_compiled                         # noqa: E402

# The production meshes are v5e pods; their roofline uses its published peaks.
TARGET_KIND = "TPU v5 lite"

BATCH_AXES = {
    "tokens": ("batch", None), "labels": ("batch", None),
    "frames": ("batch", None, None), "patches": ("batch", None, None),
}


def _shardings_for_batch(mesh, specs: dict):
    return {k: NamedSharding(mesh, rules.spec_for(mesh, BATCH_AXES[k], v.shape))
            for k, v in specs.items()}


def _param_trees(cfg: ModelConfig, mesh):
    spec = tfm.model_spec(cfg)
    params = abstract_params(spec)
    axes = axes_tree(spec)
    shardings = jax.tree.map(
        lambda ax, s: NamedSharding(mesh, rules.spec_for(mesh, ax, s.shape)),
        axes, params,
        is_leaf=lambda x: isinstance(x, tuple) and not isinstance(x, dict))
    return params, shardings


def _opt_trees(params, shardings, opt_dtype=jnp.float32):
    # PERF (SSPerf, llama4/train_4k iter 3): 400B-param archs cannot hold
    # fp32 m+v on 16GB/chip even at 512 chips; bf16 second/first moments
    # (stochastic-rounding-friendly) halve optimizer bytes.
    f = lambda s: jax.ShapeDtypeStruct(s.shape, opt_dtype)
    state = {"m": jax.tree.map(f, params), "v": jax.tree.map(f, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    shard = {"m": shardings, "v": shardings,
             "step": NamedSharding(shardings_mesh(shardings), P())}
    return state, shard


def shardings_mesh(shardings):
    return jax.tree.leaves(shardings)[0].mesh


def _cache_trees(cfg: ModelConfig, shape: ShapeSpec, mesh,
                 cache_dtype=jnp.float32):
    # PERF (SSPerf, internlm2/decode_32k iteration 2): a bf16 cache on the
    # CPU-lowered artifact forces a full-stack bf16<->f32 convert sandwich
    # around every per-layer cache update (f32 dots). f32 storage removes it
    # here; on real TPU the native bf16 MXU dot removes it with bf16 storage.
    specs = dec.cache_specs(cfg, shape, dtype=cache_dtype)
    struct = dec.cache_struct(cfg, shape)
    shardings = {}
    for name, s in specs.items():
        if name == "pos":
            shardings[name] = NamedSharding(mesh, P())
        else:
            axes = struct[name][1]
            shardings[name] = NamedSharding(
                mesh, rules.spec_for(mesh, axes, s.shape))
    return specs, shardings


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               accum: int = 8, chunk: int = 1024, verbose: bool = True,
               opt_dtype=jnp.float32, moe_ep: bool = False):
    import dataclasses
    cfg = configs.get(arch)
    if moe_ep and cfg.moe:
        # shard_map expert parallelism: experts shard over 'data', so the
        # param rule chain must lead with 'data' for this lowering.
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, ep=True))
        rules.LOGICAL_RULES["expert"] = ("data", "model", None)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        result = {"arch": arch, "shape": shape_name,
                  "mesh": "multi" if multi_pod else "single",
                  "status": "skip", "reason": why}
        if verbose:
            print(json.dumps(result), flush=True)
        return result
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules.set_mesh(mesh)
    t0 = time.time()
    try:
        batch_specs = input_specs(cfg, shape)
        batch_shard = _shardings_for_batch(mesh, batch_specs)
        params, pshard = _param_trees(cfg, mesh)

        if shape.kind == "train":
            opt_state, oshard = _opt_trees(params, pshard, opt_dtype)
            opt = AdamWConfig()
            a = accum if shape.global_batch % accum == 0 else 1

            def train_fn(p, s, b):
                from ..train.step import train_step
                return train_step(p, s, b, cfg=cfg, opt=opt, accum=a,
                                  chunk=chunk)

            fn = jax.jit(train_fn,
                         in_shardings=(pshard, oshard, batch_shard),
                         out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
                         donate_argnums=(0, 1))
            args = (params, opt_state, batch_specs)
        elif shape.kind == "prefill":
            def prefill_fn(p, b):
                return dec.prefill(p, cfg, b, chunk=chunk)

            fn = jax.jit(prefill_fn, in_shardings=(pshard, batch_shard))
            args = (params, batch_specs)
        else:  # decode
            cache_specs_, cshard = _cache_trees(cfg, shape, mesh)

            def serve_fn(p, c, b):
                return dec.decode_step(p, cfg, c, b)

            fn = jax.jit(serve_fn,
                         in_shardings=(pshard, cshard, batch_shard),
                         out_shardings=(NamedSharding(
                             mesh, rules.spec_for(mesh, ("batch", "vocab"),
                                                  (shape.global_batch, cfg.vocab))),
                             cshard),
                         donate_argnums=(1,))
            args = (params, cache_specs_, batch_specs)

        with mesh:
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            roof = from_compiled(compiled, chips, TARGET_KIND)

        tokens = shape.global_batch * (shape.seq_len if shape.kind == "train" else 1)
        if shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len
        mult = 6 if shape.kind == "train" else 2
        model_flops = mult * cfg.active_param_count() * tokens / chips
        result = {
            "arch": arch, "shape": shape_name,
            "mesh": "multi" if multi_pod else "single",
            "status": "ok", "chips": chips,
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "bytes_per_device": int(mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
            "arg_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "model_flops_per_device": model_flops,
            **roof.as_dict(),
            "useful_flops_ratio": model_flops / max(roof.flops_per_device, 1.0),
            "roofline_fraction": roof.compute_fraction(model_flops),
        }
    except Exception as e:  # noqa: BLE001 - dry-run failures are findings
        result = {"arch": arch, "shape": shape_name,
                  "mesh": "multi" if multi_pod else "single",
                  "status": "fail", "error": f"{type(e).__name__}: {e}"}
    finally:
        rules.set_mesh(None)
        if moe_ep:
            rules.LOGICAL_RULES["expert"] = ("model", None)
    if verbose:
        print(json.dumps(result), flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--accum", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in configs.ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape, False))
                cells.append((arch, shape, True))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        meshes = [args.multi_pod] if not args.both_meshes else [False, True]
        cells = [(args.arch, args.shape, m) for m in meshes]

    results = [lower_cell(a, s, multi_pod=m, accum=args.accum,
                          chunk=args.chunk) for a, s, m in cells]
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if r["status"] == "fail"]
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
