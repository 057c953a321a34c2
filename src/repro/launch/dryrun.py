import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.distributed import context as dist_ctx
from repro.distributed import sharding as shd
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim import AdamW, AdamWState
from repro.roofline import (collective_op_counts, cost_dict, memory_stats,
                            model_flops, roofline_terms)
from repro.roofline import hlo_cost
from repro.train import TrainState, make_train_step

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"
LAST_HLO = ""  # set by lower_cell; used by tools/profile_cell.py


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_shardings(cfg, shape, mesh, batch_spec_tree):
    out = {}
    for k, v in batch_spec_tree.items():
        if k == "positions":      # (3, B, S): batch on dim 1
            ba = shd.batch_axes(mesh)
            ok = shape.global_batch % shd.batch_axis_size(mesh) == 0
            out[k] = NamedSharding(mesh, P(None, ba if ok else None, None))
        elif k == "cur_pos":
            out[k] = NamedSharding(mesh, P())
        else:
            out[k] = NamedSharding(
                mesh, shd.batch_spec(mesh, shape.global_batch, len(v.shape)))
    return out


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               microbatches: int = 1, donate: bool = True):
    """Lower + compile one (arch x shape x mesh) cell; return the report."""
    cfg = configs.get(arch)
    shape = configs.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    dist_ctx.set_mesh(mesh)       # layers with shard_map paths pick it up
    model = build_model(cfg)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    params_abs = S.abstract_params(model)
    p_shards = shd.param_shardings(params_abs, mesh)
    batch_abs = S.input_specs(cfg, shape)

    with mesh:
        if shape.kind == "train":
            moment_dt = (jnp.bfloat16 if cfg.param_dtype == "bfloat16"
                         else jnp.float32)
            opt = AdamW(moment_dtype=moment_dt)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            mu_specs = shd.opt_state_specs(params_abs, mesh)
            o_shards = AdamWState(step=NamedSharding(mesh, P()),
                                  mu=_named(mesh, mu_specs),
                                  nu=_named(mesh, mu_specs))
            step_fn = make_train_step(model, opt, microbatches=microbatches)
            state_abs = TrainState(params=params_abs, opt=opt_abs, comp=None)
            state_sh = TrainState(params=p_shards, opt=o_shards, comp=None)
            b_shards = _batch_shardings(cfg, shape, mesh, batch_abs)
            jitted = jax.jit(step_fn,
                             in_shardings=(state_sh, b_shards),
                             donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(state_abs, batch_abs)
        elif shape.kind == "prefill":
            caches_abs = S.abstract_caches(model, shape)
            c_shards = shd.cache_shardings(cfg, caches_abs, mesh,
                                           shape.global_batch)
            b_shards = _batch_shardings(cfg, shape, mesh, batch_abs)
            fn = lambda p, b, c: model.prefill(p, b, c)
            jitted = jax.jit(fn, in_shardings=(p_shards, b_shards, c_shards),
                             donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(params_abs, batch_abs, caches_abs)
        else:  # decode
            caches_abs = S.abstract_caches(model, shape)
            c_shards = shd.cache_shardings(cfg, caches_abs, mesh,
                                           shape.global_batch)
            tok_sh = NamedSharding(
                mesh, shd.batch_spec(mesh, shape.global_batch, 1))
            fn = lambda p, t, c, pos: model.decode_step(p, t, c, pos)
            jitted = jax.jit(fn, in_shardings=(p_shards, tok_sh, c_shards,
                                               NamedSharding(mesh, P())),
                             donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(
                params_abs, batch_abs["tokens"], caches_abs,
                batch_abs["cur_pos"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = memory_stats(compiled)
    print(compiled.memory_analysis())
    costs = cost_dict(compiled)
    print({k: v for k, v in costs.items()
           if k in ("flops", "bytes accessed", "utilization")})
    hlo = compiled.as_text()
    global LAST_HLO
    LAST_HLO = hlo            # kept for offline profiling (tools/profile_cell)
    # post-SPMD HLO is the PER-DEVICE program: analyze() yields per-chip
    # flops/bytes/collective traffic, trip-count-aware (hlo_cost docstring)
    cost = hlo_cost.analyze(hlo)
    coll_counts = collective_op_counts(hlo)

    flops = float(cost.flops)                 # per chip
    bytes_hbm = float(cost.bytes)             # per chip
    coll_total = float(cost.collective_bytes)  # per chip
    terms = roofline_terms(flops=flops, bytes_hbm=bytes_hbm,
                           bytes_collective=coll_total, chips=1)
    mflops = model_flops(cfg, shape) / chips   # per-chip share

    report = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": shape.kind,
        "microbatches": microbatches,
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_hbm,
        "collective_bytes_per_chip": int(coll_total),
        "collective_by_kind": {k: int(v)
                               for k, v in cost.collective_by_kind.items()},
        "collective_counts": coll_counts,
        "xla_cost_analysis_flops": float(costs.get("flops", 0.0)),
        "model_flops_per_chip": mflops,
        "useful_flops_ratio": (mflops / flops) if flops else None,
        "memory": mem,
        "bytes_per_chip": (mem["argument_size_in_bytes"]
                           + mem["temp_size_in_bytes"]) // max(chips, 1),
        **terms,
    }
    return report


def run_cells(cells, *, multi_pod: bool, out_dir: Path, tag: str = ""):
    out_dir.mkdir(parents=True, exist_ok=True)
    for arch, shape_name, skipped in cells:
        mesh_tag = "pod2" if multi_pod else "pod1"
        name = f"{arch}__{shape_name}__{mesh_tag}{tag}.json"
        path = out_dir / name
        if path.exists():
            print(f"[skip existing] {name}")
            continue
        if skipped:
            json.dump({"arch": arch, "shape": shape_name, "ok": True,
                       "skipped": True,
                       "reason": "full-attention@500k (DESIGN.md)"},
                      open(path, "w"), indent=1)
            print(f"[documented skip] {name}")
            continue
        print(f"=== {arch} x {shape_name} ({mesh_tag}) ===", flush=True)
        try:
            rep = lower_cell(arch, shape_name, multi_pod=multi_pod)
            print(f"  ok: compile={rep['compile_s']}s dominant="
                  f"{rep['dominant']} frac={rep['roofline_fraction']:.3f}",
                  flush=True)
        except Exception as e:  # record failures — they are bugs to fix
            rep = {"arch": arch, "shape": shape_name, "ok": False,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
            print(f"  FAILED: {type(e).__name__}: {str(e)[:200]}", flush=True)
        json.dump(rep, open(path, "w"), indent=1, default=str)


def main():
    # the production meshes need 512 devices: forced host devices, set
    # before the first jax computation
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()

    cells = configs.cells(include_skipped=True)
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for mp in meshes:
        run_cells(cells, multi_pod=mp, out_dir=Path(args.out))


if __name__ == "__main__":
    main()
