import os
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
    ).strip()
# ^ MUST precede any jax-touching import: jax locks device count on first init.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we AOT-compile the REAL step function (train_step with Adam
update for train cells; prefill / decode_step for serving cells) against
ShapeDtypeStruct inputs on the production mesh, then record:

* ``compiled.memory_analysis()``  — proves the cell fits per-device HBM;
* ``compiled.cost_analysis()``    — XLA's own counters (scan-body-once!);
* our HLO-parsed per-device costs (while-loop corrected) + roofline terms.

Artifacts land in ``--out`` as one JSON per cell; EXPERIMENTS.md §Dry-run
and §Roofline are generated from them (benchmarks/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape decode_32k --mesh both
  python -m repro.launch.dryrun --all --mesh single --out runs/dryrun
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, arch_shapes, dryrun_cells, get_config
from repro.configs.base import TrainConfig
from repro.distributed import sharding as shard
from repro.distributed.hlo_analysis import analyze_hlo
from repro.distributed.roofline import roofline_from_cost
from repro.launch.mesh import make_production_mesh
from repro.models import model_zoo
from repro.models.model_zoo import build, cache_specs, input_specs, serve_table_spec
from repro.optim import adam_init
from repro.train.train_step import TrainState, make_train_step
from repro.utils import get_logger, tree_bytes

log = get_logger("dryrun")


def _abstract_opt_state(params):
    return jax.eval_shape(adam_init, params)


def _model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train / 2·N·D inference (N=active params)."""
    n_active = model_zoo.count_params_analytic(cfg, active_only=True)
    if shape.kind == "train":
        return 6.0 * n_active * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per request


def build_cell(arch: str, shape_name: str, head: str | None = None,
               overrides: dict | None = None):
    """→ (jitted_fn, example_args pytree of ShapeDtypeStruct, meta)."""
    cfg = get_config(arch)
    if head:
        cfg = cfg.replace(head=head)
    for k, v in (overrides or {}).items():
        if k.startswith("ds."):
            cfg = cfg.replace(ds=cfg.ds.replace(**{k[3:]: v}))
        else:
            cfg = cfg.replace(**{k: v})
    shape = SHAPES[shape_name]
    bundle = build(cfg)
    mesh = None  # bound by caller via `with jax.set_mesh(mesh):`

    params, ds_state = bundle.abstract_params()
    specs = input_specs(cfg, shape)
    return cfg, shape, bundle, params, ds_state, specs


def lower_cell(mesh, arch: str, shape_name: str, head: str | None = None, donate: bool = True,
               overrides: dict | None = None):
    cfg, shape, bundle, params, ds_state, specs = build_cell(arch, shape_name, head, overrides)
    p_shard = shard.param_shardings(mesh, params)
    in_shard = shard.input_shardings(mesh, cfg, specs, shape)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        # Production microbatching: big archs accumulate gradients over
        # microbatches (divides activation memory; per-device HBM budget
        # is 16 GB on v5e). Global batch 256 stays 16/32-way DP-divisible.
        n_params = model_zoo.count_params_analytic(cfg)
        micro = 8 if n_params > 4e9 else (4 if n_params > 1.5e9 else 1)
        tcfg = TrainConfig(microbatches=micro)
        step = make_train_step(bundle, tcfg)
        opt = _abstract_opt_state(params)
        opt_shard = type(opt)(
            step=repl,
            m=shard.param_shardings(mesh, opt.m),
            v=shard.param_shardings(mesh, opt.v),
        )
        if cfg.head == "ds":
            ds_shard = type(ds_state)(mask=NamedSharding(mesh, P(None, "model")))
        else:
            ds_shard = None

        state = TrainState(params=params, opt=opt, ds_state=ds_state)
        state_shard = TrainState(params=p_shard, opt=opt_shard, ds_state=ds_shard)
        fn = jax.jit(
            step,
            in_shardings=(state_shard, in_shard),
            donate_argnums=(0,) if donate else (),
        )
        args = (state, specs)
    elif shape.kind == "prefill":
        table = serve_table_spec(cfg)
        t_shard = shard.serve_table_shardings(mesh, table) if table is not None else None
        if cfg.head != "ds":
            table, t_shard = ds_state, None

        def fn_prefill(params, table, batch):
            return bundle.prefill(params, table, batch)

        fn = jax.jit(fn_prefill, in_shardings=(p_shard, t_shard, in_shard))
        args = (params, table, specs)
    else:  # decode
        table = serve_table_spec(cfg)
        t_shard = shard.serve_table_shardings(mesh, table) if table is not None else None
        if cfg.head != "ds":
            table, t_shard = ds_state, None
        cache = cache_specs(cfg, shape)
        c_shard = shard.cache_shardings(mesh, cfg, cache, shape)
        pos = jax.ShapeDtypeStruct((), jnp.int32)

        def fn_decode(params, table, cache, token, pos):
            return bundle.decode_step(params, table, cache, token, pos)

        fn = jax.jit(
            fn_decode,
            in_shardings=(p_shard, t_shard, c_shard, in_shard["token"], repl),
            donate_argnums=(2,) if donate else (),
        )
        args = (params, table, cache, specs["token"], pos)
    return fn, args, cfg, shape


def run_cell(mesh, mesh_name: str, arch: str, shape_name: str, head=None, hlo_dir=None,
             overrides: dict | None = None, tag: str = ""):
    t0 = time.time()
    fn, args, cfg, shape = lower_cell(mesh, arch, shape_name, head, overrides=overrides)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    cost = analyze_hlo(txt)
    n_dev = mesh.devices.size
    rf = roofline_from_cost(cost, n_devices=n_dev, model_flops=_model_flops(cfg, shape))

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "head": head or cfg.head,
        "tag": tag,
        "overrides": {k: str(v) for k, v in (overrides or {}).items()},
        "n_devices": int(n_dev),
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        },
        "xla_cost_analysis": {
            "flops": float(ca.get("flops", 0) or 0),
            "bytes_accessed": float(ca.get("bytes accessed", 0) or 0),
        },
        "hlo_cost": {k: v for k, v in cost.items()},
        "roofline": rf.to_dict(),
        "param_bytes_global": tree_bytes(args[0].params if shape.kind == "train" else args[0]),
    }
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(hlo_dir, f"{arch}__{shape_name}__{mesh_name}.hlo.txt"), "w") as f:
            f.write(txt)
    print(
        f"[dryrun] {arch:>22s} x {shape_name:<12s} x {mesh_name:<6s} OK  "
        f"compile={t_compile:6.1f}s  flops/dev={cost['flops']:.3e}  "
        f"hbm/dev={cost['bytes']:.3e}B  coll/dev={cost['coll_wire_bytes']:.3e}B  "
        f"bottleneck={rf.bottleneck}  temp={rec['memory_analysis']['temp_bytes']/2**30:.2f}GiB"
    )
    print("  memory_analysis:", mem)
    print("  cost_analysis: flops=%.4g bytes=%.4g" % (
        rec["xla_cost_analysis"]["flops"], rec["xla_cost_analysis"]["bytes_accessed"]))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--head", choices=["ds", "full"], default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="runs/dryrun")
    ap.add_argument("--hlo-dir", type=str, default=None)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. remat=dots, ds.serve_kernel=grouped)")
    ap.add_argument("--tag", type=str, default="")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v in ("True", "False"):
            v = v == "True"
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        overrides[k] = v

    cells = []
    if args.all:
        cells = dryrun_cells()
    else:
        assert args.arch, "--arch required unless --all"
        shapes = [SHAPES[args.shape]] if args.shape else arch_shapes(args.arch)
        cells = [(args.arch, s) for s in shapes]

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", make_production_mesh(multi_pod=True)))

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        for mesh_name, mesh in meshes:
            tag = f"{arch}__{shape.name}__{mesh_name}" + (f"__{args.head}" if args.head else "") + (f"__{args.tag}" if args.tag else "")
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                continue
            try:
                rec = run_cell(mesh, mesh_name, arch, shape.name, args.head, args.hlo_dir,
                               overrides=overrides, tag=args.tag)
            except Exception as e:  # noqa: BLE001 — record per-cell failure
                failures += 1
                rec = {
                    "arch": arch, "shape": shape.name, "mesh": mesh_name,
                    "ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:],
                }
                print(f"[dryrun] {arch} x {shape.name} x {mesh_name} FAILED: {e}")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    print(f"[dryrun] done, {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
