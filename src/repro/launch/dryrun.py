import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh, the parameter/optimizer/
cache ShapeDtypeStruct trees with their NamedShardings, lowers the right
step function (train_step / prefill_step / serve_step), compiles it, and
records:

  * compiled.memory_analysis()  -> bytes/device (proves it fits)
  * compiled.cost_analysis()    -> HLO FLOPs / bytes for the roofline
  * collective bytes by op type -> parsed from the optimized HLO

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json; the
roofline table (benchmarks/roofline.py, EXPERIMENTS.md) reads them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-medium-14b \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.shapes import SHAPES
from repro.launch import hlo_analysis, hlo_cost, specs
from repro.launch.mesh import make_production_mesh
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.runtime import sharding, train_loop

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _serve_params_shape(cfg: ModelConfig, p_shape):
    """Quantize the weight dtypes for posit-weight serving cells."""
    if not cfg.weight_posit:
        return p_shape
    from repro.models.layers import pcfg
    store = pcfg(cfg.weight_posit).storage_dtype

    def one(path, leaf):
        name = sharding._path_str(path)
        quantizable = (name.endswith("/w") or name == "tok_embed"
                       or name.endswith("moe/wi") or name.endswith("moe/wg")
                       or name.endswith("moe/wo"))
        if quantizable and leaf.dtype == jnp.float32 and len(leaf.shape) >= 2:
            return jax.ShapeDtypeStruct(leaf.shape, store)
        return leaf

    return jax.tree_util.tree_map_with_path(one, p_shape)


def _ef_shardings(p_shape, mesh, cfg, n_pods):
    n_data = mesh.shape.get("data", 1)
    pspecs = sharding.param_specs(p_shape, mesh, fsdp=True, n_data=n_data)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, P("pod", *s)), pspecs)


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str) -> dict:
    spec = SHAPES[shape]
    cfg = configs.config_for_cell(arch, shape)
    if multi_pod:
        cfg = dataclasses.replace(cfg, batch_axes=("pod", "data"))
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    n_pods = mesh.shape.get("pod", 1)

    p_shape = specs.params_shape(cfg)
    p_sh = sharding.param_shardings(p_shape, mesh, fsdp=cfg.fsdp)
    record = {"arch": arch, "shape": shape,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "kind": spec.kind, "ok": False}
    t0 = time.time()

    if spec.kind == "train":
        opt_cfg = adamw.AdamWConfig()
        opt_shape = jax.eval_shape(lambda p: adamw.init(p, opt_cfg), p_shape)
        opt_sh = sharding.param_shardings(opt_shape, mesh, fsdp=cfg.fsdp)
        batch_sds = specs.input_specs(cfg, spec)
        b_specs = sharding.batch_specs(batch_sds, mesh, cfg)
        b_sh = sharding.to_shardings(b_specs, mesh)
        step_sds = jax.ShapeDtypeStruct((), jnp.int32)
        step_sh = NamedSharding(mesh, P())
        compressed = multi_pod and bool(cfg.grad_compress)
        fn = train_loop.make_train_step(
            cfg, opt_cfg, n_pods=n_pods, compressed=compressed)
        metrics_sh = {"loss": step_sh, "grad_norm": step_sh}
        if compressed:
            ef_shape = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct((n_pods,) + l.shape,
                                               jnp.float32), p_shape)
            ef_sh = _ef_shardings(p_shape, mesh, cfg, n_pods)
            # pod-tiled batch: (n_pods, B/n_pods, ...)
            tiled_batch = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(
                    (n_pods, l.shape[0] // n_pods) + l.shape[1:], l.dtype),
                batch_sds)
            tb_sh = jax.tree.map(
                lambda l: NamedSharding(
                    mesh, P("pod", "data", *([None] * (len(l.shape) - 2)))),
                tiled_batch)
            jitted = jax.jit(fn, in_shardings=(p_sh, opt_sh, ef_sh, tb_sh,
                                               step_sh),
                             out_shardings=(p_sh, opt_sh, ef_sh, metrics_sh))
            args = (p_shape, opt_shape, ef_shape, tiled_batch, step_sds)
        else:
            jitted = jax.jit(fn, in_shardings=(p_sh, opt_sh, b_sh, step_sh),
                             out_shardings=(p_sh, opt_sh, metrics_sh))
            args = (p_shape, opt_shape, batch_sds, step_sds)

    elif spec.kind == "prefill":
        batch_sds = specs.input_specs(cfg, spec)
        b_sh = sharding.to_shardings(
            sharding.batch_specs(batch_sds, mesh, cfg), mesh)
        fn = train_loop.make_prefill_step(cfg)
        # §Perf: shard the *output* cache (batch + seq over the mesh) —
        # without out_shardings the compiler materializes it replicated
        cache_out_shape, logits_shape = jax.eval_shape(
            fn, p_shape, batch_sds)
        c_sh = sharding.to_shardings(
            sharding.cache_specs(cache_out_shape, mesh, cfg), mesh)
        l_sh = NamedSharding(mesh, sharding.filter_spec(
            P(sharding.batch_axes(spec.global_batch, mesh), "model"),
            logits_shape.shape, mesh))
        jitted = jax.jit(fn, in_shardings=(p_sh, b_sh),
                         out_shardings=(c_sh, l_sh))
        args = (p_shape, batch_sds)

    else:  # decode
        p_shape = _serve_params_shape(cfg, p_shape)
        p_sh = sharding.param_shardings(p_shape, mesh, fsdp=False)
        cache_shape = specs.cache_shape(cfg, spec)
        seq_shard = spec.global_batch == 1          # long-context cells
        c_specs = sharding.cache_specs(cache_shape, mesh, cfg,
                                       seq_axis_shard=seq_shard)
        c_sh = sharding.to_shardings(c_specs, mesh)
        tok_sds = specs.decode_token_spec(spec)
        tok_axes = sharding.batch_axes(spec.global_batch, mesh)
        tok_sh = NamedSharding(mesh, P(tok_axes))
        fn = train_loop.make_serve_step(cfg)
        logits_sh = NamedSharding(mesh, sharding.filter_spec(
            P(tok_axes, "model"), (spec.global_batch, cfg.vocab), mesh))
        jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, tok_sh),
                         out_shardings=(logits_sh, c_sh))
        args = (p_shape, cache_shape, tok_sds)

    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    if mem is not None:
        record["memory"] = {
            "argument_bytes_per_device": int(mem.argument_size_in_bytes),
            "output_bytes_per_device": int(mem.output_size_in_bytes),
            "temp_bytes_per_device": int(mem.temp_size_in_bytes),
            "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                         + mem.temp_size_in_bytes),
        }
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    # trip-count-aware analysis (XLA's cost_analysis counts loop bodies
    # once — wrong by ~n_layers for scanned stacks; see hlo_cost.py)
    trip = hlo_cost.analyze(hlo_text)
    flops = float(trip["flops"])
    byts = float(trip["bytes"])
    colls = {k: int(v) for k, v in trip["collectives"].items()}
    record["cost"] = {
        "flops_per_chip": flops,
        "bytes_per_chip": byts,
        "xla_once_through_flops": float(cost.get("flops", 0.0)),
        "xla_once_through_bytes": float(cost.get("bytes accessed", 0.0)),
    }
    record["collectives_per_chip"] = colls
    record["roofline"] = hlo_analysis.roofline_terms(
        flops_per_chip=flops, bytes_per_chip=byts,
        coll_bytes_per_chip=float(sum(colls.values())), n_chips=n_chips)
    mf = hlo_analysis.model_flops(cfg, spec)
    record["model_flops_total"] = mf
    total_hlo = flops * n_chips
    record["useful_flop_ratio"] = (mf / total_hlo) if total_hlo else None
    record["ok"] = True

    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape}__{record['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(record, f, indent=2)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--keep-going", action="store_true")
    args = ap.parse_args()

    cells = list(configs.all_cells()) if args.all else [
        (args.arch, s) for s in
        (configs.supported_shapes(args.arch) if args.shape is None
         else [args.shape])]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for arch, shape in cells:
        for multi in meshes:
            tag = f"{arch} x {shape} x {'2x16x16' if multi else '16x16'}"
            try:
                rec = run_cell(arch, shape, multi, args.out)
                mem = rec.get("memory", {})
                print(f"[OK] {tag}: lower={rec['lower_s']}s "
                      f"compile={rec['compile_s']}s "
                      f"flops/chip={rec['cost']['flops_per_chip']:.3e} "
                      f"peak/dev={mem.get('peak_bytes_per_device', 0)/2**30:.2f}GiB "
                      f"dominant={rec['roofline']['dominant']}",
                      flush=True)
            except Exception:
                failures += 1
                print(f"[FAIL] {tag}", flush=True)
                traceback.print_exc()
                if not args.keep_going:
                    raise
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
