"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production meshes with 512 placeholder host devices.

For each cell this builds the REAL step function (train_step with
microbatched grad-accum + AdamW, or prefill/serve_step over the KV cache),
jits it with the full in/out shardings from sharding/rules.py, lowers with
ShapeDtypeStruct stand-ins (no allocation), compiles, and records
memory_analysis / cost_analysis / the collective schedule for §Roofline.

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out benchmarks/artifacts/dryrun
"""

import argparse
import json
import os
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import SHAPES, ModelConfig, ShapeSpec, cells_for
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.roofline import analysis
from repro.sharding import rules
from repro.training import optimizer as opt
from repro.training.train_step import TrainConfig, make_train_step

MICROBATCHES = {"train_4k": 8}


def _init_specs(cfg: ModelConfig):
    """ShapeDtypeStructs + logical axes for params without allocation."""
    key = jax.random.PRNGKey(0)
    p_shapes = jax.eval_shape(lambda k: registry.init(cfg, k)[0], key)
    # the logical-axes tree is static python data; building it requires the
    # arrays only for their .shape, which eval_shape provides — re-run init
    # under eval_shape capturing axes via a mutable cell
    cell = {}

    def capture(k):
        params, axes = registry.init(cfg, k)
        cell["axes"] = axes
        return params

    jax.eval_shape(capture, key)
    return p_shapes, cell["axes"]


def lower_cell(cfg: ModelConfig, spec: ShapeSpec, mesh, *,
               microbatches: int | None = None):
    """Lower + compile one (arch x shape) cell on a mesh. Returns results."""
    chips = mesh.devices.size
    p_specs, p_axes = _init_specs(cfg)
    p_sh = rules.tree_shardings(p_specs, p_axes, mesh)

    if spec.kind == "train":
        batch_axes = tuple(n for n in ("pod", "data")
                           if n in mesh.axis_names)
        tcfg = TrainConfig(
            microbatches=microbatches or MICROBATCHES.get(spec.name, 1),
            batch_axes=batch_axes)
        step = make_train_step(cfg, tcfg, param_shardings=p_sh)
        o_specs = jax.eval_shape(
            lambda p: {"opt": opt.init(p)}, p_specs)
        o_sh = {"opt": opt.OptState(
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            rules.tree_shardings(o_specs["opt"].m, p_axes, mesh),
            rules.tree_shardings(o_specs["opt"].v, p_axes, mesh))}
        b_specs, b_axes = registry.batch_spec(cfg, spec.global_batch,
                                              spec.seq_len)
        b_sh = rules.tree_shardings(b_specs, b_axes, mesh)
        metrics_sh = jax.tree.map(
            lambda _: jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()),
            {"lr": 0, "grad_norm": 0, "loss": 0})
        fn = jax.jit(step,
                     in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, metrics_sh),
                     donate_argnums=(0, 1))
        args = (p_specs, o_specs, b_specs)
        tokens = spec.global_batch * spec.seq_len

    elif spec.kind == "prefill":
        def prefill_fn(params, prompt):
            return registry.prefill(params, cfg, prompt)
        pr_spec, pr_axes = registry.prompt_spec(cfg, spec.global_batch,
                                                spec.seq_len)
        pr_sh = rules.sharding_for(pr_axes, pr_spec.shape, mesh)
        c_specs, c_axes = registry.cache_spec(cfg, spec.global_batch,
                                              spec.seq_len)
        c_sh = rules.tree_shardings(c_specs, c_axes, mesh)
        logits_sh = rules.sharding_for(
            ("batch", "vocab"), (spec.global_batch, cfg.padded_vocab), mesh)
        fn = jax.jit(prefill_fn, in_shardings=(p_sh, pr_sh),
                     out_shardings=(logits_sh, c_sh))
        args = (p_specs, pr_spec)
        tokens = spec.global_batch * spec.seq_len

    else:  # decode
        def serve_fn(params, cache, token, pos):
            return registry.decode_step(params, cfg, cache, token, pos)
        c_specs, c_axes = registry.cache_spec(cfg, spec.global_batch,
                                              spec.seq_len)
        c_sh = rules.tree_shardings(c_specs, c_axes, mesh)
        b = spec.global_batch
        tok_spec = jax.ShapeDtypeStruct((b,), jnp.int32)
        pos_spec = jax.ShapeDtypeStruct((b,), jnp.int32)
        tok_sh = rules.sharding_for(("batch",), (b,), mesh)
        logits_sh = rules.sharding_for(("batch", "vocab"),
                                       (b, cfg.padded_vocab), mesh)
        fn = jax.jit(serve_fn,
                     in_shardings=(p_sh, c_sh, tok_sh, tok_sh),
                     out_shardings=(logits_sh, c_sh),
                     donate_argnums=(1,))
        args = (p_specs, c_specs, tok_spec, pos_spec)
        tokens = spec.global_batch            # one new token per sequence

    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    roof = analysis.analyze(
        arch=cfg.name, shape=spec.name,
        mesh_name="x".join(str(s) for s in mesh.devices.shape),
        chips=chips, cost=cost, hlo_text=hlo, mem_stats=mem,
        model_flops_global=analysis.model_flops(cfg, spec.kind, tokens),
        kernel_traffic=analysis.kernel_traffic(cfg, spec, chips))
    return roof, compiled


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir=None,
             verbose=True):
    cfg = configs.get(arch)
    spec = SHAPES[shape_name]
    if spec.name == "long_500k" and not configs.long_context_ok(cfg):
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped",
                "reason": "pure full attention; long_500k needs "
                          "sub-quadratic mixer (DESIGN.md)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        roof, compiled = lower_cell(cfg, spec, mesh)
        row = roof.row()
        row["status"] = "ok"
        if verbose:
            mem = compiled.memory_analysis()
            print(f"[{arch} x {shape_name} x "
                  f"{'x'.join(str(s) for s in mesh.devices.shape)}] OK")
            print(f"  memory_analysis: temp="
                  f"{getattr(mem, 'temp_size_in_bytes', 0)/2**30:.2f}GiB "
                  f"args={getattr(mem, 'argument_size_in_bytes', 0)/2**30:.2f}GiB "
                  f"out={getattr(mem, 'output_size_in_bytes', 0)/2**30:.2f}GiB")
            print(f"  cost_analysis: flops/chip={roof.flops_per_chip:.3e} "
                  f"bytes/chip={roof.bytes_per_chip:.3e}")
            print(f"  roofline: compute={row['compute_ms']:.2f}ms "
                  f"memory={row['memory_ms']:.2f}ms "
                  f"collective={row['collective_ms']:.2f}ms "
                  f"dominant={row['dominant']}")
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        row = {"arch": arch, "shape": shape_name,
               "mesh": "multi" if multi_pod else "single",
               "status": "FAIL", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
        if verbose:
            print(f"[{arch} x {shape_name}] FAIL: {row['error']}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(row, f, indent=2, default=str)
    return row


def main():
    # 512 placeholder host devices for the production meshes; read when
    # the backend starts, which is after this (no device is touched at
    # import)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(configs.ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    rows = []
    if args.all:
        for arch in configs.ARCH_IDS:
            cfg = configs.get(arch)
            for spec in cells_for(cfg):
                for mp in meshes:
                    rows.append(run_cell(arch, spec.name, mp, args.out))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mp in meshes:
            rows.append(run_cell(args.arch, args.shape, mp, args.out))
    n_ok = sum(r["status"] == "ok" for r in rows)
    n_skip = sum(r["status"] == "skipped" for r in rows)
    print(f"\n{n_ok} ok / {n_skip} skipped / "
          f"{len(rows) - n_ok - n_skip} failed of {len(rows)} cells")


if __name__ == "__main__":
    main()
