import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (assignment MULTI-POD DRY-RUN).

For every (architecture × input shape × mesh) cell:
  * build the step function (train_step for train_4k, forward for
    prefill_32k, serve_step for decode_32k / long_500k),
  * ``jax.jit(...).lower(**input_specs)`` with explicit in/out shardings,
  * ``.compile()`` — success proves the sharding config is coherent,
  * print ``memory_analysis()`` (fits?) and ``cost_analysis()`` (FLOPs/bytes),
  * derive the three roofline terms (launch/roofline.py) and append the cell
    record to a JSON results file consumed by EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh single --out experiments/dryrun
"""

import argparse
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_arch, shape_applicable
from repro.configs.registry import ARCHS
from repro.distributed.sharding import (
    logical_to_physical,
    mesh_context,
    spec_tree_to_shardings,
)
from repro.launch import roofline as RL
from repro.launch.mesh import dp_total, make_production_mesh
from repro.models import build_model
from repro.train.optimizer import AdamW, AdamWConfig
from repro.train.train_step import make_train_step


def _shardings(tree_specs, mesh, multi_pod):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, logical_to_physical(s, multi_pod)),
        tree_specs,
        is_leaf=lambda s: isinstance(s, tuple)
        and all(x is None or isinstance(x, (str, tuple)) for x in s),
    )


def _bf16_params(abstract):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
        if s.dtype == jnp.float32 and len(s.shape) >= 2
        else s,
        abstract,
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: pathlib.Path, tag: str = "baseline"):
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "skipped" if not ok else "pending",
    }
    if not ok:
        rec["skip_reason"] = why
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    model = build_model(cfg, attn_impl="reference", remat=True)
    inputs, input_spec = model.input_specs(shape)

    with mesh_context(mesh, multi_pod):
        if shape.kind == "train":
            opt = AdamW(AdamWConfig(zero1=True))
            abstract_params = model.abstract_params()
            abstract_state = opt.abstract_state(abstract_params)
            p_shard = _shardings(model.param_specs(), mesh, multi_pod)
            s_shard = _shardings(
                opt.state_specs(model.param_defs(), dp_total(mesh)), mesh, multi_pod
            )
            b_shard = _shardings(input_spec, mesh, multi_pod)
            step = make_train_step(model, opt, microbatches=cfg.train_microbatches)
            repl = NamedSharding(mesh, P())
            out_shard = (
                p_shard,
                s_shard,
                {"loss": repl, "grad_norm": repl, "lr": repl},
            )
            jf = jax.jit(
                step,
                in_shardings=(p_shard, s_shard, b_shard),
                out_shardings=out_shard,
                donate_argnums=(0, 1),
            )
            args = (abstract_params, abstract_state, inputs)
            model_flops = RL.train_model_flops(
                model.n_active_params(), shape.global_batch * shape.seq_len
            )
        elif shape.kind == "prefill":
            abstract_params = _bf16_params(model.abstract_params())
            p_shard = _shardings(model.param_specs(), mesh, multi_pod)
            b_shard = _shardings(input_spec, mesh, multi_pod)
            jf = jax.jit(
                model.forward_step,
                in_shardings=(p_shard, b_shard),
            )
            args = (abstract_params, inputs)
            model_flops = (
                2.0 * model.n_active_params() * shape.global_batch * shape.seq_len
            )
        else:  # decode
            abstract_params = _bf16_params(model.abstract_params())
            p_shard = _shardings(model.param_specs(), mesh, multi_pod)
            c_shard = _shardings(input_spec["caches"], mesh, multi_pod)
            t_shard = _shardings(input_spec["token"], mesh, multi_pod)
            pos_shard = NamedSharding(mesh, P())

            def serve_step(params, caches, token, pos):
                return model.decode_step(params, caches, token, pos)

            jf = jax.jit(
                serve_step,
                in_shardings=(p_shard, c_shard, t_shard, pos_shard),
                out_shardings=(NamedSharding(mesh, P()), c_shard),
                donate_argnums=(1,),
            )
            args = (
                abstract_params,
                inputs["caches"],
                inputs["token"],
                inputs["pos"],
            )
            model_flops = RL.decode_model_flops(
                model.n_active_params(), shape.global_batch
            )

        lowered = jf.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # persist the optimized HLO so roofline re-analysis never needs a recompile
    import gzip

    hlo_path = out_dir / f"hlo__{tag}__{arch}__{shape_name}__{mesh_name}.txt.gz"
    with gzip.open(hlo_path, "wt") as fh:
        fh.write(compiled.as_text())

    mem = compiled.memory_analysis()
    print(f"[{arch} × {shape_name} × {mesh_name}] MEMORY:", mem)
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    print(
        f"[{arch} × {shape_name} × {mesh_name}] COST: flops={ca.get('flops', 0):.3e} "
        f"bytes={ca.get('bytes accessed', 0):.3e}"
    )
    rl = RL.roofline_from_compiled(
        compiled, model_flops=model_flops, n_chips=n_chips, device_kind=RL.V5E
    )

    per_dev_bytes = (
        getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        - getattr(mem, "alias_size_in_bytes", 0)
        + getattr(mem, "temp_size_in_bytes", 0)
    )
    rec.update(
        status="ok",
        n_chips=n_chips,
        n_params=model.n_params(),
        n_active_params=model.n_active_params(),
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "per_device_total": per_dev_bytes,
            "fits_16G": bool(per_dev_bytes < 16e9),
        },
        roofline=rl.as_dict(),
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()

    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi_pod in meshes:
                mesh_name = "multi" if multi_pod else "single"
                path = out_dir / f"{args.tag}__{arch}__{shape}__{mesh_name}.json"
                if path.exists():
                    print(f"skip existing {path.name}")
                    continue
                try:
                    rec = run_cell(arch, shape, multi_pod, out_dir, tag=args.tag)
                except Exception as e:  # record failures, keep sweeping
                    failures += 1
                    rec = {
                        "arch": arch,
                        "shape": shape,
                        "mesh": mesh_name,
                        "status": "error",
                        "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-4000:],
                    }
                    print(f"[{arch} × {shape} × {mesh_name}] FAILED: {e}")
                path.write_text(json.dumps(rec, indent=2))
                print(f"wrote {path.name} status={rec['status']}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
