import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
record memory/cost/collective analysis (EXPERIMENTS.md §Dry-run, §Roofline).

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
Results land in experiments/dryrun/<arch>_<shape>_<mesh>[_cim].json.
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import all_archs, get_config
from repro.configs.base import SHAPES, shape_applicable
from repro.core.cim_layers import CIMConfig
from repro.launch import hlo_analysis, specs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (init_train_state, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models import transformer as tf
from repro.optim import AdamWConfig

ALIAS = {a: a for a in all_archs()}
ALIAS.update({
    "phi3.5-moe-42b-a6.6b": "phi35_moe", "mixtral-8x22b": "mixtral_8x22b",
    "minitron-4b": "minitron_4b", "qwen2-7b": "qwen2_7b",
    "olmo-1b": "olmo_1b", "granite-8b": "granite_8b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "internvl2-76b": "internvl2_76b", "mamba2-1.3b": "mamba2_1_3b",
    "whisper-medium": "whisper_medium",
})

PRETTY = {v: k for k, v in ALIAS.items() if k != v}


def _mem_dict(compiled) -> Dict[str, Any]:
    try:
        m = compiled.memory_analysis()
    except Exception:
        return {}
    if m is None:
        return {}
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes"]
    out = {}
    for k in keys:
        if hasattr(m, k):
            out[k] = int(getattr(m, k))
    return out


def _cost_dict(compiled) -> Dict[str, float]:
    try:
        c = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    return {k: float(v) for k, v in dict(c).items()
            if isinstance(v, (int, float))}


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             cim_mode: str = "bypass", out_dir: str = "experiments/dryrun",
             attn_impl: str = "jnp", tag: str = "",
             remat_policy: str = "full",
             compress_grads: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    cfg = cfg.replace(cim=CIMConfig(mode=cim_mode, max_gamma=2.0**16),
                      attn_impl=attn_impl, remat_policy=remat_policy)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg.name, shape_name, cfg.family)
    result: Dict[str, Any] = {
        "arch": cfg.name, "shape": shape_name, "mesh": mesh_kind,
        "cim_mode": cim_mode, "kind": shape.kind, "attn_impl": attn_impl,
        "tag": tag,
    }
    if not ok:
        result["status"] = "skipped"
        result["reason"] = why
        _dump(result, out_dir)
        return result

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            inputs = specs.input_specs(cfg, shape)
            in_specs = specs.batch_specs(inputs, mesh)
            in_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), in_specs)

            if shape.kind == "train":
                state = jax.eval_shape(
                    lambda: init_train_state(cfg, jax.random.PRNGKey(0),
                                             compress_grads=compress_grads))
                pspec = specs.param_specs(state["params"], mesh)
                sspec = {"params": pspec,
                         "opt": {"m": pspec, "v": pspec, "step": P()}}
                if compress_grads:
                    sspec["err"] = pspec
                sshard = jax.tree.map(lambda s: NamedSharding(mesh, s), sspec)
                step = make_train_step(cfg, AdamWConfig(),
                                       compress_grads=compress_grads)
                jitted = jax.jit(step, in_shardings=(sshard, in_shard),
                                 out_shardings=(sshard, None),
                                 donate_argnums=(0,))
                lowered = jitted.lower(state, inputs)
            elif shape.kind == "prefill":
                params = jax.eval_shape(
                    lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
                pspec = specs.param_specs(params, mesh)
                pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
                step = make_prefill_step(cfg)
                jitted = jax.jit(step, in_shardings=(pshard, in_shard))
                lowered = jitted.lower(params, inputs)
            else:  # decode
                def _mk_params():
                    p = tf.init_params(cfg, jax.random.PRNGKey(0))
                    if cim_mode == "deploy":
                        from repro.core.cim_layers import \
                            quantize_params_for_serving
                        p = quantize_params_for_serving(p, cfg.cim.r_w)
                    return p
                params = jax.eval_shape(_mk_params)
                pspec = specs.param_specs(params, mesh)
                pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
                cache = inputs["cache"]
                cshard = in_shard["cache"]
                tshard = in_shard["tokens"]
                step = make_serve_step(cfg)
                jitted = jax.jit(step, in_shardings=(pshard, cshard, tshard),
                                 out_shardings=(None, cshard),
                                 donate_argnums=(1,))
                lowered = jitted.lower(params, cache, inputs["tokens"])

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            result["status"] = "ok"
            result["lower_s"] = round(t_lower, 1)
            result["compile_s"] = round(t_compile, 1)
            result["memory"] = _mem_dict(compiled)
            result["cost"] = _cost_dict(compiled)
            try:
                hlo = compiled.as_text()
                result.update(hlo_analysis.analyze(hlo))
            except Exception as e:   # pragma: no cover
                result["collectives_error"] = str(e)
    except Exception as e:
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    _dump(result, out_dir)
    return result


def _dump(result: Dict[str, Any], out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    tag = "" if result.get("cim_mode", "bypass") == "bypass" else \
        f"_{result['cim_mode']}"
    if result.get("attn_impl", "jnp") != "jnp":
        tag += f"_{result['attn_impl']}"
    if result.get("tag"):
        tag += f"_{result['tag']}"
    name = (f"{ALIAS.get(result['arch'], result['arch'])}"
            f"_{result['shape']}_{result['mesh']}{tag}.json")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--cim-mode", default="bypass",
                    choices=["bypass", "fakequant", "deploy"])
    ap.add_argument("--attn-impl", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--remat-policy", default="full", choices=["full", "dots"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = all_archs() if args.arch is None else [ALIAS.get(args.arch,
                                                             args.arch)]
    shapes = list(SHAPES) if args.shape is None else [args.shape]

    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                t0 = time.time()
                r = run_cell(arch, shape, mesh_kind, cim_mode=args.cim_mode,
                             attn_impl=args.attn_impl, tag=args.tag,
                             remat_policy=args.remat_policy,
                             compress_grads=args.compress_grads,
                             out_dir=args.out)
                status = r["status"]
                extra = ""
                if status == "ok":
                    flops = r.get("hlo_flops", r.get("cost", {}).get("flops", 0))
                    extra = (f" flops/dev={flops:.3e}"
                             f" coll={r.get('collective_bytes', 0):.3e}B"
                             f" compile={r.get('compile_s')}s")
                elif status == "error":
                    extra = " " + r.get("error", "")[:160]
                print(f"[{mesh_kind:6s}] {arch:20s} {shape:12s} {status}{extra}",
                      flush=True)


if __name__ == "__main__":
    main()
