"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production mesh, extract memory analysis, cost analysis, roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --json results/dryrun.json
Flags: --multi-pod (2x16x16 mesh), --json <path>.

``main()`` forces 512 host CPU devices (:func:`force_host_devices`) before
JAX creates its backend; importing this module leaves the environment
alone. A library caller that builds its own mesh sets ``XLA_FLAGS`` itself
before JAX initializes.
"""
import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, ModelConfig, get_config, list_configs
from repro.core import hfsl
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import data_parallel_size, make_production_mesh
from repro.launch import roofline as rl
from repro.models import model as M
from repro.optim.optimizers import adamw
from repro.sharding import rules as R

# ---- perf knobs (EXPERIMENTS.md §Perf) ------------------------------------
if os.environ.get("REPRO_SSM_IMPL"):
    from repro.kernels import ops as _kops
    _kops.set_ssm_xla_impl(os.environ["REPRO_SSM_IMPL"])
if os.environ.get("REPRO_FLASH_BLOCKS"):
    from repro.kernels import ops as _kops2
    _bq, _bkv = map(int, os.environ["REPRO_FLASH_BLOCKS"].split(","))
    _kops2.set_flash_blocks(_bq, _bkv)

ASSIGNED = [
    "falcon-mamba-7b", "kimi-k2-1t-a32b", "recurrentgemma-2b", "qwen2-7b",
    "llava-next-mistral-7b", "qwen1.5-32b", "qwen2.5-32b", "qwen2.5-14b",
    "granite-moe-1b-a400m", "whisper-small",
]

# (arch, shape) pairs that are semantically inapplicable (DESIGN.md §6)
SKIPS = {
    ("whisper-small", "long_500k"):
        "enc-dec with full self+cross attention and a 448-position decoder; "
        "no sub-quadratic variant in its family",
}


def variant_for(cfg: ModelConfig, shape_name: str) -> ModelConfig:
    """long_500k on full-attention archs -> sliding-window variant."""
    if shape_name == "long_500k" and cfg.family in ("dense", "vlm", "moe") \
            and cfg.attn_variant != "sliding":
        return cfg.with_(attn_variant="sliding", sliding_window=4096)
    return cfg


def _input_sharding_tree(batch_structs, mesh, rules, *, cluster: bool):
    def leaf_spec(v):
        lead = "cluster" if cluster else "batch"
        axes = (lead,) + (None,) * (len(v.shape) - 1)
        p = R.fit_spec(R.spec_for(axes, mesh, rules), v.shape, mesh)
        return NamedSharding(mesh, p)
    return jax.tree.map(leaf_spec, batch_structs)


def _clusterize(batch_structs, n_clusters: int):
    def f(v):
        b = v.shape[0]
        if b % n_clusters != 0:
            raise ValueError(f"batch {b} does not split evenly over "
                             f"{n_clusters} clusters")
        return jax.ShapeDtypeStruct((n_clusters, b // n_clusters, *v.shape[1:]),
                                    v.dtype)
    return jax.tree.map(f, batch_structs)


def build_lowered(arch: str, shape_name: str, *, multi_pod: bool = False,
                  rules_override=None, remat: bool = True,
                  donate: bool = True, reduced: bool = False,
                  mesh=None):
    """Lower the appropriate step for (arch, shape) on the production mesh.

    Returns (lowered, meta) — meta carries cfg/shape/mesh info for reports.
    ``reduced=True`` shrinks config+shape for subprocess smoke tests.
    """
    from repro.configs.base import InputShape
    cfg = variant_for(get_config(arch), shape_name)
    shape = INPUT_SHAPES[shape_name]
    if reduced:
        cfg = variant_for(get_config(arch).reduced(), shape_name)
        cfg = cfg.with_(sliding_window=64)
        shape = InputShape(shape.name, 128, 16, shape.kind)
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 1
    for n in mesh.shape.values():
        chips *= n

    if shape.kind == "train":
        rules = rules_override or R.train_rules(cfg.family)
        C = data_parallel_size(mesh)
        opt = adamw(1e-4)
        state_spec = hfsl.hfsl_state_spec(cfg, C, opt, M.model_spec)
        state_structs = R.shape_structs(state_spec)
        state_sh = jax.tree.map(lambda p: NamedSharding(mesh, p),
                                R.partition_specs(state_spec, mesh, rules))
        batch_structs = _clusterize(M.input_specs(cfg, shape), C)
        batch_sh = _input_sharding_tree(batch_structs, mesh, rules,
                                        cluster=True)

        def loss_fn(params, batch, cfg_):
            return M.lm_loss(params, batch, cfg_, remat=remat)

        step = hfsl.make_hfsl_step(cfg, opt, loss_fn, always_sync=True)

        def train_step(state, batch):
            with R.use_rules(mesh, rules):
                return step(state, batch)

        jitted = jax.jit(train_step,
                         in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,) if donate else ())
        lowered = jitted.lower(state_structs, batch_structs)

    elif shape.kind == "prefill":
        rules = rules_override or (
            R.moe_serving_rules()
            if (cfg.family == "moe"
                and os.environ.get("REPRO_MOE_SERVE", "0") == "1")
            else dict(R.DEFAULT_RULES))
        param_spec = M.model_spec(cfg)
        param_structs = R.shape_structs(param_spec)
        param_sh = jax.tree.map(lambda p: NamedSharding(mesh, p),
                                R.partition_specs(param_spec, mesh, rules))
        batch_structs = M.input_specs(cfg, shape)
        batch_sh = _input_sharding_tree(batch_structs, mesh, rules,
                                        cluster=False)

        def prefill_step(params, batch):
            with R.use_rules(mesh, rules):
                return M.prefill(params, batch, cfg)

        lowered = jax.jit(prefill_step,
                          in_shardings=(param_sh, batch_sh)).lower(
            param_structs, batch_structs)

    else:  # decode
        rules = rules_override or (
            R.long_decode_rules() if shape.global_batch == 1
            else dict(R.DEFAULT_RULES))
        param_spec = M.model_spec(cfg)
        param_structs = R.shape_structs(param_spec)
        param_sh = jax.tree.map(lambda p: NamedSharding(mesh, p),
                                R.partition_specs(param_spec, mesh, rules))
        window = cfg.sliding_window if cfg.attn_variant == "sliding" else 0
        cache_len = min(window, shape.seq_len) if window else shape.seq_len
        cache_spec = M.cache_spec(cfg, shape.global_batch, cache_len)
        cache_structs = R.shape_structs(cache_spec)
        cache_sh = jax.tree.map(lambda p: NamedSharding(mesh, p),
                                R.partition_specs(cache_spec, mesh, rules))
        batch_structs = M.input_specs(cfg, shape)
        batch_sh = _input_sharding_tree(batch_structs, mesh, rules,
                                        cluster=False)
        pos_struct = jax.ShapeDtypeStruct((), jnp.int32)

        def serve_step(params, token, caches, pos):
            with R.use_rules(mesh, rules):
                return M.decode_step(params, token, caches, pos, cfg)

        jitted = jax.jit(serve_step,
                         in_shardings=(param_sh, batch_sh["token"],
                                       cache_sh, None),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(2,) if donate else ())
        lowered = jitted.lower(param_structs, batch_structs["token"],
                               cache_structs, pos_struct)

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
            "chips": chips, "kind": shape.kind,
            "family": cfg.family, "cfg": cfg, "shape_obj": shape}
    return lowered, meta


def _memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            rules_override=None, verbose: bool = True) -> dict:
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": SKIPS[(arch, shape_name)]}
    t0 = time.perf_counter()
    lowered, meta = build_lowered(arch, shape_name, multi_pod=multi_pod,
                                  rules_override=rules_override)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = _memory_analysis_dict(compiled)
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    xla_costs = {k: float(ca.get(k, 0.0)) for k in ("flops", "bytes accessed")}

    costs = rl.analyze_hlo_text(compiled.as_text())
    model_flops = rl.model_flops_for(meta["cfg"], meta["shape_obj"])
    roof = rl.Roofline.from_costs(
        costs, arch=arch, shape=shape_name, mesh=meta["mesh"],
        chips=meta["chips"], model_flops=model_flops, memory_analysis=mem)

    result = {
        "arch": arch, "shape": shape_name, "mesh": meta["mesh"],
        "chips": meta["chips"], "kind": meta["kind"], "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem, "xla_cost_analysis": xla_costs,
        "roofline": roof.asdict(),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh={meta['mesh']} OK "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        print(f"  memory_analysis: {mem}")
        print(f"  flops/dev={costs.flops:.3e} bytes/dev={costs.bytes_accessed:.3e} "
              f"coll/dev={costs.collective_bytes:.3e}")
        print(f"  terms: compute={roof.compute_s:.4f}s memory={roof.memory_s:.4f}s "
              f"collective={roof.collective_s:.4f}s -> {roof.bottleneck}-bound; "
              f"useful={roof.useful_ratio:.3f}")
    return result


def force_host_devices(n: int = 512) -> None:
    """Ask XLA's CPU backend for ``n`` host devices (the production mesh).

    Takes effect only before JAX creates its backend, so entry points call
    it first thing in ``main()``. A device count already in ``XLA_FLAGS``
    wins."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def main(argv=None) -> int:
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default="all")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    setup_compile_cache()

    archs = ASSIGNED if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    results = []
    failures = 0
    for a in archs:
        for s in shapes:
            try:
                results.append(run_one(a, s, multi_pod=args.multi_pod))
            except Exception as e:
                failures += 1
                traceback.print_exc()
                results.append({"arch": a, "shape": s, "status": "error",
                                "error": f"{type(e).__name__}: {e}"})
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
        print(f"[dryrun] wrote {args.json}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
