import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first initialization). Placeholder host devices exist ONLY for this
# dry-run; smoke tests and benchmarks see the real single device.

"""Multi-pod dry-run: for every (architecture x input shape x mesh), AOT-lower
and compile the production step function against ShapeDtypeStruct inputs
(no allocation), then record memory analysis, cost analysis, and the
collective schedule for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config, get_shape, supports_shape
from repro.data.pipeline import batch_logical_axes, input_specs
from repro.launch import flops as flops_lib
from repro.launch.hlo_analysis import collective_bytes, roofline_terms
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch.serve import decode_rules_overrides, serve_options_for
from repro.launch.sharding import make_rules, sharding_for_tree, use_rules
from repro.launch.train import default_optimizer, make_train_state_specs
from repro.models import transformer as T
from repro.models.kvcache import cache_bytes, cache_logical_axes
from repro.optim import clip_by_global_norm
from repro.utils import get_logger, human_bytes, human_count, tree_bytes

log = get_logger("repro.dryrun")


def _lower_pair(cfg, shape, mesh, *, extra_rules: Optional[Dict] = None,
                window: int = 0, opts_set: frozenset = frozenset()):
    """Build + lower + compile the step for one (arch, shape, mesh).
    Returns (compiled, lowered, meta). opts_set: perf-iteration levers
    ('grads_constraint', 'sp', 'moe_dedup', 'mla_flashdecode')."""
    specs = input_specs(cfg, shape)
    rules_ov = dict(extra_rules or {})
    if "mtp" in opts_set and shape.kind in ("train", "prefill"):
        # manual tensor-parallel blocks with explicit bf16 AG/RS collectives
        rules_ov.setdefault("act_res_seq", "model")
        rules_ov.setdefault("_manual_tp", True)
    if "sp" in opts_set and shape.kind == "train":
        # Megatron-SP: shard the residual stream's seq dim over 'model' so the
        # per-layer activation collectives become RS/AG pairs instead of ARs.
        rules_ov.setdefault("act_res_seq", "model")
    if "mla_flashdecode" in opts_set and shape.kind == "decode" and cfg.use_mla:
        rules_ov.setdefault("act_kv_seq", ("model",))
        rules_ov.setdefault("kv_lora", None)
    if "moe2d" in opts_set and shape.kind == "decode" and cfg.num_experts:
        # weights-stationary 2D expert layout for decode
        rules_ov.setdefault("expert_embed", None)
        rules_ov.setdefault("expert_mlp", "data")
        rules_ov.setdefault("_moe_2d", True)
    if shape.kind == "decode":
        rules_ov = dict(decode_rules_overrides(cfg, shape, mesh), **rules_ov)
    rules = make_rules(cfg, mesh, rules_ov)
    p_axes = T.param_logical_axes(cfg)
    params_sh = sharding_for_tree(p_axes, mesh, rules)
    meta: Dict[str, Any] = {}

    if shape.kind == "train":
        optimizer = default_optimizer(cfg)
        state_abs, state_axes = make_train_state_specs(cfg, optimizer)
        state_sh = sharding_for_tree(state_axes, mesh, rules)
        batch_sh = sharding_for_tree(batch_logical_axes(cfg, shape), mesh, rules)

        def step(state, inputs):
            with use_rules(mesh, rules):
                batch = inputs["batch"]

                def lf(p):
                    if "bf16_gather" in opts_set:
                        # cast BEFORE the FSDP all-gathers so weights cross
                        # the wire in bf16 (grads still flow to f32 masters)
                        p = jax.tree_util.tree_map(
                            lambda a: a.astype(jnp.bfloat16)
                            if a.dtype == jnp.float32 else a, p)
                    return T.loss_fn(cfg, p, batch, window=window)

                (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(state["params"])
                if "grads_constraint" in opts_set:
                    # pin grads to the parameter shardings so GSPMD lowers the
                    # data-parallel reduction as reduce-scatter, not all-reduce
                    grads = jax.lax.with_sharding_constraint(
                        grads, sharding_for_tree(p_axes, mesh, rules))
                grads, gnorm = clip_by_global_norm(grads, 1.0)
                updates, opt2 = optimizer.update(grads, state["opt"], state["params"],
                                                 state["step"])
                params2 = jax.tree_util.tree_map(lambda p, u: (p + u).astype(p.dtype),
                                                 state["params"], updates)
                return ({"params": params2, "opt": opt2, "step": state["step"] + 1},
                        dict(metrics, loss=loss, grad_norm=gnorm))

        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None), donate_argnums=(0,))
        lowered = jitted.lower(state_abs, specs)
        meta["state_bytes"] = tree_bytes(state_abs)
    elif shape.kind == "prefill":
        batch_sh = sharding_for_tree(batch_logical_axes(cfg, shape), mesh, rules)

        def step(params, inputs):
            with use_rules(mesh, rules):
                return T.prefill(cfg, params, inputs["batch"], window=window)

        jitted = jax.jit(step, in_shardings=(params_sh, batch_sh))
        lowered = jitted.lower(T.abstract_params(cfg), specs)
    else:  # decode
        opts = serve_options_for(cfg, shape, mesh)
        opts = dataclasses_replace(opts, window=window) if window else opts
        enc_len = shape.seq_len // 2 if cfg.is_encoder_decoder else 0
        c_axes = cache_logical_axes(cfg, shape.global_batch, shape.seq_len, enc_len)
        cache_sh = sharding_for_tree(c_axes, mesh, rules)
        tok_sh = sharding_for_tree(("act_batch", None), mesh, rules)
        logits_sh = sharding_for_tree(("act_batch", "act_vocab"), mesh, rules)

        def step(params, cache, tokens, pos):
            with use_rules(mesh, rules):
                return T.serve_step(cfg, params, cache, tokens, pos, opts)

        jitted = jax.jit(step, in_shardings=(params_sh, cache_sh, tok_sh, None),
                         out_shardings=(logits_sh, cache_sh), donate_argnums=(1,))
        lowered = jitted.lower(T.abstract_params(cfg), specs["cache"], specs["tokens"],
                               specs["pos"])
        meta["cache_bytes"] = cache_bytes(cfg, shape.global_batch, shape.seq_len, enc_len)
        meta["seq_sharded_cache"] = opts.seq_sharded_cache
    compiled = lowered.compile()
    return compiled, lowered, meta


def dataclasses_replace(opts, **kw):
    import dataclasses

    return dataclasses.replace(opts, **kw)


def run_pair(arch: str, shape_name: str, *, multi_pod: bool, out_dir: Optional[str],
             window: int = 0, save_hlo: bool = False,
             extra_rules: Optional[Dict] = None, tag: str = "",
             opts_set: frozenset = frozenset(), cfg_overrides: Optional[Dict] = None
             ) -> Dict[str, Any]:
    import dataclasses as _dc

    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = get_shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    name = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    ok, why = supports_shape(cfg, shape)
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "status": "ok",
    }
    if not ok:
        result.update(status="skipped", reason=why)
        log.info("SKIP  %-50s %s", name, why)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(out_dir, name + ".json"), "w") as f:
                json.dump(result, f, indent=1)
        return result
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = int(np.prod(mesh.devices.shape))
        compiled, lowered, meta = _lower_pair(cfg, shape, mesh, window=window,
                                              extra_rules=extra_rules,
                                              opts_set=opts_set)
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        txt = compiled.as_text()
        coll_total, coll_by_kind = collective_bytes(txt)
        analytic = flops_lib.step_flops(cfg, shape, window=window)
        model_fl = flops_lib.model_flops_6nd(cfg, shape)
        param_bytes_total = tree_bytes(T.abstract_params(cfg))
        hbm_traffic = flops_lib.hbm_traffic_bytes(
            cfg, shape, chips=chips, param_bytes_total=param_bytes_total,
            cache_bytes_total=meta.get("cache_bytes", 0))
        rl = roofline_terms(
            device_kind=PRODUCTION_DEVICE_KIND, analytic_flops=analytic.total, chips=chips,
            hbm_bytes_per_chip=hbm_traffic,
            collective_bytes_per_chip=coll_total,
            model_flops=model_fl, hlo_flops_raw=float(ca.get("flops", 0.0)))
        result.update(
            compile_s=round(time.time() - t0, 1),
            chips=chips,
            param_bytes_total=param_bytes_total,
            state_bytes=meta.get("state_bytes"),
            cache_bytes=meta.get("cache_bytes"),
            seq_sharded_cache=meta.get("seq_sharded_cache"),
            memory={
                "argument_bytes_per_device": ma.argument_size_in_bytes,
                "output_bytes_per_device": ma.output_size_in_bytes,
                "temp_bytes_per_device": ma.temp_size_in_bytes,
                "peak_bytes_per_device": ma.peak_memory_in_bytes,
                "alias_bytes_per_device": ma.alias_size_in_bytes,
            },
            cost_analysis={k: ca[k] for k in ("flops", "bytes accessed") if k in ca},
            collective_bytes_per_device=coll_total,
            collective_by_kind=coll_by_kind,
            analytic_flops=analytic.total,
            analytic_detail=analytic.detail,
            model_flops_6nd=model_fl,
            hbm_traffic_bytes_per_chip=hbm_traffic,
            roofline=rl.as_dict(),
        )
        fits = (ma.argument_size_in_bytes + ma.temp_size_in_bytes) <= 16e9
        result["fits_16g_hbm"] = bool(fits)
        log.info(
            "OK    %-50s %5.1fs args=%s temp=%s coll=%s dom=%s t_dom=%.1fms",
            name, result["compile_s"],
            human_bytes(ma.argument_size_in_bytes), human_bytes(ma.temp_size_in_bytes),
            human_bytes(coll_total), rl.dominant,
            1e3 * max(rl.compute_s, rl.memory_s, rl.collective_s))
        if save_hlo and out_dir:
            with open(os.path.join(out_dir, name + ".hlo.txt"), "w") as f:
                f.write(txt)
    except Exception as e:  # noqa: BLE001 — a dry-run failure IS the signal
        result.update(status="failed", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-3000:])
        log.error("FAIL  %-50s %s: %s", name, type(e).__name__, str(e)[:200])
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, name + ".json"), "w") as f:
            json.dump(result, f, indent=1, default=float)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id or 'all'")
    ap.add_argument("--shape", default=None, help="shape name or 'all'")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--opt", default="", help="comma list: grads_constraint,sp,moe_dedup,mla_flashdecode")
    ap.add_argument("--set", default="", help="cfg overrides k=v,k=v (ints/floats)")
    args = ap.parse_args()
    opts_set = frozenset(filter(None, args.opt.split(",")))
    cfg_overrides = {}
    for kv in filter(None, args.set.split(",")):
        k, v = kv.split("=")
        try:
            cfg_overrides[k] = int(v)
        except ValueError:
            try:
                cfg_overrides[k] = float(v)
            except ValueError:
                cfg_overrides[k] = v
    archs = list(ASSIGNED_ARCHS) if (args.all or args.arch in (None, "all")) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape in (None, "all")) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                results.append(run_pair(arch, shape, multi_pod=mp, out_dir=args.out,
                                        window=args.window, save_hlo=args.save_hlo,
                                        tag=args.tag, opts_set=opts_set,
                                        cfg_overrides=cfg_overrides or None))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "failed" for r in results)
    log.info("dry-run complete: %d ok, %d skipped, %d FAILED", n_ok, n_skip, n_fail)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
