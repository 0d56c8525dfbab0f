"""Substrate: optimizers, checkpointing, data pipeline, HLO analysis, flops."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, restore_latest, save_checkpoint
from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import batch_logical_axes, input_specs, make_batch
from repro.launch import flops as flops_lib
from repro.launch.hlo_analysis import collective_bytes, parse_collectives, roofline_terms
from repro.optim import (
    adafactor,
    adamw,
    clip_by_global_norm,
    cosine_schedule,
    make_optimizer,
    sgdm,
    sparse_adamw,
)


# --- optimizers -------------------------------------------------------------


@pytest.mark.parametrize("make", [
    lambda: adamw(lambda s: 0.1),
    lambda: adafactor(lambda s: 0.5, min_dim_factored=4),
    lambda: sgdm(lambda s: 0.05),
    lambda: sparse_adamw(lambda s: 0.1),
])
def test_optimizer_descends_quadratic(make):
    opt = make()
    params = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)),
                               jnp.float32)}
    state = opt.init(params)
    target = jnp.ones((8, 8))

    def loss(p):
        return jnp.mean(jnp.square(p["w"] - target))

    l0 = float(loss(params))
    for step in range(80):
        grads = jax.grad(loss)(params)
        updates, state = opt.update(grads, state, params, jnp.asarray(step))
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
    assert float(loss(params)) < l0 * 0.3


def test_make_optimizer_registry_and_unknown_name():
    """Every registered name builds an Optimizer (sparse_adamw included);
    an unknown name fails with an actionable error listing the valid ones."""
    for name in ("adamw", "adafactor", "sgdm", "sparse_adamw"):
        opt = make_optimizer(name, lambda s: 0.1)
        assert callable(opt.init) and callable(opt.update)
    with pytest.raises(ValueError) as ei:
        make_optimizer("adam", lambda s: 0.1)
    msg = str(ei.value)
    assert "'adam'" in msg
    for name in ("adamw", "adafactor", "sgdm", "sparse_adamw"):
        assert name in msg, f"error message must list {name}: {msg}"


def test_adafactor_state_is_factored():
    opt = adafactor(lambda s: 0.1, min_dim_factored=8)
    params = {"big": jnp.zeros((16, 32)), "small": jnp.zeros((4,))}
    st = opt.init(params)
    assert set(st["big"]) == {"vr", "vc"}
    assert st["big"]["vr"].shape == (16,) and st["big"]["vc"].shape == (32,)
    assert set(st["small"]) == {"v"}
    axes = opt.state_logical_axes({"big": ("a", "b"), "small": ("c",)},
                                  {"big": jax.ShapeDtypeStruct((16, 32), jnp.float32),
                                   "small": jax.ShapeDtypeStruct((4,), jnp.float32)})
    assert axes["big"]["vr"] == ("a",) and axes["big"]["vc"] == ("b",)


def test_clip_and_schedule():
    tree = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(jnp.linalg.norm(clipped["a"])) <= 1.0 + 1e-5
    lr = cosine_schedule(1.0, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1.0, abs=1e-3)
    assert float(lr(100)) == pytest.approx(0.1, abs=1e-2)


# --- checkpoint -------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "step": jnp.asarray(7, jnp.int32)}
    path = save_checkpoint(str(tmp_path), 7, state)
    assert os.path.exists(path)
    restored = load_checkpoint(path, state)
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))
    latest, step = restore_latest(str(tmp_path), state)
    assert step == 7


def test_checkpoint_gc(tmp_path):
    state = {"x": jnp.zeros(2)}
    for s in range(5):
        save_checkpoint(str(tmp_path), s, state, keep=2)
    ckpts = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert len(ckpts) == 2


# --- data pipeline ----------------------------------------------------------


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_input_specs_structurally_match_concrete(arch, shape_name):
    """input_specs (dry-run) and make_batch (real data) must agree exactly."""
    cfg = get_smoke_config(arch)
    shape = ShapeConfig(shape_name, 64, 4, INPUT_SHAPES[shape_name].kind)
    specs = input_specs(cfg, shape)
    concrete = make_batch(cfg, shape)
    s_flat, s_def = jax.tree_util.tree_flatten(specs)
    c_flat, c_def = jax.tree_util.tree_flatten(concrete)
    assert s_def == c_def
    for s, c in zip(s_flat, c_flat):
        assert tuple(s.shape) == tuple(c.shape), (arch, shape_name)
        assert s.dtype == c.dtype
    axes = batch_logical_axes(cfg, shape)
    a_def = jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda t: 0, axes, is_leaf=lambda t: isinstance(t, tuple)))
    assert a_def == s_def


# --- HLO analysis -----------------------------------------------------------


def test_collective_parser_counts_scan_trips():
    import subprocess
    import sys

    from conftest import SRC

    code = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh
mesh = make_mesh((4,), ("x",))
def f(h):
    def body(c, x):
        return c + jax.lax.psum(x, "x"), None
    out, _ = jax.lax.scan(body, h[0], h)
    return out
from repro.compat import shard_map
fn = shard_map(f, mesh=mesh, in_specs=P(None, "x"), out_specs=P("x"), check_vma=False)
comp = jax.jit(fn).lower(jax.ShapeDtypeStruct((6, 64), jnp.float32)).compile()
print("<<<HLO>>>")
print(comp.as_text())
"""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    hlo = proc.stdout.split("<<<HLO>>>")[1]
    recs = parse_collectives(hlo)
    ar = [r for r in recs if r.kind == "all-reduce"]
    assert ar, "no all-reduce found"
    assert max(r.executions for r in ar) == 6  # scan length propagated


def test_roofline_terms_dominance():
    rl = roofline_terms(device_kind="TPU v5 lite", analytic_flops=1e18, chips=256, hbm_bytes_per_chip=1e9,
                        collective_bytes_per_chip=1e8, model_flops=8e17,
                        hlo_flops_raw=1e13)
    assert rl.dominant == "compute"
    assert 0 < rl.useful_ratio < 1


def test_roofline_peaks_refuse_unknown_device_kind():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline_terms(device_kind="cpu", analytic_flops=1.0, chips=1,
                       hbm_bytes_per_chip=1.0, collective_bytes_per_chip=1.0,
                       model_flops=1.0, hlo_flops_raw=1.0)


# --- analytic flops ---------------------------------------------------------


def test_analytic_flops_vs_cost_analysis_single_layer():
    """On a 1-layer config the scan body is counted once by XLA too, so
    cost_analysis must bracket the analytic forward count."""
    import dataclasses

    from repro.models import transformer as T

    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"), num_layers=1,
                              remat_policy="none", tie_embeddings=True)
    B, S = 2, 64
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32),
             "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))}
    comp = jax.jit(lambda p, b: T.loss_fn(cfg, p, b)).lower(params, batch).compile()
    hlo_flops = comp.cost_analysis()["flops"]
    analytic = flops_lib.forward_flops(cfg, B, S).total
    # forward-only analytic should be within ~2.5x of XLA's forward count
    # (XLA counts masks/softmax/etc., we count matmuls+attention)
    assert analytic < hlo_flops * 1.6
    assert hlo_flops < analytic * 3.0, (hlo_flops, analytic)


def test_step_flops_shapes():
    cfg = get_config("llama3.2-1b")
    tr = flops_lib.step_flops(cfg, INPUT_SHAPES["train_4k"]).total
    pf = flops_lib.step_flops(cfg, INPUT_SHAPES["prefill_32k"]).total
    dc = flops_lib.step_flops(cfg, INPUT_SHAPES["decode_32k"]).total
    assert tr > pf > dc > 0
    mf = flops_lib.model_flops_6nd(cfg, INPUT_SHAPES["train_4k"])
    assert 0.3 < mf / tr < 1.2  # 6ND ~ analytic for a dense model


def test_moe_active_flops_much_smaller_than_total():
    cfg = get_config("kimi-k2-1t-a32b")
    assert cfg.num_active_params() < cfg.num_params() / 15
