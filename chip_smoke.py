#!/usr/bin/env python3
"""Prove that DistGNNEngine trains on the TPU, through its normal entry
points, at the width of the repo's full-size GNN workload.

    python chip_smoke.py              # one chip: phases a, b, c
    python chip_smoke.py --chips 4    # a four-chip mesh: phases b and c only

Phases, in one process (a chip belongs to one process at a time):

  a. device check — JAX must see TPU devices (and enough of them) before any
     work is done;
  b. small oracle check — on a 4,096-vertex graph, the engine's distributed
     step (compiled Pallas kernels, edge-cut p2p exchange) against its
     single-device jnp reference, for gcn and gat, at highest matmul
     precision;
  c. full-width training — configs/gcn_paper.py (2^20 vertices, average
     degree 16, 256 features, 256 hidden, 64 classes, 3 layers; graph from
     er_graph, as `examples/train_gnn_distributed.py --config gcn-paper`
     builds it) for a few edge-cut p2p steps; the loss must be finite and
     fall.

Lines starting with "info:" are information, not metrics.  The last line of
standard output is one JSON object naming the device; every failure exits
non-zero without printing it.  Run from the root of a checkout: the script
imports the program from ./src.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ORACLE_VERTICES = 4096
ORACLE_STEPS = 3
# Distributed and reference steps add the same f32 terms in different orders
# (slot-sequential gather-sum vs XLA's gather-reduce), so at highest
# matmul precision they agree to f32 rounding, not bitwise.
ORACLE_LOSS_ATOL = 1e-4
ORACLE_LOGITS_RTOL = 1e-3
TRAIN_STEPS = 5


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check_devices(chips: int):
    """Phase a: TPU devices, at least ``chips`` of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        fail(f"--chips {chips} but JAX finds {len(devs)} TPU devices")
    info(f"device_kind={devs[0].device_kind} devices={len(devs)}")
    return devs


def build_engine(g, chips: int, cfg):
    """The engine on a 1-D mesh over ``chips`` devices, with the checks
    that no fallback hides the device."""
    import jax

    from repro.compat import make_mesh
    from repro.core.engine import DistGNNEngine

    mesh = make_mesh((chips,), ("w",), devices=jax.devices()[:chips])
    eng = DistGNNEngine(g, mesh=mesh, cfg=cfg)
    if eng.interpret or not eng.cfg.use_pallas:
        fail(f"kernels would not be compiled (interpret={eng.interpret}, "
             f"use_pallas={eng.cfg.use_pallas})")
    return eng


def oracle_check(chips: int) -> None:
    """Phase b: distributed step vs single-device reference on the chip."""
    import jax
    import numpy as np

    from repro.core.engine import EngineConfig
    from repro.core.graph import er_graph

    g = er_graph(ORACLE_VERTICES, avg_degree=16, feature_dim=64,
                 num_classes=8, seed=1)
    for model in ("gcn", "gat"):
        cfg = EngineConfig(model=model, execution="p2p", hidden=64,
                           num_layers=3, lr=0.05)
        with jax.default_matmul_precision("highest"):
            eng = build_engine(g, chips, cfg)
            losses, logits = eng.train(ORACLE_STEPS)
            ref_losses, ref_logits = eng.train(ORACLE_STEPS, reference=True)
        logits, ref_logits = np.asarray(logits), np.asarray(ref_logits)
        loss_gap = max(abs(a - b) for a, b in zip(losses, ref_losses))
        logit_gap = float(np.max(np.abs(logits - ref_logits))
                          / max(float(np.max(np.abs(ref_logits))), 1e-30))
        info(f"oracle {model}: losses={losses} reference={ref_losses} "
             f"max loss gap={loss_gap:.3e} logits rel gap={logit_gap:.3e}")
        if not all(math.isfinite(x) for x in losses + ref_losses):
            fail(f"oracle {model}: non-finite loss")
        if loss_gap > ORACLE_LOSS_ATOL or logit_gap > ORACLE_LOGITS_RTOL:
            fail(f"oracle {model}: distributed step disagrees with the "
                 f"reference (loss gap {loss_gap:.3e} > {ORACLE_LOSS_ATOL} "
                 f"or logits gap {logit_gap:.3e} > {ORACLE_LOGITS_RTOL})")


def full_width_training(chips: int) -> None:
    """Phase c: gcn-paper width, edge-cut p2p, a few steps."""
    from repro.configs import gcn_paper

    wl = gcn_paper.CONFIG
    t0 = time.perf_counter()
    g = gcn_paper.build_graph(wl)
    t1 = time.perf_counter()
    eng = build_engine(g, chips, gcn_paper.engine_config(wl,
                                                         execution="p2p"))
    t2 = time.perf_counter()
    info(f"gcn-paper: {gcn_paper.GRAPH_GENERATOR} V={g.num_vertices} "
         f"E={g.num_edges} K={eng.K} dims={eng.dims} "
         f"partitioner={eng.cfg.partitioner} chips={chips}")
    info(f"host seconds: graph={t1 - t0:.3f} engine layout={t2 - t1:.3f}")
    state = eng.init_state()
    # this compile is the one the first step call reuses
    t = time.perf_counter()
    mem = eng.lower_step(state).compile().memory_analysis()
    info(f"compile seconds={time.perf_counter() - t:.3f}")
    info(f"compiled step memory per chip (bytes): "
         f"peak={mem.peak_memory_in_bytes} "
         f"argument={mem.argument_size_in_bytes} "
         f"output={mem.output_size_in_bytes} "
         f"temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
    step = eng.make_step()
    losses, secs = [], []
    for _ in range(TRAIN_STEPS):
        t = time.perf_counter()
        state, metrics, _ = step(state)
        losses.append(float(metrics["loss"]))  # blocks on the step
        secs.append(time.perf_counter() - t)
    info(f"losses={losses}")
    info(f"step seconds={secs}")
    if not all(math.isfinite(x) for x in losses):
        fail(f"non-finite loss at gcn-paper width: {losses}")
    if not losses[-1] < losses[0]:
        fail(f"loss did not fall at gcn-paper width: {losses}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: all phases on one chip; 4: the full-width "
                    "p2p step and the oracle check on a four-chip mesh")
    args = ap.parse_args()

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no program next to this script (expected {src}/repro)")
    sys.path.insert(0, src)

    devs = check_devices(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    info(f"compile cache: {enable_compile_cache()}")
    t = time.perf_counter()
    oracle_check(args.chips)
    info(f"oracle phase seconds={time.perf_counter() - t:.3f}")
    t = time.perf_counter()
    full_width_training(args.chips)
    info(f"full-width phase seconds={time.perf_counter() - t:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
