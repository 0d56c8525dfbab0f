#!/usr/bin/env python3
"""Readings of the control and of planted faults, for setting a cell's
correctness limits (the benchmark's own runs never run this).

    python bench/control.py --workload <cell> --seeds 11,12,13

For each seed it builds the cell's graph and weights, runs the reference
at the configuration's precision, and compares with it, by `check.compare`,
what stands in the program's place:

  control      the reference at the next lower precision (``high``, three
               bfloat16 passes, for float32 at ``highest``)
  half_batch   the reference with half of the train vertices left out and
               the mean taken over the rest
  reorder      (``--faults`` only) no fault: the reference with each
               vertex's neighbour slots summed in the reverse order, a
               second sound float32 run, for how far two sound runs of one
               seed can part

A step that returns its state unchanged reads 1 on ``update_gap`` by
construction and needs no run.  One JSON line per seed goes to stdout;
``control_leaf_norms`` gives the norms behind the control's gaps.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def readings(cell, devs, seed: int,
             faults=("control", "half_batch")) -> dict:
    """{fault: gaps} for one seed, for each of ``faults``."""
    import jax
    import numpy as np

    import check
    import graphs
    import reference

    cfg, S = cell.config, int(cell.traffic["ref_steps"])
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    data = graphs.build(cfg, seed)
    K = int(data.in_degree().max())
    ref = reference.for_cell(cell, data, devs, K)
    p0 = jax.device_get(reference.init_params(ref.model, cell.dims, seed,
                                              ref.args))
    sound = ref.train(p0, cfg["lr"], S, cfg["matmul_precision"])
    out = {}
    if "control" in faults:
        lower = {"highest": "high"}[cfg["matmul_precision"]]
        low = ref.train(p0, cfg["lr"], S, lower)
        out["control"] = check.compare(p0, low, sound, cfg["lr"])
        out["control_leaf_norms"] = check.leaf_norms(p0, low, sound,
                                                     cfg["lr"])
    if "half_batch" in faults:
        rng = np.random.default_rng(seed)
        half = data.train_mask & (rng.random(data.num_vertices) < 0.5)
        out["half_batch"] = check.compare(
            p0, ref.train(p0, cfg["lr"], S, cfg["matmul_precision"],
                          weights=half), sound, cfg["lr"])
    if "reorder" in faults:
        other = reference.for_cell(cell, data, devs, K, reverse_slots=True)
        out["reorder"] = check.compare(
            p0, other.train(p0, cfg["lr"], S, cfg["matmul_precision"]),
            sound, cfg["lr"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one reading each")
    ap.add_argument("--faults", default="control,half_batch",
                    help="comma-separated: control, half_batch, reorder")
    args = ap.parse_args()
    root = os.path.dirname(BENCH)
    sys.path[:0] = [BENCH, os.path.join(root, "src")]
    import run
    from manifest import Cell, load_manifest

    cell = Cell(load_manifest(), args.workload)
    run.enable_compile_cache()
    devs = run.devices_for(cell.chips)[:cell.chips]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": cell.name, "seed": seed,
                          **readings(cell, devs, seed,
                                     tuple(args.faults.split(",")))}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
