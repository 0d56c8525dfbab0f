"""ISSUE 7 serving benchmark: the two inference tiers measured for real.

``python benchmarks/bench_serving.py --json`` writes BENCH_serving.json
(same artifact contract as BENCH_step_pipeline.json): a forced-host
4-device subprocess measures

* the THROUGHPUT tier — layer-wise full-graph sweep wall-clock at two
  vertex counts, each sweep oracle-checked (<= 1e-4) and its
  CommStats.inference_bytes cross-checked EXACTLY against the standalone
  ``cost_models.inference_bytes_per_sweep``;
* the LATENCY tier — a GNNQueryEngine query stream: qps, p50/p99 latency,
  and the serve-step compile count (must be exactly 1).

The artifact is written BEFORE asserting so a failed claim leaves evidence.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Tuple

_SERVING_PROBE = r"""
import json, time
import jax
import numpy as np
from repro.core.engine import DistGNNEngine, EngineConfig
from repro.core.graph import sbm_graph
from repro.core.partition.cost_models import inference_bytes_per_sweep
from repro.core.serving import GNNQueryEngine

n_dev = len(jax.devices())

# -- throughput tier: sweep wall vs vertex count --------------------------
sweeps = []
for V in (256, 512):
    g = sbm_graph(V, num_blocks=8, p_in=0.05, p_out=0.003, seed=0)
    eng = DistGNNEngine(g, cfg=EngineConfig(execution="p2p", hidden=32,
                                            lr=0.3))
    state = eng.init_state()
    step = eng.make_step()
    for _ in range(3):
        state, _, _ = step(state)
    params = state["params"]
    H = eng.infer_full_graph(params=params)  # compile + first sweep
    jax.block_until_ready(H)
    t0 = time.perf_counter()
    N = 5
    for _ in range(N):
        H = eng.infer_full_graph(params=params)
    jax.block_until_ready(H)
    wall = (time.perf_counter() - t0) / N
    emb = eng.global_embeddings(H)
    ref = eng.global_embeddings(eng.infer_full_graph(params=params,
                                                     reference=True))
    err = float(np.max(np.abs(emb - ref)))
    expect = (N + 1) * inference_bytes_per_sweep(
        "p2p", eng.dims, model="gcn", family="edge_cut", g=g, part=eng.part)
    sweeps.append(dict(vertices=V, sweep_seconds=wall, oracle_err=err,
                       inference_bytes=int(eng.comm_stats.inference_bytes),
                       cost_model_bytes=int(expect),
                       bytes_match=eng.comm_stats.inference_bytes == expect,
                       compiles=eng._jit_infer._cache_size()))

# -- latency tier: query stream -------------------------------------------
g = sbm_graph(512, num_blocks=8, p_in=0.05, p_out=0.003, seed=0)
eng = DistGNNEngine(g, cfg=EngineConfig(
    execution="p2p", batching="node_wise", batch_size=16, fanouts=(4, 4),
    hidden=32, lr=0.3, cache_policy="static_degree", cache_capacity=32))
state, _, _ = eng.run_epoch_minibatch(4)
qe = GNNQueryEngine(eng, state["params"])
rng = np.random.default_rng(0)
qe.query(rng.choice(g.num_vertices, 8, replace=False))  # warmup compile
qe.stats.latencies_s.clear()
qe.stats.queries = 0
NQ = 24
for _ in range(NQ):
    qe.query(rng.choice(g.num_vertices, 8, replace=False))
queries = dict(num_queries=NQ, targets_per_query=8,
               qps=qe.stats.qps(),
               p50_ms=qe.stats.percentile_ms(50),
               p99_ms=qe.stats.percentile_ms(99),
               rounds=qe.stats.rounds, compiles=qe.num_compiles())

print("BENCH_JSON " + json.dumps(dict(devices=n_dev, sweeps=sweeps,
                                      queries=queries)))
"""


def bench_serving(out_dir: str = "experiments/dryrun"
                  ) -> Tuple[List[Dict], str]:
    """Measure both serving tiers on a forced-host 4-device subprocess and
    write BENCH_serving.json; assert one compile per tier, oracle err
    <= 1e-4, bytes == the standalone cost model, qps > 0."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(repo, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _SERVING_PROBE],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"serving probe failed:\n{proc.stdout}\n"
                           f"{proc.stderr[-3000:]}")
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("BENCH_JSON ")][-1]
    result = json.loads(line[len("BENCH_JSON "):])
    # write the artifact BEFORE asserting so a failed claim leaves evidence
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_serving.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=float)
    rows = []
    for s in result["sweeps"]:
        rows.append(dict(tier="sweep", vertices=s["vertices"],
                         sweep_s=round(s["sweep_seconds"], 4),
                         oracle_err=s["oracle_err"],
                         bytes_match=s["bytes_match"],
                         compiles=s["compiles"]))
        assert s["oracle_err"] <= 1e-4, s
        assert s["bytes_match"], (
            f"CommStats.inference_bytes {s['inference_bytes']} != cost model "
            f"{s['cost_model_bytes']}")
        assert s["compiles"] == 1, s
    q = result["queries"]
    rows.append(dict(tier="queries", qps=round(q["qps"], 1),
                     p50_ms=round(q["p50_ms"], 2),
                     p99_ms=round(q["p99_ms"], 2),
                     rounds=q["rounds"], compiles=q["compiles"]))
    assert q["compiles"] == 1, "serve step recompiled"
    assert q["qps"] > 0, q
    return rows, (f"qps={q['qps']:.1f} p99_ms={q['p99_ms']:.2f} "
                  f"artifact={path}")


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="run the serving bench and write BENCH_serving.json")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    if not args.json:
        ap.error("pass --json")
    rows, derived = bench_serving(args.out)
    for r in rows:
        print(r)
    print(derived)


if __name__ == "__main__":
    main()
