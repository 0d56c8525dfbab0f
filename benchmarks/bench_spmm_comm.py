"""Survey Table 2 / CAGNET claim: collective bytes per distributed-SpMM
execution model, measured from lowered HLO on a forced-multi-device subprocess
(benchmarks keep the main process at 1 device)."""
from __future__ import annotations

import os
import subprocess
import sys
from typing import Dict, List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = r"""
import jax, numpy as np, jax.numpy as jnp, json
from repro.core.graph import er_graph, sbm_graph
from repro.core.execution.spmm_models import (spmm_replicated, spmm_1d_broadcast,
    spmm_1d_ring, spmm_1d_p2p, spmm_2d_summa, spmm_15d, p2p_plan)
from repro.launch.hlo_analysis import collective_bytes
from repro.compat import make_mesh

V, D = 512, 64
g = sbm_graph(V, num_blocks=8, p_in=0.04, p_out=0.002, seed=0)
# relabel vertices by a locality-aware partition so device row-blocks align
# with communities (what a real deployment does before distributing)
from repro.core.partition import PARTITIONERS
part = PARTITIONERS["metis_like"](g, 8)
order = np.argsort(part.assignment, kind="stable")
A_np = g.to_dense_adj()[np.ix_(order, order)]
A = jnp.asarray(A_np)
H = jnp.asarray(np.random.default_rng(0).standard_normal((V, D)).astype(np.float32))
m1 = make_mesh((8,), ("w",))
m2 = make_mesh((4, 2), ("r", "c"))
rows = []
def measure(name, fn, mesh, *extra):
    comp = jax.jit(lambda a, h: fn(mesh, a, h, *extra)).lower(A, H).compile()
    total, kinds = collective_bytes(comp.as_text())
    rows.append(dict(model=name, collective_bytes=int(total), by_kind=kinds))
measure("C:replicated", spmm_replicated, m1)
measure("CC:1d_broadcast", spmm_1d_broadcast, m1)
measure("CC:1d_ring(chunk)", spmm_1d_ring, m1)
plan = p2p_plan(A_np, 8)
measure("CC:1d_p2p(selective)", spmm_1d_p2p, m1, plan)
measure("CCR:2d_summa", spmm_2d_summa, m2)
measure("CCR:1.5d", spmm_15d, m2)
print("<<<JSON>>>")
print(json.dumps(rows))
"""


def bench_spmm_comm() -> Tuple[List[Dict], str]:
    import json

    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", _CODE], capture_output=True,
                          text=True, timeout=600, env=env)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    rows = json.loads(proc.stdout.split("<<<JSON>>>")[1])
    base = next(r for r in rows if r["model"] == "CC:1d_broadcast")["collective_bytes"]
    p2p = next(r for r in rows if "p2p" in r["model"])["collective_bytes"]
    return rows, f"p2p_vs_1d_broadcast={p2p / max(base, 1):.3f}"
