"""Full-graph training: the engine's jitted step over the whole graph.

Set-up builds the graph, the engine (layout), the seed's weights and the
compiled step, then drives that step through the first ``ref_steps``
steps, the ones the reference follows.  The window keeps calling the same
step on the carried state, reading the loss each step, as
`DistGNNEngine.train` does, until ``seconds`` have passed; ``epoch_s`` is
the window's length over the steps in it (one step is one epoch).  After
the window the program is freed and the reference replays the first steps
for the comparison that decides ``correct``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import os
import shutil
import time

import numpy as np

import check
import graphs
import reference
import tracing
from manifest import BENCH, ManifestError, load_module, load_peaks

TRACE_DIR = os.path.join(BENCH, ".trace")


class CompileCounter:
    """Counts jaxpr traces and backend compiles (cache loads included)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1

    def close(self):
        self._mon.unregister_event_duration_listener(self._on)


def engine_config(cfg: dict, traffic: dict, model_args: dict):
    """The engine's configuration: the configuration's and the mix's
    settings plus ``model_args`` (`Cell.model_args`), each of which has to
    be a field that `EngineConfig` has and the others do not set."""
    from repro.core.engine import EngineConfig

    kw = dict(
        model=cfg["model"], hidden=cfg["hidden_dim"],
        num_layers=cfg["num_layers"], lr=cfg["lr"],
        partition_family=cfg["partition_family"],
        partitioner=cfg["partitioner"], execution=traffic["execution"],
        protocol=traffic["protocol"],
        exchange_chunks=traffic["exchange_chunks"],
        p2p_buckets=traffic["p2p_buckets"])
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    for key in model_args:
        if key not in fields or key in kw:
            raise ManifestError(
                f"model_args key {key!r} of configuration {cfg['name']!r} "
                + ("is set by the harness" if key in kw
                   else "is no field of the engine's EngineConfig"))
    return EngineConfig(**kw, **model_args)


def build_program(ecfg, data, devs, require_tpu: bool):
    """The engine of configuration ``ecfg`` on a 1-D mesh over ``devs``."""
    from repro.compat import make_mesh
    from repro.core.engine import DistGNNEngine
    from repro.core.graph import Graph

    g = Graph(indptr=data.indptr, indices=data.indices,
              num_vertices=data.num_vertices, features=data.features,
              labels=data.labels, train_mask=data.train_mask,
              val_mask=data.val_mask, test_mask=data.test_mask)
    mesh = make_mesh((len(devs),), ("w",), devices=list(devs))
    eng = DistGNNEngine(g, mesh=mesh, cfg=ecfg)
    if require_tpu and (eng.interpret or not eng.cfg.use_pallas):
        raise RuntimeError("the engine would not compile its kernels "
                           f"(interpret={eng.interpret})")
    return eng


def place_params(state, params):
    """``params`` in the state's own layout; the tree must match."""
    import jax

    want = jax.tree_util.tree_structure(state["params"])
    got = jax.tree_util.tree_structure(params)
    if want != got:
        raise RuntimeError(f"weights tree {got} is not the engine's {want}")
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, state["params"])
    return dict(state, params=jax.device_put(params, shardings))


def distinct_sources(data, chips: int):
    """Per chip: (distinct source rows, own rows, real in-edges)."""
    V = data.num_vertices
    rows = V // chips
    out = []
    for c in range(chips):
        lo, hi = data.indptr[c * rows], data.indptr[(c + 1) * rows]
        seen = np.zeros(V, bool)
        seen[data.indices[lo:hi]] = True
        out.append((int(seen.sum()), rows, int(hi - lo)))
    return out


def run(cell, devs, *, seed: int, seconds: float, trace: bool, start: float,
        require_tpu: bool, log) -> dict:
    import jax

    cfg, traffic, args = cell.config, cell.traffic, cell.model_args
    ecfg = engine_config(cfg, traffic, args)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    model = load_module("refs", cfg["model"])
    S = int(traffic["ref_steps"])
    clock = time.perf_counter
    host = {}

    t = clock()
    data = graphs.build(cfg, seed)
    host["graph_s"] = clock() - t
    K = int(data.in_degree().max())
    t = clock()
    eng = build_program(ecfg, data, devs, require_tpu)
    host["layout_s"] = clock() - t
    dims = cell.dims
    if list(eng.dims) != dims:
        raise RuntimeError(f"engine widths {eng.dims} are not {dims}")
    p0 = reference.init_params(model, dims, seed, args)
    state = place_params(eng.init_state(), p0)
    p0 = jax.device_get(p0)
    t = clock()
    compiled = eng.lower_step(state).compile()
    host["compile_s"] = clock() - t
    mem = compiled.memory_analysis()
    hbm = mem.peak_memory_in_bytes
    del compiled
    step = eng.make_step()
    losses, params1 = [], None
    for i in range(S):
        state, metrics, logits = step(state)
        losses.append(float(metrics["loss"]))
        if i == 0:
            params1 = jax.device_get(state["params"])
    paramsS = jax.device_get(state["params"])

    counter = CompileCounter()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        profiler = jax.profiler.trace(TRACE_DIR)
    else:
        profiler = contextlib.nullcontext()
    window_losses = []
    with profiler:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = clock()
            setup_s = t0 - start
            while True:
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    state, metrics, out = step(state)
                with jax.profiler.TraceAnnotation("bench.loss_read"):
                    window_losses.append(float(metrics["loss"]))
                del out
                if clock() - t0 >= seconds:
                    break
            t1 = clock()
    counter.close()
    if counter.count:
        raise RuntimeError(f"{counter.count} compiles inside the window")
    steps = len(window_losses)
    # The TPU runtime holds a program's scratch (temp) buffers in a
    # reservation apart from the allocator's buffers, so a chip's peak is
    # the two peaks together.
    stats = [d.memory_stats() or {} for d in devs]
    used = [st.get("peak_bytes_in_use", 0) + st.get("peak_bytes_reserved", 0)
            for st in stats]
    peak = max(used)
    log(f"memory of the fullest chip: {stats[used.index(peak)]}; the "
        f"step's temp {mem.temp_size_in_bytes} B, arguments "
        f"{mem.argument_size_in_bytes} B")
    V = data.num_vertices
    prog = dict(losses=losses, params1=params1, params=paramsS,
                logits=np.asarray(logits)[:V])
    del logits, metrics
    log(f"setup {setup_s:.3f} s (graph {host['graph_s']:.3f}, layout "
        f"{host['layout_s']:.3f}, compile {host['compile_s']:.3f}); window "
        f"{t1 - t0:.3f} s, {steps} steps, losses {window_losses}")
    ctx = dict(host=host, chips=len(devs), steps=steps, window_s=t1 - t0)
    del state, step, eng
    gc.collect()

    t = clock()
    ref = reference.for_cell(cell, data, devs, K)
    r = ref.train(p0, cfg["lr"], S)
    del ref
    log(f"reference {clock() - t:.3f} s; losses program {losses} "
        f"reference {r['losses']}")
    log("leaf norms [grad program, reference, change program, reference]: "
        + json.dumps(check.leaf_norms(p0, prog, r, cfg["lr"])))
    ctx["checks"] = check.judge(check.compare(p0, prog, r, cfg["lr"]),
                                cell.limits)
    failed = sum(not math.isfinite(x) for x in window_losses)
    ctx.update(attempted=steps, failed=failed, memory_peak_bytes=peak,
               end_to_end=dict(epoch_s=(t1 - t0) / steps,
                               step_hbm_gib=hbm / 2 ** 30, setup_s=setup_s))
    if trace:
        counts = load_module("counts", cfg["model"])
        ctx["peaks"] = (load_peaks(devs[0].device_kind)
                        if devs[0].platform == "tpu" else None)
        ctx["flops_per_step"] = counts.flops(V, data.num_edges, dims, **args)
        ctx["agg_bytes_per_chip"] = float(np.mean([
            counts.agg_bytes(n, v, e, dims, **args)
            for n, v, e in distinct_sources(data, len(devs))]))
        dev_ops, spans = tracing.load(TRACE_DIR)
        ctx["trace"] = (tracing.reduce(dev_ops, spans, _window(spans),
                                       V // len(devs), range(K, K + 129))
                        if dev_ops else None)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return ctx


def _window(spans):
    for name, s, d in spans:
        if name == "bench.window":
            return s, s + d
    raise RuntimeError("the trace holds no bench.window span")
