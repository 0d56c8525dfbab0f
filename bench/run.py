#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json; its configuration,
traffic mix, job, limits and per-layer readers are files found by name (see
`manifest`).  The run needs the accelerator the cell asks for and exits
non-zero, printing no result, where JAX finds none or too few.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last lines on standard error give each number compared for
``correct`` beside its limit; the last line on standard output is the
result, one JSON object.
"""
from __future__ import annotations

import time

START = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class RunError(RuntimeError):
    """The run cannot produce a result; nothing is printed to stdout."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (where
    JAX_COMPILATION_CACHE_DIR says, else the checkout's fixed directory),
    holding every program however fast it compiled, so that only a
    checkout's first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def devices_for(chips: int, require_tpu: bool = True):
    """The first ``chips`` devices; an error unless they are TPUs."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise RunError(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips; JAX finds {len(devs)}")
    return devs


def device_record(devs, peak_bytes) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def read_metrics(cell, ctx: dict, trace: bool):
    """The cell's end-to-end metrics (``--trace 0``) or per-layer ones,
    each from its reader; a reader that finds nothing is left out."""
    from manifest import load_module

    out = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] not in ctx["end_to_end"]:
                raise RunError(f"the job reports no {m['name']}")
            out[m["name"]] = {"value": ctx["end_to_end"][m["name"]],
                              "unit": m["unit"]}
        return out
    for m in cell.per_layer:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, require_tpu: bool = True, cell=None) -> dict:
    """Run a cell and return its result; ``cell`` stands in for the
    manifest's (tests use small cells)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (BENCH, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from manifest import Cell, load_manifest, load_module

    if cell is None:
        cell = Cell(load_manifest(), args.workload)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise RunError(f"no program under {ROOT}/src")
    enable_compile_cache()
    devs = devices_for(cell.chips, require_tpu)
    job = load_module("jobs", cell.traffic["job"])
    ctx = job.run(cell, devs[:cell.chips], seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  start=START, require_tpu=require_tpu, log=log)
    result = {
        "correct": all(c["ok"] for c in ctx["checks"].values())
        and ctx["failed"] == 0,
        "attempted": ctx["attempted"], "failed": ctx["failed"],
        "metrics": read_metrics(cell, ctx, bool(args.trace)),
        "device": device_record(devs, ctx["memory_peak_bytes"]),
    }
    if args.trace and ctx["trace"] is not None:
        result["device"].update(busy_s=ctx["trace"].busy_s,
                                window_s=ctx["trace"].window_s)
        result["breakdown"] = {"device_ops": ctx["trace"].top_ops,
                               "idle_gaps": ctx["trace"].idle_gaps}
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in ctx["checks"].items()}
    return result


def cli() -> int:
    try:
        result = main()
    except RunError as e:
        log(f"FAILED: {e}")
        return 1
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    log(f"correct = {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(cli())
