"""Find a cell's files by the names in BENCHMARK.json.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json``, the job a mix names
``bench/jobs/<job>.py``, a per-layer metric's reader
``bench/metrics/<metric>.py``, a model's reference layer
``bench/refs/<model>.py`` and its operation counts ``bench/counts/<model>.py``,
and a cell's correctness limits ``bench/limits/<cell>.json``.  Adding a
configuration, mix, metric or cell adds files; no file here changes.

A configuration file states, besides its graph (``graph``,
``num_vertices``, ``avg_degree``, ``graph_seed``) and its training
(``lr``, ``optimizer``, ``dtype``, ``matmul_precision``,
``partition_family``, ``partitioner``):

  model         the reference layer and counts, ``bench/refs/<model>.py``
                and ``bench/counts/<model>.py``, and the engine's model
  feature_dim, num_layers, num_classes
                the widths; ``hidden_dim`` is the width of a hidden layer's
                output, for a model with heads the heads' outputs
                concatenated (see `Cell.dims`)
  model_args    optional object of the model's further arguments (heads,
                a skip connection), given alike to the engine's
                ``EngineConfig``, the reference's ``init_params`` and
                layers (``Ctx.args``) and the counts; absent means none
  labels        ``"single"`` (the default): one class a vertex, trained by
                softmax cross-entropy; ``"multi"``: ``num_classes`` 0/1
                labels a vertex, by sigmoid cross-entropy
  label_rate    the share of positive labels, which ``"multi"`` requires
  train_frac    the share of vertices that train
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ManifestError(f"{what} {name!r} is not a valid name")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT.fullmatch(unit):
        raise ManifestError(f"unit {unit!r} of {what} is not a valid unit")
    return unit


def _json(path: str, what: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"no {what} at {os.path.relpath(path, ROOT)}")
    except json.JSONDecodeError as e:
        raise ManifestError(f"{what} {path} is not JSON: {e}")


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots)."""
    check_name(name, kind)
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"no {kind} module {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_manifest(root: str = ROOT) -> dict:
    """BENCHMARK.json with every name and unit checked."""
    m = _json(os.path.join(root, "BENCHMARK.json"), "BENCHMARK.json")
    for c in m.get("configs", []):
        check_name(c["name"], "configuration")
    for w in m.get("workloads", []):
        check_name(w["name"], "workload")
        check_name(w["config"], "configuration")
        check_name(w["traffic"], "traffic")
    for kind in ("end_to_end", "per_layer"):
        for metric in m.get(kind, []):
            check_name(metric["name"], "metric")
            check_unit(metric["unit"], metric["name"])
            if metric.get("better") not in ("lower", "higher"):
                raise ManifestError(
                    f"metric {metric['name']}: better must be lower|higher")
    return m


class Cell:
    """One workload of the manifest with everything it names, loaded."""

    def __init__(self, manifest: dict, workload: str, root: str = ROOT):
        by_name = {w["name"]: w for w in manifest["workloads"]}
        if workload not in by_name:
            raise ManifestError(f"no workload {workload!r} in BENCHMARK.json "
                                f"(have {sorted(by_name)})")
        self.workload = by_name[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        cfg_entry = {c["name"]: c for c in manifest["configs"]}[
            self.workload["config"]]
        self.config = _json(os.path.join(root, cfg_entry["file"]),
                            "configuration")
        self.traffic = _json(os.path.join(
            BENCH, "traffic", check_name(self.workload["traffic"], "traffic")
            + ".json"), "traffic mix")
        self.limits = _json(os.path.join(BENCH, "limits", workload + ".json"),
                            "limits file")
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if workload in m.get("workloads", [workload])]

    @property
    def dims(self) -> list:
        """Layer widths: [features, hidden.., classes].  For a model with
        heads, ``hidden_dim`` is a hidden layer's concatenated width (4
        heads of 256 give 1024)."""
        c = self.config
        return ([c["feature_dim"]] + [c["hidden_dim"]] * (c["num_layers"] - 1)
                + [c["num_classes"]])

    @property
    def model_args(self) -> dict:
        """The configuration's ``model_args``, checked, lists as tuples;
        {} where it has none."""
        args = self.config.get("model_args", {})
        what = f"model_args of configuration {self.workload['config']!r}"
        if not isinstance(args, dict):
            raise ManifestError(f"{what} is not an object")
        out = {}
        for key, value in args.items():
            check_name(key, f"key of {what}:")
            items = value if isinstance(value, list) else [value]
            if not all(v is None or isinstance(v, (bool, int, float, str))
                       for v in items):
                raise ManifestError(f"{what}: {key} is neither a JSON "
                                    f"scalar nor a list of them")
            out[key] = tuple(value) if isinstance(value, list) else value
        return out


def load_peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = _json(os.path.join(BENCH, "peaks.json"), "peak table")
    if device_kind not in table["kinds"]:
        raise ManifestError(f"no peaks for device kind {device_kind!r} "
                            f"(have {sorted(table['kinds'])})")
    return table["kinds"][device_kind]
