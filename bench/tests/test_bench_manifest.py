"""BENCHMARK.json, the files it names, and the peak table."""
import json
import os

import pytest

import bench_support  # noqa: F401  (puts bench/ and src/ on sys.path)
import manifest
from manifest import Cell, ManifestError, load_manifest, load_module

ROOT = manifest.ROOT


def test_every_cell_finds_its_files_by_name():
    m = load_manifest()
    for w in m["workloads"]:
        cell = Cell(m, w["name"])
        assert cell.config["name"] == w["config"]
        load_module("jobs", cell.traffic["job"])
        load_module("refs", cell.config["model"])
        load_module("counts", cell.config["model"])
        assert set(cell.limits) <= {"loss_gap", "grad_gap", "update_gap",
                                    "logits_gap"}
        assert {x["name"] for x in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        for metric in cell.per_layer:
            assert callable(load_module("metrics", metric["name"]).read)


def test_manifest_keeps_the_contract_shape():
    m = load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in m["configs"]]
    assert len(set(names)) == len(names)
    for c in m["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["reduced"] == json.load(open(
            os.path.join(ROOT, c["file"])))["reduced"]
    moves = {x["name"] for x in m["end_to_end"]}
    for x in m["per_layer"]:
        assert x["moves"] in moves
    for x in m["end_to_end"]:
        assert 0.01 <= x["bound"] <= 0.25
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 2)


@pytest.mark.parametrize("bad", ["has space", "a,b", "x/y", "", "-lead",
                                 "µs", "n" * 65])
def test_bad_names_are_refused(bad):
    with pytest.raises(ManifestError):
        manifest.check_name(bad, "metric")


@pytest.mark.parametrize("good", ["epoch_s", "agg_s.full", "gcn-paper",
                                  "gcn-paper.full.c1", "_x", "9a"])
def test_good_names_pass(good):
    assert manifest.check_name(good, "metric") == good


@pytest.mark.parametrize("bad", ["tokens per second", "", "µs", "x" * 17])
def test_bad_units_are_refused(bad):
    with pytest.raises(ManifestError):
        manifest.check_unit(bad, "metric")


def test_manifest_with_a_bad_unit_is_refused(tmp_path):
    m = load_manifest()
    m["end_to_end"][0]["unit"] = "s per epoch"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    with pytest.raises(ManifestError):
        load_manifest(str(tmp_path))


def test_unknown_cell_and_module_are_errors():
    with pytest.raises(ManifestError):
        Cell(load_manifest(), "no-such-cell")
    with pytest.raises(ManifestError):
        load_module("metrics", "no_such_metric")


def test_peak_table_rejects_an_unknown_device_kind():
    row = manifest.load_peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ManifestError):
        manifest.load_peaks("cpu")
