"""BENCHMARK.json against the contract it is written to, and against the
files it names."""

import os
import re

import pytest

from benchmarks import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _one_line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    for p in manifest["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert os.path.isdir(os.path.join(harness.ROOT, p))
    assert os.path.getsize(harness.MANIFEST) <= 64 * 1024


def test_the_check_fits_the_drivers_budget_with_24_cells(manifest):
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200 \
        <= 43200


def test_every_name_and_unit_is_in_the_allowed_characters(manifest):
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in manifest[key]]
        assert len(got) == len(set(got)), f"duplicate name in {key}"
        names += got
    names += [w["traffic"] for w in manifest["workloads"]]
    for n in names:
        assert NAME.match(n), n
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_entries_have_just_the_contracts_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _one_line(c["why"]) and _one_line(c["source"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _one_line(w["why"]) and w["chips"] in (1, 4)
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _one_line(m["layer"])


def test_every_cells_files_exist_and_load(manifest):
    pairs = set()
    for w in manifest["workloads"]:
        cell = harness.load_cell(manifest, w["name"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert cell.traffic["name"] == w["traffic"]
        assert cell.config["name"] == w["config"]
        assert isinstance(cell.traffic["worker_config"], dict)
        harness.load_module(manifest, "flops", cell.config["flops"])
        harness.load_module(manifest, "reference", cell.config["reference"])
        for sub, entries in (("end_to_end", cell.end_to_end),
                             ("layer_metrics", cell.per_layer)):
            for m in entries:
                assert callable(harness.load_module(manifest, sub,
                                                    m["name"]).read)


def test_configs_are_used_published_and_unreduced(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] == []


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    for w in manifest["workloads"]:
        cell = harness.load_cell(manifest, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_metric_workload_lists_name_real_cells(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", [])) <= cells


def test_layers_of_the_manifest_are_perf_mds(manifest):
    with open(os.path.join(harness.ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in manifest["per_layer"]}:
        assert f"| {layer} |" in perf, layer


def test_four_chip_cells_are_within_the_limit(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_unknown_workload_is_refused(manifest):
    with pytest.raises(harness.Refused):
        harness.load_cell(manifest, "no-such-cell")
