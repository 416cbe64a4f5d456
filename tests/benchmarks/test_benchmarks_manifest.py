"""BENCHMARK.json against the contract it is written to, and against the
files it names."""

import copy
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


# Size keys that are widths, by the names configurations use (the catalog's
# and the program's): a cut may take depth, experts held, heads held or rows
# of the vocabulary, never one of these.
WIDTH_KEYS = {
    "hidden_size", "d_model", "n_embd", "dim", "intermediate_size",
    "moe_intermediate_size", "ffn_hidden_size", "d_ff", "mlp_ratio",
    "expansion_factor", "expand", "head_dim", "head_size", "d_head",
    "d_state", "state_size", "d_conv", "conv_kernel", "sliding_window",
    "num_experts_per_tok", "experts_per_token", "moe_topk", "top_k",
    "input_hw", "input_channels"}
WIDTH_PARTS = ("intermediate", "latent", "state_size", "proj_")
WIDTH_ENDS = ("_dim", "_rank", "_width", "hidden_size", "head_size")


def is_width(key: str) -> bool:
    return key in WIDTH_KEYS or key.endswith(WIDTH_ENDS) \
        or any(part in key for part in WIDTH_PARTS)


def check_config(entry: dict, cfg: dict) -> None:
    """A manifest entry against its configuration file: the same source and
    the same ``reduced``; every reduced key held in the file beside the
    source's value under ``published``; the deployment the cut stands for
    in one line; no width cut."""
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    reduced = cfg["reduced"]
    assert isinstance(reduced, list) and len(reduced) <= 16
    assert len(set(reduced)) == len(reduced)
    for key in reduced:
        assert NAME.match(key), key
        assert not is_width(key), f"{key} is a width: never cut"
        assert key in cfg, f"{key} is reduced but not in the file"
        assert key in cfg.get("published", {}), \
            f"{key} is reduced but the source's value is not stated"
        assert cfg[key] != cfg["published"][key], \
            f"{key} is listed as reduced and equals the source's"
    if reduced:
        assert _one_line(cfg.get("deployment", "")), \
            "a cut configuration states the deployment it stands for"
    else:
        assert "published" not in cfg or cfg["published"] == {}


def test_configs_are_used_published_and_unreduced(manifest):
    """Every configuration is used and its ``reduced`` is held honest
    (``check_config``); ``vgg16`` is whole."""
    used = {w["config"] for w in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        check_config(c, harness.load_json(os.path.join(harness.ROOT,
                                                       c["file"])))
    vgg = next(c for c in manifest["configs"] if c["name"] == "vgg16")
    assert vgg["reduced"] == []


def test_the_toy_configurations_pass_whole_and_cut(toy_lm_manifest):
    by = {c["name"]: c for c in toy_lm_manifest["configs"]}
    assert by["toy"]["reduced"] == [] and by["toy_lm"]["reduced"]
    for c in by.values():
        check_config(c, harness.load_json(os.path.join(harness.ROOT,
                                                       c["file"])))


def _cut(toy_lm_manifest):
    entry = copy.deepcopy(next(c for c in toy_lm_manifest["configs"]
                               if c["name"] == "toy_lm"))
    return entry, harness.load_json(os.path.join(harness.ROOT,
                                                 entry["file"]))


def _no_published(entry, cfg):
    del cfg["published"]


def _one_value_not_published(entry, cfg):
    del cfg["published"]["vocab"]


def _key_not_in_the_file(entry, cfg):
    del cfg["moe_experts"]


def _a_width(entry, cfg):
    for c in (entry, cfg):
        c["reduced"] = c["reduced"] + ["d_model"]
    cfg["published"]["d_model"] = 2048


def _an_unnamed_width(entry, cfg):
    for c in (entry, cfg):
        c["reduced"] = c["reduced"] + ["kv_lora_rank"]
    cfg["kv_lora_rank"], cfg["published"]["kv_lora_rank"] = 8, 512


def _experts_per_token(entry, cfg):
    for c in (entry, cfg):
        c["reduced"] = c["reduced"] + ["moe_topk"]
    cfg["published"]["moe_topk"] = 8


def _manifest_says_less(entry, cfg):
    entry["reduced"] = entry["reduced"][:-1]


def _manifest_says_whole(entry, cfg):
    entry["reduced"] = []


def _no_deployment(entry, cfg):
    del cfg["deployment"]


def _listed_but_unchanged(entry, cfg):
    cfg["published"]["n_layer"] = cfg["n_layer"]


def _another_source(entry, cfg):
    entry["source"] = "https://example.org/another"


@pytest.mark.parametrize("break_it", [
    _no_published, _one_value_not_published, _key_not_in_the_file, _a_width,
    _an_unnamed_width, _experts_per_token, _manifest_says_less,
    _manifest_says_whole, _no_deployment, _listed_but_unchanged,
    _another_source], ids=lambda f: f.__name__.strip("_"))
def test_a_dishonest_reduced_fails(toy_lm_manifest, break_it):
    entry, cfg = _cut(toy_lm_manifest)
    check_config(entry, cfg)
    break_it(entry, cfg)
    with pytest.raises(AssertionError):
        check_config(entry, cfg)


@pytest.mark.parametrize("key,width", [
    ("hidden_size", True), ("moe_intermediate_size", True),
    ("q_lora_rank", True), ("qk_rope_head_dim", True), ("v_head_dim", True),
    ("num_experts_per_tok", True), ("mlp_ratio", True), ("d_model", True),
    ("num_hidden_layers", False), ("n_layer", False), ("vocab_size", False),
    ("vocab", False), ("n_routed_experts", False), ("moe_experts", False),
    ("num_attention_heads", False)])
def test_which_keys_are_widths(key, width):
    assert is_width(key) is width


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
