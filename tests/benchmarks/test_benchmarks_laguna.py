"""The routed model's cell: its manifest entries, configuration (a chip's
share of heads, experts and vocabulary), FLOP counter against a hand
count, reference, traffic and four readers, and a toy-size run of the
model through the harness on the CPU mesh with its 8-bit control."""

import copy
import json
import math
import os

import numpy as np
import pytest
from test_benchmarks_manifest import check_config

from benchmarks import harness, scopes, trace
from benchmarks.flops import laguna as flops

CONFIG = "laguna-s-2.1"
CELL = "laguna-s-2.1-t8192-b1-bsp-1chip"
TRAFFIC = "tokens-t8192-b1-bsp"
TOY_CELL = "toy-laguna-b2-bsp-1chip"
TOY_DIR = "tests/benchmarks/toy"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (48, 5), "num_experts": (256, 8),
           "num_attention_heads": (48, 12), "num_key_value_heads": (8, 2),
           "num_attention_heads_per_layer": ([48, 72, 72, 72] * 12,
                                             [12, 18, 18, 18, 12]),
           "vocab_size": (100352, 12544)}
READERS = [
    {"name": "moe_ms", "unit": "ms/step", "better": "lower",
     "source": "device_trace", "layer": "expert layer",
     "moves": "train_throughput", "workloads": [CELL]},
    {"name": "expert_roofline_share", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "expert layer", "moves": "mfu",
     "workloads": [CELL]},
    {"name": "mixed_attention_ms", "unit": "ms/step", "better": "lower",
     "source": "device_trace", "layer": "mixed attention",
     "moves": "train_throughput", "workloads": [CELL]},
    {"name": "window_attention_roofline_share", "unit": "%",
     "better": "higher", "source": "device_trace",
     "layer": "mixed attention", "moves": "mfu", "workloads": [CELL]}]


@pytest.fixture(scope="module")
def entry(manifest):
    return next(c for c in manifest["configs"] if c["name"] == CONFIG)


@pytest.fixture(scope="module")
def config(entry):
    return harness.load_json(os.path.join(harness.ROOT, entry["file"]))


@pytest.fixture(scope="module")
def toy_laguna_manifest(toy_manifest):
    """The toy manifest with the routed model at the tests' size, added as
    files and entries alone."""
    m = copy.deepcopy(toy_manifest)
    m["configs"].append({
        "name": "toy_laguna",
        "source": "theanompi_tpu/models/routed_lm.py RoutedLM at a "
                  "rehearsal size (the routed model of benchmarks/configs/"
                  "laguna-s-2.1.json, not a published size)",
        "file": TOY_DIR + "/configs/toy_laguna.json",
        "reduced": ["num_experts"],
        "why": "CPU rehearsal of the routed model's cell"})
    m["workloads"].append({
        "name": TOY_CELL, "config": "toy_laguna",
        "traffic": "toy-laguna-b2-bsp", "chips": 1,
        "why": "rehearsal: two window layers to two full, 4 of 16 experts"})
    m["per_layer"] += [dict(r, workloads=[TOY_CELL]) for r in READERS]
    return m


# -- the manifest ------------------------------------------------------------------

def test_the_manifest_has_the_configuration_its_cell_and_readers(manifest):
    """Appended behind what the benchmark had; nothing here pins what a
    later PR appends behind them."""
    configs = [c["name"] for c in manifest["configs"]]
    cells = [w["name"] for w in manifest["workloads"]]
    assert configs.index(CONFIG) >= 2 and configs[:2] == ["vgg16",
                                                          "ouro-2.6b"]
    assert cells.index(CELL) >= 3
    cell = manifest["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert [c for c in cells if c.startswith(CONFIG)] == [CELL]   # one cell
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(READERS[0]["name"])
    assert at >= 21 and manifest["per_layer"][at:at + 4] == READERS
    # the three the looped cell brought keep their lists
    for m in manifest["per_layer"]:
        if m["name"] in ("loop_stack_ms", "exit_head_ms",
                         "attention_roofline_share"):
            assert m["workloads"] == ["ouro-2.6b-t4096-b2-bsp-1chip"]


def test_the_cell_reads_the_sixteen_generic_metrics_and_its_four(manifest):
    cell = harness.load_cell(manifest, CELL)
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    got = [m["name"] for m in cell.per_layer]
    unlisted = [m["name"] for m in manifest["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 16 and got[:16] == unlisted
    assert got[16:20] == [r["name"] for r in READERS]
    assert not any(n.startswith(("exchange_", "loop_", "exit_"))
                   or n == "attention_roofline_share" for n in got)
    assert {"train_throughput", "mfu", "peak_hbm", "setup_s"} <= {
        m["name"] for m in cell.end_to_end}
    for other in ("vgg16-b384-bsp-1chip", "ouro-2.6b-t4096-b2-bsp-1chip"):
        assert not {r["name"] for r in READERS} & {
            m["name"] for m in harness.load_cell(manifest, other).per_layer}


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r["name"])
def test_a_readers_entry_has_a_layer_a_metric_and_a_file(manifest, reader):
    assert reader in manifest["per_layer"]
    assert reader["moves"] in {m["name"] for m in manifest["end_to_end"]}
    with open(os.path.join(harness.ROOT, "PERF.md")) as f:
        assert f"| {reader['layer']} |" in f.read()
    assert callable(harness.load_module(manifest, "layer_metrics",
                                        reader["name"]).read)


# -- the configuration ---------------------------------------------------------------

def test_the_configuration_is_the_sources_but_for_its_cut(entry, config):
    check_config(entry, config)
    assert entry["reduced"] == config["reduced"] == list(REDUCED)
    assert config["published"] == {k: v[0] for k, v in REDUCED.items()}
    for key, (_, held) in REDUCED.items():
        assert config[key] == held, key
    assert entry["source"] == config["source"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():      # every key, typed in
        assert key in config, key
        if key not in config["reduced"]:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value, key


def test_the_cut_is_a_quarter_of_the_heads_and_the_floors(config):
    """One whole period behind the leading dense layer, 8 experts of 256
    with the router whole, a quarter of each layer's heads, an eighth of
    the vocabulary; no width moved."""
    assert config["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert config["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert len(config["layer_types"]) == len(config["gating_types"]) == 48
    pub = config["published"]
    assert [4 * h for h in config["num_attention_heads_per_layer"]] \
        == pub["num_attention_heads_per_layer"][:5]
    assert 4 * config["num_key_value_heads"] == pub["num_key_value_heads"]
    assert 8 * config["vocab_size"] == pub["vocab_size"]
    assert (config["hidden_size"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["sliding_window"], config["num_experts_per_tok"]) == (
                3072, 128, 12288, 1024, 1024, 512, 10)
    for key in ("deployment", "assumed", "modelfile", "modelclass",
                "n_class", "worker_config", "flops", "reference"):
        assert key in config, key
    for point in ("block", "gate", "rotary", "router", "shared_expert",
                  "auxiliary_loss", "share", "init", "optimizer",
                  "recomputation"):
        assert point in config["assumed"], point
    assert "32 chips" in config["deployment"]
    assert config["check"]["optimizer"]["name"] == "adam"
    assert set(config["check"]["grad_leaves"]) == {
        "embed", "head", "block1/moe/router", "block1/moe/experts",
        "block1/attn/wg", "block0/mlp/wd", "block2/attn/wq", "norm_f"}
    assert "first_cost_tol" not in config       # the harness's 0.25 holds


def test_the_programs_keys_say_what_the_published_ones_say(config, manifest):
    wc = config["worker_config"]
    for ours, theirs in (
            ("vocab", "vocab_size"), ("d_model", "hidden_size"),
            ("head_dim", "head_dim"), ("n_kv_head", "num_key_value_heads"),
            ("n_layer", "num_hidden_layers"),
            ("n_head_per_layer", "num_attention_heads_per_layer"),
            ("d_ff", "intermediate_size"),
            ("expert_width", "moe_intermediate_size"),
            ("shared_width", "shared_expert_intermediate_size"),
            ("top_k", "num_experts_per_tok"),
            ("routed_scale", "moe_routed_scaling_factor"),
            ("window", "sliding_window"), ("rope", "rope_parameters"),
            ("norm_eps", "rms_norm_eps"), ("seq_len", "seq_len")):
        assert wc[ours] == config[theirs], (ours, theirs)
    n = wc["n_layer"]
    assert wc["layer_types"] == config["layer_types"][:n]
    assert wc["mlp_layer_types"] == config["mlp_layer_types"][:n]
    # the router scores every expert of the model; this chip holds eight
    assert wc["n_experts"] == config["published"]["num_experts"] == 256
    assert wc["experts_held"] == [0, config["num_experts"]]
    assert config["n_class"] == config["vocab_size"]
    assert wc["learning_rate"] \
        == config["check"]["optimizer"]["learning_rate"]
    assert wc["attn_impl"] == "flash"
    cell = harness.load_cell(manifest, CELL)
    traffic = cell.traffic["worker_config"]
    assert traffic["seq_len"] == config["seq_len"] == 8192
    assert traffic["batch_size"] == 1 and traffic["steps_per_call"] == 1
    assert traffic["para_load"] and traffic["para_load_workers"] == 2
    assert traffic["synthetic_train"] >= 4096 and traffic["rule"] == "bsp"


def test_the_model_file_builds_the_shapes_the_counter_counts(config):
    """``n_params`` of the FLOP file against the model's own tree, leaf
    shapes only (nothing of this size is drawn here)."""
    import jax

    from theanompi_tpu.models.routed_lm import RoutedLM

    class Shapes(RoutedLM):
        def init_params(self, key):
            return jax.eval_shape(super().init_params, key)

    model = Shapes(dict(config["worker_config"], n_workers=1, batch_size=1,
                        synthetic_train=2, seq_len=8))
    shapes = {k: v.shape for k, v in __import__(
        "benchmarks.reference.check", fromlist=["by_path"]).by_path(
            model.params).items()}
    assert sum(math.prod(s) for s in shapes.values()) \
        == flops.n_params(config) == config["n_params"] == 602_680_320
    assert shapes["block1/moe/router"] == (3072, 256)
    assert shapes["block1/moe/experts/wg"] == (8, 3072, 1024)
    assert shapes["block2/attn/wq"] == (3072, 18 * 128)
    assert shapes["block4/attn/wq"] == (3072, 12 * 128)
    assert shapes["block2/attn/wk"] == (3072, 2 * 128)
    assert shapes["block1/attn/wg"] == (3072, 18)
    assert shapes["block0/mlp/wd"] == (12288, 3072)
    assert shapes["head/w"] == (3072, 12544)
    assert [b.attn.window for b in model.blocks] == [None, 512, 512, 512,
                                                     None]
    assert [len(b.attn.freq) for b in model.blocks] == [32, 64, 64, 64, 32]


# -- the FLOP counter against the hand count -------------------------------------------

def test_the_counter_gives_the_hand_count(config):
    """ISSUE 37's arithmetic: 305.9M multiply-accumulates and 1.835 GFLOP a
    token trained, 15.04 TFLOP a step of one sequence of 8,192."""
    t, d, hd = 8192, 3072, 128
    full = d * hd * (2 * 12 + 2 * 2) + d * 12           # projections, gate
    window = d * hd * (2 * 18 + 2 * 2) + d * 18
    assert (full, window) == (11_046_912, 15_783_936)
    full_core = 2 * hd * 12 * (t * (t + 1) // 2)        # the causal half
    window_core = 2 * hd * 18 * (512 * 513 // 2 + (t - 512) * 512)
    assert full_core == t * 12_584_448 and window_core == t * 2_285_712
    assert window_core / t / (2 * hd * 18) == 512 - 512 * 511 / (2 * t)
    expert = 3 * d * 1024
    routed = expert * 10 * 8 // 256                     # expected, a token
    sparse = d * 256 + expert + routed
    per_token = (full + 12_584_448 + 3 * d * 12288) \
        + 3 * (window + 2_285_712 + sparse) \
        + (full + 12_584_448 + sparse) + d * 12544
    assert per_token == 305_943_984
    assert flops.forward_macs_per_sample(config) == t * per_token
    assert flops.train_flops_per_sample(config) == 6 * t * per_token
    assert 6 * per_token / 1e9 == pytest.approx(1.835, abs=0.001)
    assert flops.train_flops_per_sample(config) / 1e12 \
        == pytest.approx(15.04, abs=0.005)
    assert flops.attn_core_train_flops_per_sample(config) \
        == 6 * (2 * full_core + 3 * window_core)
    assert flops.routed_experts_train_flops_per_sample(config) \
        == 6 * 4 * t * routed
    assert flops.n_params(config) == 602_680_320 == (
        full + 3 * d * 12288 + 2 * d) + 3 * (
            window + d * 256 + 9 * expert + 2 * d) + (
                full + d * 256 + 9 * expert + 2 * d) + 2 * d * 12544 + d
    assert 16 * flops.n_params(config) / 1e9 == pytest.approx(9.64, abs=0.005)


def test_the_shares_of_the_step_are_what_the_cells_why_says(config):
    whole = flops.train_flops_per_sample(config)
    t, d = 8192, 3072
    core = flops.attn_core_train_flops_per_sample(config)
    proj = 6 * t * (2 * 11_046_912 + 3 * 15_783_936)
    assert (core + proj) / whole == pytest.approx(0.33, abs=0.01)
    moe = 6 * t * 4 * (d * 256 + 3 * d * 1024) \
        + flops.routed_experts_train_flops_per_sample(config)
    assert moe / whole == pytest.approx(0.17, abs=0.01)
    assert 6 * t * 3 * d * 12288 / whole == pytest.approx(0.37, abs=0.01)
    assert 6 * t * d * 12544 / whole == pytest.approx(0.13, abs=0.01)
    # each held expert sees 320 tokens a step under uniform routing
    assert t * 10 // 256 == 320


def test_a_window_no_shorter_than_the_sequence_is_the_causal_half():
    assert flops.keys_seen("sliding_attention", 8, 8) \
        == flops.keys_seen("full_attention", 8, 8) == 36
    assert flops.keys_seen("sliding_attention", 8, 3) == 6 + 5 * 3


def test_the_harness_reads_the_counter_per_sequence(manifest, config):
    cell = harness.load_cell(manifest, CELL)
    mod = harness.load_module(manifest, "flops", cell.config["flops"])
    assert mod.train_flops_per_sample(cell.config) \
        == flops.train_flops_per_sample(config)


# -- the reference -----------------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    for path in ("benchmarks/reference/laguna.py",
                 TOY_DIR + "/reference/toy_laguna.py"):
        with open(os.path.join(harness.ROOT, path)) as f:
            text = f.read()
        assert "theanompi_tpu" not in text.replace(
            "theanompi_tpu/models", ""), path


def test_the_reference_protocol_is_whole(manifest, config):
    ref = harness.load_module(manifest, "reference", config["reference"])
    assert all(callable(getattr(ref, f))
               for f in ("forward", "batch", "train_loss"))
    small = dict(config, worker_config=dict(config["worker_config"],
                                            seq_len=8))
    x, y = ref.batch(small, np.random.RandomState(2147489000))
    assert x.dtype == y.dtype == np.int32 and x.shape == y.shape == (1, 8)
    assert (x[:, 1:] == y[:, :-1]).all()
    assert 0 <= x.min() and x.max() < config["vocab_size"]
    pub = ref.PUBLISHED
    assert (pub["head_dim"], pub["window"], pub["top_k"], pub["scale"],
            pub["eps"], pub["first_held"]) == (128, 512, 10, 2.5, 1e-6, 0)
    assert list(pub["layer_types"]) == config["layer_types"]
    assert pub["rope"] == config["rope_parameters"]


# -- the toy cell through the harness -------------------------------------------------------

@pytest.fixture(scope="module")
def toy_run(toy_laguna_manifest):
    return harness.run_cell(toy_laguna_manifest, TOY_CELL, seed=3,
                            seconds=1.5, trace=False)


def failing(run):
    return {k for k, (value, limit) in run.compared.items()
            if not value <= limit}


def test_the_toy_cell_runs_and_is_correct(toy_run, toy_laguna_manifest):
    run = toy_run
    assert run.problems == [] and run.correct and run.failed == 0
    assert run.attempted == run.window.steps > 10
    assert run.compiles_in_window == 0 and run.global_batch == 2
    assert abs(run.first_cost - math.log(128)) < harness.FIRST_COST_TOL
    ref = run.reference
    assert ref["ok"] and ref["steps"] == 3 and ref["leaves_nought"] == []
    assert 0 < ref["grad_norm_gap"] <= ref["grad_norm_tol"]
    assert 0 < ref["change_norm_gap"] <= ref["change_norm_tol"]
    assert ref["sys_losses"][0] == run.first_cost
    cell = harness.load_cell(toy_laguna_manifest, TOY_CELL)
    assert run.flops_per_sample == flops.train_flops_per_sample(cell.config)


@pytest.mark.parametrize("seed", [41, 2147489999])
def test_sound_toy_runs_of_other_seeds_are_correct(toy_laguna_manifest,
                                                   seed):
    run = harness.run_cell(toy_laguna_manifest, TOY_CELL, seed=seed,
                           seconds=0.5, trace=False)
    assert run.correct and failing(run) == set(), run.problems


@pytest.mark.parametrize("dtype,passes", [("float32", True),
                                          ("float8_e4m3fn", False)])
def test_the_limits_separate_the_8_bit_control(toy_laguna_manifest, toy_run,
                                               dtype, passes):
    run = harness.run_cell(toy_laguna_manifest, TOY_CELL, seed=3,
                           seconds=0.5, trace=False,
                           control={"compute_dtype": dtype})
    assert toy_run.correct and run.correct is passes, run.problems
    if passes:                  # float32: the reference is the model
        assert run.reference["grad_norm_gap"] < 1e-4
        assert run.reference["change_norm_gap"] < 1e-4
        assert run.reference["grad_rel_err"] < 1e-4
    else:
        assert failing(run) & {"grad_norm_gap", "change_norm_gap"}


def test_the_toy_cell_is_added_by_files_alone(toy_manifest,
                                              toy_laguna_manifest):
    for key, had in toy_manifest.items():
        if isinstance(had, list):
            assert toy_laguna_manifest[key][:len(had)] == had, key
    cell = harness.load_cell(toy_laguna_manifest, TOY_CELL)
    check_config(toy_laguna_manifest["configs"][-1], cell.config)
    assert flops.n_params(cell.config) == cell.config["n_params"]


# -- the four readers ------------------------------------------------------------------------

FWD = "jit(per_worker)/jvp(block1)/"
BACK = "jit(per_worker)/transpose(jvp(block1))/jvp(block1)/checkpoint/"


class FakeRun:
    """What a reader takes of a traced run: one chip, a stretch of 300 ns
    in which the host counted two steps."""

    steps_per_call, global_batch = 1, 1
    peaks = {"bf16_flops_per_s": 197e12}

    def __init__(self, cell, scoped=True):
        self.cell = cell
        self.trace_window = (100, 400)
        self.traced = harness.Stretch(seconds=3e-7, steps=2)
        ops = [("%a.1 = f32[] fusion(...)", 100, 130),     # attn_core fwd
               ("%a.2 = f32[] fusion(...)", 125, 140),     # attn projection
               ("%r.1 = f32[] fusion(...)", 140, 150),     # router
               ("%e.1 = f32[] fusion(...)", 150, 170),     # experts fwd
               ("%e.2 = f32[] fusion(...)", 170, 200),     # experts bwd
               ("%s.1 = f32[] fusion(...)", 200, 210),     # shared expert
               ("%u.1 = f32[] fusion(...)", 210, 220),     # update
               ("%a.1 = f32[] fusion(...)", 300, 330),
               ("%e.1 = f32[] fusion(...)", 380, 415)]     # over the end
        self.tables = trace.TraceTables([trace.DeviceTables(
            0, modules=[("jit_per_worker(1)", 100, 220),
                        ("jit_per_worker(1)", 300, 370)], ops=ops)])
        self.scopes = {
            "a.1": FWD + "attn/attn_core/pallas_call",
            "a.2": FWD + "attn/dot_general",
            "r.1": FWD + "moe/router/dot_general",
            "e.1": FWD + "moe/while/body/experts/ragged_dot_general",
            "e.2": BACK + "moe/while/body/transpose(jvp(experts))/"
                          "ragged_dot_general",
            "s.1": FWD + "moe/shared_expert/dot_general",
            "u.1": "jit(per_worker)/mul"} if scoped else {}


def read(manifest, name, run):
    return harness.load_module(manifest, "layer_metrics", name).read(run)


def test_the_readers_take_their_scopes_time_per_step(manifest):
    run = FakeRun(harness.load_cell(manifest, CELL))
    # moe: 140..210 and the run over the end clipped to 380..400
    assert read(manifest, "moe_ms", run) == pytest.approx(
        (70 + 20) / 2 / 1e6)
    assert read(manifest, "mixed_attention_ms", run) == pytest.approx(
        (40 + 30) / 2 / 1e6)
    assert scopes.scope_ms_per_step(run, "experts") == pytest.approx(
        (50 + 20) / 2 / 1e6)
    want = flops.routed_experts_train_flops_per_sample(run.cell.config)
    assert read(manifest, "expert_roofline_share", run) == pytest.approx(
        100 * want / 197e12 / 35e-9)
    want = flops.attn_core_train_flops_per_sample(run.cell.config)
    assert read(manifest, "window_attention_roofline_share", run) \
        == pytest.approx(100 * want / 197e12 / 30e-9)


@pytest.mark.parametrize("name", [r["name"] for r in READERS])
def test_a_reader_that_finds_nothing_returns_nothing(manifest, name):
    cell = harness.load_cell(manifest, CELL)
    unjoined = FakeRun(cell, scoped=False)
    assert read(manifest, name, unjoined) is None
    untraced = FakeRun(cell)
    untraced.tables = untraced.trace_window = untraced.traced = None
    assert read(manifest, name, untraced) is None
    # a program without the scope (a parent, another model): nothing
    other = FakeRun(cell)
    other.scopes = {k: "jit(per_worker)/jvp()/conv_general_dilated"
                    for k in other.scopes}
    assert read(manifest, name, other) is None
    # a configuration whose counter has no such count: no share
    if name.endswith("roofline_share"):
        looped = FakeRun(harness.load_cell(manifest,
                                           "ouro-2.6b-t4096-b2-bsp-1chip"))
        if name == "expert_roofline_share":
            assert read(manifest, name, looped) is None
