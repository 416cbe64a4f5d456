"""The Kimi Linear cell: its manifest entries, configuration (a chip's
share of experts and vocabulary, the catalog's keys whole), FLOP and byte
counter against a hand count, reference, traffic and three readers, and a
toy-size run of the model through the harness on the CPU mesh with its
8-bit control."""

import copy
import json
import math
import os

import numpy as np
import pytest
from test_benchmarks_manifest import check_config

from benchmarks import harness, trace
from benchmarks.flops import kimi_linear as flops
from theanompi_tpu.models.layers import DeltaAttention

CONFIG = "kimi-linear-48b-a3b"
CELL = "kimi-linear-48b-a3b-t16384-b1-bsp-1chip"
TRAFFIC = "tokens-t16384-b1-bsp"
TOY_CELL = "toy-kimi-b2-bsp-1chip"
TOY_DIR = "tests/benchmarks/toy"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")
REDUCED = {"num_hidden_layers": (27, 5), "num_experts": (256, 8),
           "vocab_size": (163840, 20480)}
READERS = [
    {"name": "kda_ms", "unit": "ms/step", "better": "lower",
     "source": "device_trace", "layer": "linear attention",
     "moves": "train_throughput", "workloads": [CELL]},
    {"name": "kda_core_roofline_share", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "linear attention", "moves": "mfu",
     "workloads": [CELL]},
    {"name": "mla_ms", "unit": "ms/step", "better": "lower",
     "source": "device_trace", "layer": "latent attention",
     "moves": "train_throughput", "workloads": [CELL]}]


@pytest.fixture(scope="module")
def entry(manifest):
    return next(c for c in manifest["configs"] if c["name"] == CONFIG)


@pytest.fixture(scope="module")
def config(entry):
    return harness.load_json(os.path.join(harness.ROOT, entry["file"]))


@pytest.fixture(scope="module")
def toy_kimi_manifest(toy_manifest):
    """The toy manifest with the Kimi Linear model at the tests' size,
    added as files and entries alone."""
    m = copy.deepcopy(toy_manifest)
    m["configs"].append({
        "name": "toy_kimi",
        "source": "theanompi_tpu/models/routed_lm.py RoutedLM in Kimi "
                  "Linear's form at a rehearsal size (the model of "
                  "benchmarks/configs/kimi-linear-48b-a3b.json, not a "
                  "published size)",
        "file": TOY_DIR + "/configs/toy_kimi.json",
        "reduced": ["num_experts"],
        "why": "CPU rehearsal of the Kimi Linear cell"})
    m["workloads"].append({
        "name": TOY_CELL, "config": "toy_kimi", "traffic": "toy-kimi-b2-bsp",
        "chips": 1,
        "why": "rehearsal: kda, kda, kda, mla, kda; 4 of 16 experts"})
    m["per_layer"] += [dict(r, workloads=[TOY_CELL]) for r in READERS]
    return m


# -- the manifest ------------------------------------------------------------------

def test_the_manifest_has_the_configuration_its_cell_and_readers(manifest):
    """Appended behind what the benchmark had; nothing here pins what a
    later PR appends behind them."""
    configs = [c["name"] for c in manifest["configs"]]
    cells = [w["name"] for w in manifest["workloads"]]
    assert configs.index(CONFIG) >= 4 and configs[:4] == [
        "vgg16", "ouro-2.6b", "laguna-s-2.1", "lfm2-24b-a2b"]
    assert cells.index(CELL) >= 5
    cell = manifest["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert [c for c in cells if c.startswith(CONFIG)] == [CELL]   # one cell
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(READERS[0]["name"])
    # the three readers, each listing this cell alone
    assert at >= 35 and manifest["per_layer"][at:at + 3] == READERS


def test_the_cell_reads_the_sixteen_generic_metrics_and_its_three(manifest):
    cell = harness.load_cell(manifest, CELL)
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    got = [m["name"] for m in cell.per_layer]
    unlisted = [m["name"] for m in manifest["per_layer"]
                if "workloads" not in m]
    ours = [r["name"] for r in READERS]
    # what a later PR appends, unlisted or listing this cell, may follow
    assert len(unlisted) >= 16 and got[:16] == unlisted[:16]
    assert [g for g in got if g in ours] == ours
    assert set(unlisted) <= set(got)
    assert {"train_throughput", "mfu", "peak_hbm", "setup_s"} <= {
        m["name"] for m in cell.end_to_end}
    for other in ("vgg16-b384-bsp-1chip", "ouro-2.6b-t4096-b2-bsp-1chip",
                  "laguna-s-2.1-t8192-b1-bsp-1chip",
                  "lfm2-24b-a2b-t8192-b1-bsp-1chip"):
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


def test_the_traffic_is_one_long_sequence_and_no_epoch_ends(manifest):
    traffic = harness.load_cell(manifest, CELL).traffic
    wc = traffic["worker_config"]
    assert traffic["name"] == TRAFFIC
    assert (wc["rule"], wc["batch_size"], wc["seq_len"], wc["steps_per_call"],
            wc["para_load"], wc["para_load_workers"]) == (
                "bsp", 1, 16384, 1, True, 2)
    assert wc["synthetic_train"] == 4096 and "pad" not in json.dumps(wc)


# -- the configuration ---------------------------------------------------------------

def test_the_configuration_is_the_sources_but_for_its_cut(entry, config):
    check_config(entry, config)
    assert entry["reduced"] == config["reduced"] == list(REDUCED)
    assert config["published"] == {k: v[0] for k, v in REDUCED.items()}
    for key, (_, held) in REDUCED.items():
        assert config[key] == held, key
    assert entry["source"] == config["source"] == SOURCE
    assert config["model_type"] == "kimi_linear"


def test_the_cut_is_the_floors_and_no_width_moves(config):
    """The dense layer and the four behind it, which hold one whole 3 : 1
    period (the pattern counts from 1); 8 experts of 256 with the router
    whole; an eighth of the vocabulary; every width as published."""
    lin = config["linear_attn_config"]
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) \
        == list(range(1, 28))
    held = ["kda" if i + 1 in lin["kda_layers"] else "mla"
            for i in range(config["num_hidden_layers"])]
    assert held == ["kda", "kda", "kda", "mla", "kda"]
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] == 4
    pub = config["published"]
    assert 32 * config["num_experts"] == pub["num_experts"]
    assert 8 * config["vocab_size"] == pub["vocab_size"]
    assert (config["hidden_size"], lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"], config["num_attention_heads"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["kv_lora_rank"],
            config["moe_intermediate_size"], config["num_experts_per_token"],
            config["intermediate_size"], config["num_shared_experts"]) == (
                2304, 32, 128, 4, 32, 128, 64, 128, 512, 1024, 8, 9216, 1)
    assert config["q_lora_rank"] is None and config["mla_use_nope"]
    for key in ("deployment", "assumed", "modelfile", "modelclass",
                "n_class", "worker_config", "flops", "reference"):
        assert key in config, key
    for point in ("block", "pattern", "head_dim", "kda", "mla", "router",
                  "expert_bias", "experts", "dense", "share", "init",
                  "compute_dtype", "optimizer", "recomputation", "data"):
        assert point in config["assumed"], point
    for said in ("A_log", "b_dt", "0.005", "9.927"):
        assert said in config["assumed"]["init"], said
    assert "1e-6" in config["assumed"]["router"]
    assert "32 chips" in config["deployment"]
    assert config["check"]["optimizer"]["name"] == "adam"
    for leaf in ("block0/kda/A_log", "block0/kda/b_dt", "block0/kda/w_fb",
                 "block3/mla/w_kvb", "block3/mla/wq", "block1/moe/router"):
        assert leaf in config["check"]["grad_leaves"], leaf
    assert "first_cost_tol" not in config       # the harness's 0.25 holds


def test_the_programs_keys_say_what_the_published_ones_say(config, manifest):
    wc = config["worker_config"]
    lin = config["linear_attn_config"]
    for ours, theirs in (
            ("vocab", "vocab_size"), ("d_model", "hidden_size"),
            ("n_layer", "num_hidden_layers"), ("d_ff", "intermediate_size"),
            ("expert_width", "moe_intermediate_size"),
            ("shared_width", "moe_intermediate_size"),
            ("top_k", "num_experts_per_token"),
            ("routed_scale", "routed_scaling_factor"),
            ("norm_eps", "rms_norm_eps"), ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("seq_len", "seq_len")):
        assert wc[ours] == config[theirs], (ours, theirs)
    assert wc["kda_head_dim"] == lin["head_dim"]
    assert lin["short_conv_kernel_size"] == DeltaAttention.TAPS
    n = wc["n_layer"]
    assert wc["layer_types"] == [
        "kda" if i + 1 in lin["kda_layers"] else "mla" for i in range(n)]
    dense = config["first_k_dense_replace"]
    assert wc["mlp_layer_types"] == ["dense"] * dense + ["sparse"] * (
        n - dense)
    assert wc["n_head_per_layer"] == [lin["num_heads"]] * n \
        == [config["num_attention_heads"]] * n
    # the router scores every expert of the model; this chip holds eight
    assert wc["n_experts"] == config["published"]["num_experts"] == 256
    assert wc["experts_held"] == [0, config["num_experts"]]
    assert (wc["router_scoring"], wc["tie_word_embeddings"]) == (
        config["moe_router_activation_func"], config["tie_word_embeddings"])
    assert "head_dim" not in wc            # the top-level 72 is read by none
    assert config["n_class"] == config["vocab_size"]
    assert wc["learning_rate"] \
        == config["check"]["optimizer"]["learning_rate"]
    assert wc["attn_impl"] == "flash"
    traffic = harness.load_cell(manifest, CELL).traffic["worker_config"]
    assert traffic["seq_len"] == config["seq_len"] == 16384


def test_the_model_file_builds_the_shapes_the_counter_counts(config):
    """``n_params`` of the counter against the model's own tree, leaf
    shapes only (nothing of this size is drawn here), and the expert bias
    in the state."""
    import jax

    from benchmarks.reference.check import by_path
    from theanompi_tpu.models.routed_lm import RoutedLM

    class Shapes(RoutedLM):
        def init_params(self, key):
            return jax.eval_shape(super().init_params, key)

    model = Shapes(dict(config["worker_config"], n_workers=1, batch_size=1,
                        synthetic_train=2, seq_len=64))
    shapes = {k: v.shape for k, v in by_path(model.params).items()}
    assert sum(math.prod(s) for s in shapes.values()) \
        == flops.n_params(config) == config["n_params"] == 602_433_408
    assert shapes["block0/kda/wq"] == (2304, 32 * 128)
    assert shapes["block0/kda/q_conv"] == (4, 32 * 128)
    assert shapes["block0/kda/w_fa"] == (2304, 128)
    assert shapes["block0/kda/w_fb"] == (128, 32 * 128)
    assert shapes["block0/kda/w_gb"] == (128, 32 * 128)
    assert shapes["block0/kda/A_log"] == (32,)
    assert shapes["block0/mlp/wd"] == (9216, 2304)
    assert shapes["block3/mla/wq"] == (2304, 32 * 192)
    assert shapes["block3/mla/w_kva"] == (2304, 512 + 64)
    assert shapes["block3/mla/w_kvb"] == (512, 32 * 256)
    assert shapes["block3/mla/wo"] == (32 * 128, 2304)
    assert shapes["block1/moe/router"] == (2304, 256)
    assert shapes["block1/moe/experts/wd"] == (8, 1024, 2304)
    assert shapes["block1/moe/shared_expert/wg"] == (2304, 1024)
    assert shapes["embed/w"] == (20480, 2304)
    assert shapes["head/w"] == (2304, 20480)
    assert {k: v.shape for k, v in model.bn_state.items()} == {
        f"block{i}": (256,) for i in range(1, 5)}


# -- the counter against the hand count ---------------------------------------------

def test_the_counter_gives_the_hand_count(config):
    """Per token: a kda layer's projections and taps 39,510,016 and its
    chunked core 2,255,200 multiply-accumulates; the mla layer's
    projections 29,114,368 and its causal core 10,240 (T + 1) / 2; the
    dense layer 63,700,992; a routed layer 9,437,184; the head 47,185,920:
    42.14 TFLOP a step of one sequence of 16,384.  The core's bytes are 38
    a token and channel and 12 a token and head, each kda layer."""
    t, d = 16384, 2304
    kda = 4 * d * 4096 + 2 * (d * 128 + 128 * 4096) + d * 32 + 3 * 4 * 4096
    assert kda == 39_510_016
    chunk = flops.kda_core_macs_per_chunk_head(64, 128, 128)
    assert chunk == 2080 * 512 + 2016 * 128 + 64 * 63 * 62 // 6 \
        + 3 * 64 * 128 * 128 == 4_510_400
    core = chunk * 32 // 64
    assert core == 2_255_200
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d
    assert mla == 29_114_368
    routed = d * 256 + 3 * d * 1024 + 3 * d * 1024 * 8 * 8 // 256
    assert routed == 9_437_184
    per_token = 4 * (kda + core) + mla + 3 * d * 9216 + 4 * routed \
        + d * 20480
    assert flops.forward_macs_per_sample(config) \
        == t * per_token + 32 * 320 * t * (t + 1) // 2
    assert flops.train_flops_per_sample(config) / 1e12 \
        == pytest.approx(42.14, abs=0.005)
    assert flops.kda_core_train_flops_per_sample(config) \
        == 6 * 4 * t * core
    assert flops.kda_core_train_bytes_per_sample(config) \
        == 4 * t * (4096 * 38 + 32 * 12) == 10_225_713_152
    # memory-bound: 12.49 ms of bytes at 819 GB/s against 4.50 of FLOPs
    assert flops.kda_core_train_bytes_per_sample(config) / 819e9 \
        > flops.kda_core_train_flops_per_sample(config) / 197e12
    assert flops.n_params(config) == 602_433_408
    assert 16 * flops.n_params(config) / 1e9 == pytest.approx(9.64,
                                                              abs=0.005)


def test_the_harness_reads_the_counter_per_sequence(manifest, config):
    cell = harness.load_cell(manifest, CELL)
    mod = harness.load_module(manifest, "flops", cell.config["flops"])
    assert mod.train_flops_per_sample(cell.config) \
        == flops.train_flops_per_sample(config)


# -- the reference -------------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    for path in ("benchmarks/reference/kimi_linear.py",
                 TOY_DIR + "/reference/toy_kimi.py"):
        with open(os.path.join(harness.ROOT, path)) as f:
            text = f.read()
        assert "theanompi_tpu" not in text, path


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
    assert (pub["head_dim"], pub["nope"], pub["rope"], pub["v_dim"],
            pub["top_k"], pub["scale"], pub["eps"], pub["first_held"]) == (
                128, 128, 64, 128, 8, 2.446, 1e-5, 0)


# -- the toy cell through the harness -------------------------------------------------

@pytest.fixture(scope="module")
def toy_run(toy_kimi_manifest):
    return harness.run_cell(toy_kimi_manifest, TOY_CELL, seed=2147500000,
                            seconds=1.5, trace=False)


def failing(run):
    return {k for k, (value, limit) in run.compared.items()
            if not value <= limit}


def test_the_toy_cell_runs_and_is_correct(toy_run, toy_kimi_manifest):
    run = toy_run
    assert run.problems == [] and run.correct and run.failed == 0
    assert run.attempted == run.window.steps > 5
    assert run.compiles_in_window == 0 and run.global_batch == 2
    assert abs(run.first_cost - math.log(128)) < harness.FIRST_COST_TOL
    ref = run.reference
    assert ref["ok"] and ref["steps"] == 3 and ref["leaves_nought"] == []
    assert 0 < ref["grad_norm_gap"] <= ref["grad_norm_tol"]
    assert 0 < ref["change_norm_gap"] <= ref["change_norm_tol"]
    assert ref["sys_losses"][0] == run.first_cost
    cell = harness.load_cell(toy_kimi_manifest, TOY_CELL)
    assert run.flops_per_sample == flops.train_flops_per_sample(cell.config)


@pytest.mark.parametrize("dtype,passes", [("float32", True),
                                          ("float8_e4m3fn", False)])
def test_the_limits_separate_the_8_bit_control(toy_kimi_manifest, toy_run,
                                               dtype, passes):
    run = harness.run_cell(toy_kimi_manifest, TOY_CELL, seed=2147500000,
                           seconds=0.5, trace=False,
                           control={"compute_dtype": dtype})
    assert toy_run.correct and run.correct is passes, run.problems
    if passes:                  # float32: the reference is the model
        assert run.reference["grad_norm_gap"] < 1e-4
        assert run.reference["change_norm_gap"] < 1e-4
    else:
        assert failing(run) & {"grad_norm_gap", "change_norm_gap"}


def test_the_toy_cell_is_added_by_files_alone(toy_manifest,
                                              toy_kimi_manifest):
    for key, had in toy_manifest.items():
        if isinstance(had, list):
            assert toy_kimi_manifest[key][:len(had)] == had, key
    cell = harness.load_cell(toy_kimi_manifest, TOY_CELL)
    check_config(toy_kimi_manifest["configs"][-1], cell.config)
    assert flops.n_params(cell.config) == cell.config["n_params"]


# -- the three readers --------------------------------------------------------------------

FWD = "jit(per_worker)/jvp(block0)/"
BACK = "jit(per_worker)/transpose(jvp(block0))/jvp(block0)/checkpoint/"


class FakeRun:
    """What a reader takes of a traced run: one chip, a traced stretch of
    100..400 ns, the train program starting at 100, 200 and 300 (two whole
    periods between the first and the last start, whatever the host
    counted)."""

    steps_per_call, global_batch = 1, 1
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def __init__(self, cell, scoped=True):
        self.cell = cell
        self.trace_window = (100, 400)
        self.traced = harness.Stretch(seconds=3e-7, steps=3)
        ops = [("%p.1 = bf16[] fusion(...)", 100, 120),    # kda projections
               ("%c.1 = f32[] fusion(...)", 120, 135),     # kda core fwd
               ("%o.1 = bf16[] fusion(...)", 135, 140),    # kda out
               ("%c.2 = f32[] while(...)", 150, 170),      # kda core bwd
               ("%m.1 = bf16[] fusion(...)", 170, 180),    # mla projection
               ("%s.1 = bf16[] custom-call(...)", 180, 190),  # mla core
               ("%f.1 = bf16[] fusion(...)", 190, 195),    # moe
               ("%c.1 = f32[] fusion(...)", 220, 230),
               ("%s.1 = bf16[] custom-call(...)", 290, 310)]  # over the end
        self.tables = trace.TraceTables([trace.DeviceTables(
            0, modules=[("jit_per_worker(1)", 100, 195),
                        ("jit_per_worker(1)", 200, 295),
                        ("jit_per_worker(1)", 300, 395)], ops=ops)])
        self.scopes = {
            "p.1": FWD + "kda/dot_general",
            "c.1": FWD + "kda/kda_core/while",
            "o.1": FWD + "kda/dot_general",
            "c.2": BACK + "kda/transpose(jvp(kda_core))/while",
            "m.1": FWD.replace("block0", "block3") + "mla/dot_general",
            "s.1": FWD.replace("block0", "block3")
            + "mla/attn_core/pallas_call",
            "f.1": FWD + "moe/dot_general"} if scoped else {}


def read(manifest, name, run):
    return harness.load_module(manifest, "layer_metrics", name).read(run)


def test_the_readers_take_their_scopes_time_per_whole_period(manifest):
    run = FakeRun(harness.load_cell(manifest, CELL))
    # kda: 100..140, 150..170, 220..230 of the sub-window 100..300, over
    # its two periods (the host's three steps do not count)
    assert read(manifest, "kda_ms", run) == pytest.approx(
        (40 + 20 + 10) / 2 / 1e6)
    # mla: 170..190 and 290..300
    assert read(manifest, "mla_ms", run) == pytest.approx(
        (20 + 10) / 2 / 1e6)
    core_s = (15 + 20 + 10) / 2 / 1e9
    config = run.cell.config
    least = max(flops.kda_core_train_flops_per_sample(config) / 197e12,
                flops.kda_core_train_bytes_per_sample(config) / 819e9)
    assert read(manifest, "kda_core_roofline_share", run) \
        == pytest.approx(100 * least / core_s)


@pytest.mark.parametrize("name", [r["name"] for r in READERS])
def test_a_reader_that_finds_nothing_returns_nothing(manifest, name):
    cell = harness.load_cell(manifest, CELL)
    assert read(manifest, name, FakeRun(cell, scoped=False)) is None
    untraced = FakeRun(cell)
    untraced.tables = untraced.trace_window = untraced.traced = None
    assert read(manifest, name, untraced) is None
    once = FakeRun(cell)            # one execution: no whole period
    once.tables.devices[0].modules[1:] = []
    assert read(manifest, name, once) is None
    # a program without the scope (the parent, another model): nothing
    other = FakeRun(cell)
    other.scopes = {k: "jit(per_worker)/jvp(block0)/attn/dot_general"
                    for k in other.scopes}
    assert read(manifest, name, other) is None
    # a configuration whose counter has no delta rule: no share
    if name == "kda_core_roofline_share":
        lfm2 = FakeRun(harness.load_cell(manifest,
                                         "lfm2-24b-a2b-t8192-b1-bsp-1chip"))
        assert read(manifest, name, lfm2) is None
