"""The looped language model's cell: its manifest entries, configuration,
FLOP counter, reference and traffic, a toy-size run of the model through
the harness on the CPU mesh, and the three readers whose manifest entries
wait for a benchmark PR (``test_benchmarks_spans.py`` pins PR 25's nine
metrics as the tail of ``per_layer``: PERF.md section 7)."""

import copy
import json
import math
import os

import numpy as np
import pytest
from test_benchmarks_manifest import check_config

from benchmarks import harness, scopes, trace
from benchmarks.flops import looped_lm

CONFIG = "ouro-2.6b"
CELL = "ouro-2.6b-t4096-b2-bsp-1chip"
TOY_CELL = "toy-ouro-b2-bsp-1chip"
TOY_DIR = "tests/benchmarks/toy"
# the source's config.json as the catalog holds it, but for layer_types
# (48 times "full_attention")
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro",
    "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152}


# The three readers' entries, as the PR that can append to ``per_layer``
# will add them (PERF.md section 7 (l)); the files they name are here.
READERS = [
    {"name": "loop_stack_ms", "unit": "ms/step", "better": "lower",
     "source": "device_trace", "layer": "looped stack",
     "moves": "train_throughput", "workloads": [CELL]},
    {"name": "exit_head_ms", "unit": "ms/step", "better": "lower",
     "source": "device_trace", "layer": "exit head",
     "moves": "train_throughput", "workloads": [CELL]},
    {"name": "attention_roofline_share", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "looped stack", "moves": "mfu",
     "workloads": [CELL]}]


@pytest.fixture(scope="module")
def landed(manifest):
    """The real manifest with the readers' entries appended."""
    m = copy.deepcopy(manifest)
    m["per_layer"] += READERS
    return m


@pytest.fixture(scope="module")
def entry(landed):
    return next(c for c in landed["configs"] if c["name"] == CONFIG)


@pytest.fixture(scope="module")
def config(entry):
    return harness.load_json(os.path.join(harness.ROOT, entry["file"]))


@pytest.fixture(scope="module")
def toy_ouro_manifest(toy_manifest):
    """The toy manifest with the looped model at the tests' size, added as
    files and entries alone."""
    m = copy.deepcopy(toy_manifest)
    m["configs"].append({
        "name": "toy_ouro",
        "source": "theanompi_tpu/models/looped_lm.py LoopedLM at a "
                  "rehearsal size (the looped model of benchmarks/configs/"
                  "ouro-2.6b.json, not a published size)",
        "file": TOY_DIR + "/configs/toy_ouro.json", "reduced": [],
        "why": "CPU rehearsal of the looped model's cell"})
    m["workloads"].append({
        "name": TOY_CELL, "config": "toy_ouro", "traffic": "toy-ouro-b2-bsp",
        "chips": 1, "why": "rehearsal: three loop steps over two layers"})
    m["per_layer"] += [dict(r, workloads=[TOY_CELL]) for r in READERS]
    return m


# -- the manifest ------------------------------------------------------------------

def test_the_manifest_has_the_configuration_and_its_cell(manifest):
    """Appended behind what the benchmark had; nothing here pins what a
    later PR appends behind them."""
    configs = [c["name"] for c in manifest["configs"]]
    cells = [w["name"] for w in manifest["workloads"]]
    assert configs.index(CONFIG) >= 1 and configs[0] == "vgg16"
    assert cells.index(CELL) >= 2 and cells[:2] == [
        "vgg16-b384-bsp-1chip", "vgg16-b384-bsp-4chip"]
    cell = manifest["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "tokens-t4096-b2-bsp", 1)


def test_the_cell_reads_the_metrics_that_name_no_cell(manifest, landed):
    """The sixteen unlisted metrics and neither of the exchange's; with the
    readers' entries appended, those three too."""
    cell = harness.load_cell(manifest, CELL)
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in manifest["per_layer"]
        if CELL in m.get("workloads", [CELL])]
    assert len(cell.per_layer) >= 16
    assert not any(m["name"].startswith("exchange_")
                   for m in cell.per_layer)
    assert {"train_throughput", "mfu", "peak_hbm", "setup_s"} <= {
        m["name"] for m in cell.end_to_end}
    assert [m["name"] for m in harness.load_cell(landed, CELL).per_layer] \
        == [m["name"] for m in cell.per_layer] + [r["name"] for r in READERS]


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r["name"])
def test_a_readers_entry_is_ready_to_append(landed, reader):
    """Each waiting entry has the keys the manifest's have, a layer PERF.md
    lists, an end-to-end metric the cell reports, and a file to run."""
    assert set(reader) == set(landed["per_layer"][6])    # one with a list
    assert reader["moves"] in {m["name"] for m in landed["end_to_end"]}
    with open(os.path.join(harness.ROOT, "PERF.md")) as f:
        assert f"| {reader['layer']} |" in f.read()
    assert callable(harness.load_module(landed, "layer_metrics",
                                        reader["name"]).read)


# -- the configuration ---------------------------------------------------------------

def test_the_configuration_is_the_sources_but_for_its_cut(entry, config):
    check_config(entry, config)
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["layer_types"] == ["full_attention"] * 48
    # depth alone is cut: the first of twelve stages of four layers
    assert config["num_hidden_layers"] == 4
    assert "twelve" in config["deployment"]
    for key in ("deployment", "assumed", "modelfile", "modelclass",
                "n_class", "worker_config", "flops", "reference"):
        assert key in config, key
    assert config["check"]["optimizer"]["name"] == "adam"
    assert "first_cost_tol" not in config       # the harness's 0.25 holds
    assert config["sample_unit"] == "sequence"


def test_the_programs_keys_say_what_the_published_ones_say(config, landed):
    wc = config["worker_config"]
    for ours, theirs in (("vocab", "vocab_size"), ("d_model", "hidden_size"),
                         ("n_head", "num_attention_heads"),
                         ("n_layer", "num_hidden_layers"),
                         ("d_ff", "intermediate_size"),
                         ("loop_steps", "total_ut_steps"),
                         ("rope_theta", "rope_theta"),
                         ("norm_eps", "rms_norm_eps"),
                         ("seq_len", "seq_len")):
        assert wc[ours] == config[theirs], (ours, theirs)
    assert wc["d_model"] // wc["n_head"] == config["head_dim"]
    assert config["n_class"] == config["vocab_size"]
    assert wc["learning_rate"] \
        == config["check"]["optimizer"]["learning_rate"]
    cell = harness.load_cell(landed, CELL)
    traffic = cell.traffic["worker_config"]
    assert traffic["seq_len"] == config["seq_len"] == 4096
    assert traffic["batch_size"] == 2 and traffic["steps_per_call"] == 1
    assert traffic["para_load"] and traffic["para_load_workers"] == 2
    assert traffic["synthetic_train"] // traffic["batch_size"] >= 2048
    assert cell.chips == 1


def test_the_model_file_builds_the_shapes_the_counter_counts(config):
    """``n_params`` of the FLOP file against the model's own tree, leaf
    shapes only (nothing of this size is drawn here)."""
    import jax

    from theanompi_tpu.models.looped_lm import LoopedLM

    class Shapes(LoopedLM):
        def init_params(self, key):
            return jax.eval_shape(super().init_params, key)

    model = Shapes(dict(config["worker_config"], n_workers=1,
                        batch_size=2, synthetic_train=4, seq_len=8))
    count = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(
        model.params))
    assert count == looped_lm.n_params(config) == config["n_params"]


# -- the FLOP counter against the hand count -------------------------------------------

def at_depth(config, layers, vocab=49152):
    return dict(config, num_hidden_layers=layers, vocab_size=vocab)


def test_the_counter_gives_the_hand_count_at_eight_layers(config):
    """ISSUE 34's arithmetic: 2,315M multiply-accumulates and 13.89 GFLOP a
    token trained, 56.9 TFLOP a sequence, 612.4M parameters."""
    c = at_depth(config, 8)
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4096 * 2048
    assert looped_lm.layer_macs_per_token(c) == layer == 59_768_832
    assert looped_lm.forward_macs_per_token(c) \
        == 4 * (8 * layer + 2048 * 49152) == 2_315_255_808
    per_token = looped_lm.train_flops_per_sample(c) / 4096
    assert per_token == 6 * 2_315_255_808
    assert per_token / 1e9 == pytest.approx(13.89, abs=0.005)
    assert looped_lm.train_flops_per_sample(c) / 1e12 \
        == pytest.approx(56.9, abs=0.05)
    assert looped_lm.n_params(c) == 612_438_017
    assert looped_lm.n_params(c) / 1e6 == pytest.approx(612.5, rel=1e-3)
    assert looped_lm.n_params(at_depth(config, 6)) / 1e6 \
        == pytest.approx(509.7, abs=0.05)


def test_the_heads_and_the_attention_core_are_their_shares(config):
    c = at_depth(config, 8)
    whole = looped_lm.train_flops_per_sample(c)
    heads = 6 * 4 * 2048 * 49152 * 4096
    assert 0.17 < heads / whole < 0.18          # "a fifth of the step"
    core = looped_lm.attn_core_train_flops_per_sample(c)
    assert core == 6 * 4 * 8 * 4096 * 2048 * 4096
    assert 0.11 < core / whole < 0.12
    # the attention core scales with the depth held and nothing else
    held = config["num_hidden_layers"]
    assert looped_lm.attn_core_train_flops_per_sample(config) * 8 \
        == core * held


def test_the_file_is_at_the_size_the_check_takes(config):
    """Four layers and the whole vocabulary: 406.9M parameters, 6.5 GB of
    float32 weights, gradient and Adam's two moments on a chip of 16.9."""
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 49152)
    assert looped_lm.n_params(config) == config["n_params"] == 406_884_353
    assert 16 * config["n_params"] / 1e9 == pytest.approx(6.51, abs=0.005)
    assert looped_lm.forward_macs_per_token(config) == 1_358_954_496
    assert looped_lm.train_flops_per_sample(config) * 2 / 1e12 \
        == pytest.approx(66.8, abs=0.05)        # a step of two sequences
    heads = 6 * 4 * 2048 * 49152 * 4096
    assert heads / looped_lm.train_flops_per_sample(config) \
        == pytest.approx(0.296, abs=0.001)


def test_the_harness_reads_the_counter_per_sequence(landed, config):
    cell = harness.load_cell(landed, CELL)
    mod = harness.load_module(landed, "flops", cell.config["flops"])
    assert mod.train_flops_per_sample(cell.config) \
        == looped_lm.train_flops_per_sample(config)


# -- the reference -----------------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    for path in ("benchmarks/reference/ouro.py",
                 TOY_DIR + "/reference/toy_ouro.py"):
        with open(os.path.join(harness.ROOT, path)) as f:
            text = f.read()
        assert "theanompi_tpu" not in text.replace(
            "theanompi_tpu/models", ""), path


def test_the_reference_protocol_is_whole(landed, config):
    ref = harness.load_module(landed, "reference", config["reference"])
    assert all(callable(getattr(ref, f))
               for f in ("forward", "batch", "train_loss"))
    small = dict(config, worker_config=dict(config["worker_config"],
                                            seq_len=8))
    x, y = ref.batch(small, np.random.RandomState(2147489000))
    assert x.dtype == y.dtype == np.int32 and x.shape == y.shape == (2, 8)
    assert (x[:, 1:] == y[:, :-1]).all()
    assert 0 <= x.min() and x.max() < config["vocab_size"]
    assert ref.PUBLISHED == {"n_head": 16, "loops": 4, "theta": 1e6,
                             "eps": 1e-6}


# -- the toy cell through the harness -------------------------------------------------------

@pytest.fixture(scope="module")
def toy_run(toy_ouro_manifest):
    return harness.run_cell(toy_ouro_manifest, TOY_CELL, seed=3,
                            seconds=1.5, trace=False)


def failing(run):
    return {k for k, (value, limit) in run.compared.items()
            if not value <= limit}


def test_the_toy_cell_runs_and_is_correct(toy_run):
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
    # the objective, not the evaluation loss: the entropy term is in it
    assert ref["ref_losses"][0] < math.log(128) - 0.05
    assert run.flops_per_sample == 6 * 16 * 3 * (
        2 * (4 * 64 * 64 + 3 * 64 * 96 + 16 * 64) + 64 * 128)


@pytest.mark.parametrize("seed", [41, 2147489999])
def test_sound_toy_runs_of_other_seeds_are_correct(toy_ouro_manifest, seed):
    run = harness.run_cell(toy_ouro_manifest, TOY_CELL, seed=seed,
                           seconds=0.5, trace=False)
    assert run.correct and failing(run) == set(), run.problems


@pytest.mark.parametrize("dtype,passes", [("float32", True),
                                          ("float8_e4m3fn", False)])
def test_the_limits_separate_the_8_bit_control(toy_ouro_manifest, toy_run,
                                               dtype, passes):
    run = harness.run_cell(toy_ouro_manifest, TOY_CELL, seed=3, seconds=0.5,
                           trace=False, control={"compute_dtype": dtype})
    assert toy_run.correct and run.correct is passes, run.problems
    if passes:                  # float32: the reference is the model
        assert run.reference["grad_norm_gap"] < 1e-4
        assert run.reference["change_norm_gap"] < 1e-4
        assert run.reference["grad_rel_err"] < 1e-4
    else:
        assert failing(run) & {"grad_norm_gap", "change_norm_gap"}


def test_the_toy_cell_is_added_by_files_alone(toy_manifest,
                                              toy_ouro_manifest):
    for key, had in toy_manifest.items():
        if isinstance(had, list):
            assert toy_ouro_manifest[key][:len(had)] == had, key
    cell = harness.load_cell(toy_ouro_manifest, TOY_CELL)
    check_config(toy_ouro_manifest["configs"][-1], cell.config)
    with open(os.path.join(harness.ROOT, TOY_DIR,
                           "configs/toy_ouro.json")) as f:
        assert looped_lm.n_params(json.load(f)) == cell.config["n_params"]


# -- the three readers ------------------------------------------------------------------------

LOOP = "jit(per_worker)/jvp(ut_loop)/while/body/block0/checkpoint/"
BACK = "jit(per_worker)/transpose(jvp(ut_loop))/while/body/block0/" \
       "checkpoint/rematted_computation/"


class FakeRun:
    """What a reader takes of a traced run: one chip, a stretch of 300 ns
    in which the host counted two steps."""

    steps_per_call, global_batch = 1, 2
    peaks = {"bf16_flops_per_s": 197e12}

    def __init__(self, cell, scoped=True):
        self.cell = cell
        self.trace_window = (100, 400)
        self.traced = harness.Stretch(seconds=3e-7, steps=2)
        ops = [("%f.1 = f32[] fusion(...)", 100, 130),     # attn_core fwd
               ("%f.2 = f32[] fusion(...)", 120, 160),     # mlp, overlaps
               ("%f.3 = f32[] fusion(...)", 160, 180),     # attn_core bwd
               ("%h.1 = f32[] fusion(...)", 180, 195),     # exit head
               ("%u.1 = f32[] fusion(...)", 195, 200),     # update
               ("%f.1 = f32[] fusion(...)", 300, 330),
               # after the train program's own event has ended, and
               # running over the end of the stretch
               ("%h.1 = f32[] fusion(...)", 380, 415)]
        self.tables = trace.TraceTables([trace.DeviceTables(
            0, modules=[("jit_per_worker(1)", 100, 200),
                        ("jit_per_worker(1)", 300, 370)], ops=ops)])
        self.scopes = {
            "f.1": LOOP + "attn/attn_core/pallas_call",
            "f.2": LOOP + "mlp/dot_general",
            "f.3": BACK + "attn/attn_core/pallas_call",
            "h.1": "jit(per_worker)/jvp(exit_head)/while/body/dot_general",
            "u.1": "jit(per_worker)/mul"} if scoped else {}


def read(landed, name, run):
    return harness.load_module(landed, "layer_metrics", name).read(run)


def test_the_readers_take_their_scopes_time_per_step(landed):
    run = FakeRun(harness.load_cell(landed, CELL))
    # ut_loop: 100..180 and 300..330; the head's second run is clipped to
    # the stretch and counted though the program's event has ended
    assert read(landed, "loop_stack_ms", run) \
        == pytest.approx((80 + 30) / 2 / 1e6)
    assert read(landed, "exit_head_ms", run) \
        == pytest.approx((15 + 20) / 2 / 1e6)
    assert scopes.scope_ms_per_step(run, "attn_core") \
        == pytest.approx((30 + 20 + 30) / 2 / 1e6)
    share = read(landed, "attention_roofline_share", run)
    flops = looped_lm.attn_core_train_flops_per_sample(run.cell.config) * 2
    assert share == pytest.approx(100 * flops / 197e12 / (40e-9))


@pytest.mark.parametrize("name", ["loop_stack_ms", "exit_head_ms",
                                  "attention_roofline_share"])
def test_a_reader_that_finds_nothing_returns_nothing(landed, name):
    cell = harness.load_cell(landed, CELL)
    unjoined = FakeRun(cell, scoped=False)
    assert read(landed, name, unjoined) is None
    untraced = FakeRun(cell)
    untraced.tables = untraced.trace_window = untraced.traced = None
    assert read(landed, name, untraced) is None
    # a program without the scope (a parent, another model): nothing
    other = FakeRun(cell)
    other.scopes = {k: "jit(per_worker)/jvp()/conv_general_dilated"
                    for k in other.scopes}
    assert read(landed, name, other) is None
