"""A token model cut to a chip's share, added as files: the toy token cell
end to end on the CPU mesh, its training-objective check, and what fails it.

``conftest.toy_lm_manifest`` appends the configuration (``reduced`` not
empty), the reference (its own ``batch``, a ``train_loss``), the FLOPs, the
traffic mix and the cell to the toy manifest.  Nothing under ``benchmarks/``
knows the model: what a later ``model_config`` PR does."""

import math
import os

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import check, plain_ops

CELL = "toy-lm-b4-bsp-1chip"
CELL4 = "toy-lm-b4-bsp-4chip"
TOY_DIR = "tests/benchmarks/toy"


FAULTS = "bench_toy_lm_faults"
TRAIN_NUMBERS = ("grad_norm_gap", "change_norm_gap")


def run_lm(manifest, seed, cell=CELL, **control):
    return harness.run_cell(manifest, cell, seed=seed, seconds=0.5,
                            trace=False, control=control)


@pytest.fixture(scope="module")
def lm_run(toy_lm_manifest):
    return harness.run_cell(toy_lm_manifest, CELL, seed=3, seconds=2.0,
                            trace=False)


@pytest.fixture(scope="module")
def lm(toy_lm_manifest):
    """The cell's files and a way to build its model apart from a run."""
    from theanompi_tpu.worker import WORKERS

    cell = harness.load_cell(toy_lm_manifest, CELL)
    ref_mod = harness.load_module(toy_lm_manifest, "reference", "toy_lm")

    def build(seed, **over):
        config = dict(cell.config["worker_config"])
        config.update(cell.traffic["worker_config"])
        config.update(n_workers=1, seed=seed, para_load=False,
                      synthetic_train=64, **over)
        return WORKERS["bsp"](config).build_model(cell.config["modelfile"],
                                                  cell.config["modelclass"])

    return cell, ref_mod, build


def failing(run):
    """The numbers of the run that are over their limit."""
    return {k for k, (value, limit) in run.compared.items()
            if not value <= limit}


# -- the cell ------------------------------------------------------------------

def test_the_token_cell_runs_and_is_correct(lm_run):
    run = lm_run
    assert run.problems == [] and run.correct and run.failed == 0
    assert run.attempted == run.window.steps > 10
    assert run.compiles_in_window == 0
    cfg = run.cell.config
    assert cfg["reduced"] and cfg["sample_unit"] == "sequence"
    assert cfg["n_class"] == cfg["vocab"] == 64
    # the cost is the cross-entropy plus moe_aux times an auxiliary loss
    # that is 1 at uniform routing
    assert abs(run.first_cost - math.log(64)) < harness.FIRST_COST_TOL
    assert run.global_batch == 4
    assert run.flops_per_sample == 2 * 3 * 16 * (
        2 * (4 * 32 * 32 + 16 * 32 + 32 * 4 + 2 * 2 * 32 * 128) + 32 * 64)


# seeds the limits were not read from; 7 and 2147483711 are the two of 32
# on which top-k routing sent tokens to another expert under bf16 rounding
@pytest.mark.parametrize("seed", [7, 41, 2147483711, 2147489999])
def test_sound_runs_of_other_seeds_are_correct(toy_lm_manifest, seed):
    run = run_lm(toy_lm_manifest, seed)
    assert run.correct and failing(run) == set(), run.problems


def test_the_timed_paths_first_steps_are_held_to_the_reference(lm_run):
    """Not a program of the check's own: the three costs are the first three
    the window's own dispatch returned, and the comparison was made after
    the window, outside set-up."""
    ref = lm_run.reference
    assert ref["ok"] and ref["steps"] == check.TRAIN_STEPS == 3
    assert ref["sys_losses"][0] == lm_run.first_cost
    assert len(ref["ref_losses"]) == 3
    for name, tol in (("grad_norm_gap", check.GRAD_NORM_TOL),
                      ("change_norm_gap", check.CHANGE_NORM_TOL)):
        assert 0 < ref[name] <= tol
        assert lm_run.compared[name] == [ref[name], tol]
    # the costs are reported beside them, over the way the reference's
    # loss went; no limit holds them (check.py says why)
    assert 0 < ref["step_loss_err"] < 0.1
    assert "step_loss_err" not in lm_run.compared
    # the objective is not the evaluation loss: the auxiliary term is in it
    assert ref["ref_losses"][0] - math.log(64) == pytest.approx(0.01,
                                                                abs=0.05)
    assert ref["leaves_nought"] == []
    # the sharper number of the named leaves is reported and not compared
    assert ref["grad_rel_leaf"] in ("embed/w", "block0/attn/wq",
                                    "block0/moe/wg", "block0/moe/w1",
                                    "block1/moe/w2")
    assert 0 < ref["grad_rel_err"] and "grad_rel_err" not in lm_run.compared
    assert "logit_rel_err" not in lm_run.compared
    # outside set-up: no forward check in it, the comparison timed apart
    assert lm_run.setup_phases["reference_check"] < 0.05
    assert lm_run.after_window_s["train_check"] > 0
    assert lm_run.setup_s < sum(lm_run.setup_phases.values()) + 0.5


def test_the_references_own_batch_is_token_ids(lm):
    cell, ref_mod, _ = lm
    x, y = ref_mod.batch(cell.config, np.random.RandomState(3))
    assert x.dtype == y.dtype == np.int32 and x.shape == y.shape == (4, 16)
    assert (x[:, 1:] == y[:, :-1]).all()        # next-token targets
    assert 0 <= x.min() and x.max() < cell.config["vocab"]


def test_a_token_reference_without_an_objective_gets_the_forward_check(lm):
    """``batch`` alone: the evaluation-mode logits on the reference's own
    int32 batch, in set-up, as an image model's on its crops."""
    import types
    cell, ref_mod, build = lm
    plain = types.SimpleNamespace(forward=ref_mod.forward,
                                  batch=ref_mod.batch)
    got = harness.reference_check(plain, cell.config, build(3), 3)
    assert got["ok"] and 0 < got["logit_rel_err"] < check.LOGIT_REL_TOL
    assert list(got) == ["ok", "logit_rel_err", "logit_scale", "loss_err",
                         "loss_tol", "ref_loss", "sys_loss"]
    exact = harness.reference_check(
        plain, cell.config, build(3, compute_dtype="float32"), 3)
    assert exact["logit_rel_err"] < 1e-5


# -- what fails it ---------------------------------------------------------------

@pytest.mark.parametrize("fault,fails,passes", [
    # the optimizer gets one expert weight's gradient half too long: the
    # costs and, under Adam, the length of the change do not see it
    ("ScaledGradient", {"grad_norm_gap"}, {"change_norm_gap"}),
    ("HalfBatch", {"grad_norm_gap"}, set()),
    ("StateUnchanged", set(TRAIN_NUMBERS), set()),
])
def test_a_fault_in_the_timed_path_fails_the_run(toy_lm_manifest, fault,
                                                 fails, passes):
    run = run_lm(toy_lm_manifest, 3, modelfile=FAULTS, modelclass=fault)
    assert not run.correct
    assert fails <= failing(run) and not passes & failing(run)
    assert run.failed == 0 and run.compared["costs_not_finite"] == [0, 0]
    if fault == "ScaledGradient":
        assert run.reference["grad_norm_leaf"] == "block0/moe/w1"
        assert 0.45 < run.reference["grad_norm_gap"] < 0.55
    if fault == "StateUnchanged":
        assert run.reference["grad_norm_gap"] == 1.0
        assert run.reference["change_norm_gap"] == 1.0


@pytest.mark.parametrize("dtype,passes", [("float32", True),
                                          ("float8_e4m3fn", False)])
def test_the_limits_separate_bf16_from_an_8_bit_float(toy_lm_manifest,
                                                      lm_run, dtype, passes):
    """The program's own ``compute_dtype`` switch is the control: every
    contraction's operands in the named format.  bfloat16, the cell as it
    is, passes (``lm_run``)."""
    run = run_lm(toy_lm_manifest, 3, compute_dtype=dtype)
    assert lm_run.correct and run.correct is passes, run.problems
    if passes:                      # float32: the reference is the model
        assert all(run.reference[k] < 1e-4 for k in TRAIN_NUMBERS)
        assert run.reference["grad_rel_err"] < 1e-4
        assert run.reference["step_loss_err"] < 1e-3
    else:                           # its small gradients underflow
        assert {"grad_norm_gap", "change_norm_gap"} <= failing(run)


def test_the_mesh_cell_follows_each_chips_rows(toy_lm_manifest):
    """Four chips: the reference takes each chip's rows apart and the mean
    over chips, at the learning rate the harness scaled."""
    run = run_lm(toy_lm_manifest, 3, cell=CELL4)
    assert run.correct and failing(run) == set(), run.problems
    assert run.global_batch == 16


def test_an_exchange_left_out_fails_the_mesh_cell(toy_lm_manifest):
    run = run_lm(toy_lm_manifest, 3, cell=CELL4, exch_strategy="none")
    assert not run.correct
    assert {"grad_norm_gap", "layout_faults"} <= failing(run)


# -- the pieces --------------------------------------------------------------------

def quadratic(params, x, y):
    """A training objective with a gradient one can write down: the mean
    over rows of (w . x - y)^2 / 2, plus b, whose gradient is 1."""
    import jax.numpy as jnp
    return jnp.mean((x @ params["w"] - y) ** 2) / 2 + jnp.sum(params["b"])


ADAM = {"name": "adam", "learning_rate": 0.01}


def test_the_reference_follows_adam_from_the_first_gradient():
    r = np.random.RandomState(0)
    params = {"w": r.randn(5).astype(np.float32),
              "b": np.zeros(2, np.float32)}
    batches = [(r.randn(8, 5).astype(np.float32),
                r.randn(8).astype(np.float32)) for _ in range(3)]
    got = check.follow_steps(quadratic, params, batches, 1, 0.01, ADAM)
    x, y = batches[0]
    grad = x.T @ (x @ params["w"] - y) / 8
    assert got["first_grad"]["w"] == pytest.approx(grad, rel=1e-5)
    assert got["first_grad"]["b"] == pytest.approx(np.ones(2))
    assert got["losses"][0] == pytest.approx(
        np.mean((x @ params["w"] - y) ** 2) / 2, rel=1e-5)
    # Adam's first step is the learning rate along the gradient's sign; a
    # constant gradient keeps it there
    assert got["params"]["b"] == pytest.approx(-0.03 * np.ones(2), rel=1e-4)
    assert len(got["losses"]) == 3 and set(got["params"]) == {"w", "b"}
    # two chips: the mean of each half's objective is the whole's here
    halves = check.follow_steps(quadratic, params, batches, 2, 0.01, ADAM)
    assert halves["losses"] == pytest.approx(got["losses"], rel=1e-5)


def test_the_first_gradient_is_read_from_adams_first_moment():
    from benchmarks.reference import plain_opt
    m = {"w": np.full(3, 0.05), "b": np.zeros(2)}
    got = plain_opt.first_gradient(m, 0.9)
    assert got["w"] == pytest.approx(np.full(3, 0.5)) and not got["b"].any()
    assert plain_opt.hyper(ADAM) == {"b1": 0.9, "b2": 0.999, "eps": 1e-8}
    with pytest.raises(ValueError, match="Adam only"):
        plain_opt.hyper({"name": "momentum"})


def steps_of(grad, params, losses=(1.0, 0.9, 0.8)):
    return {"losses": list(losses), "first_grad": grad, "params": params}


def test_compare_steps_takes_the_gap_of_norms_by_the_worst_leaf():
    p0 = {"a": np.zeros(4), "b": np.zeros(4), "c": np.zeros(4)}
    ref_g = {"a": np.ones(4), "b": np.ones(4) * 2, "c": np.ones(4) * 1e-6}
    ref_p = {"a": -np.ones(4), "b": -np.ones(4), "c": -np.ones(4)}
    ref = steps_of(ref_g, ref_p)
    turned = {"a": np.array([1., 1., 1., -1.]), "b": np.ones(4) * 2.04,
              "c": np.zeros(4)}
    got = check.compare_steps(ref, steps_of(turned, dict(ref_p, c=p0["c"])),
                              p0, ["a"])
    # a gradient turned and not stretched has no gap; its difference does
    assert got["ok"] and got["grad_norm_leaf"] == "b"
    assert got["grad_norm_gap"] == pytest.approx(0.02)
    assert got["grad_rel_err"] == pytest.approx(1.0)
    assert got["grad_rel_leaf"] == "a"
    # c's gradient is nought to rounding: its change is left out, by the
    # rule and not by name; its gradient's gap is held against the median
    assert got["leaves_nought"] == ["c"] and got["change_norm_gap"] == 0
    far = check.compare_steps(
        ref, steps_of(dict(ref_g, a=np.ones(4) * 1.3), ref_p), p0)
    assert not far["ok"] and far["grad_norm_leaf"] == "a"
    assert far["grad_norm_gap"] == pytest.approx(0.3)
    assert far["grad_rel_err"] is None          # no leaf was named
    still = check.compare_steps(ref, steps_of(ref_g, dict(ref_p, b=p0["b"])),
                                p0)
    assert not still["ok"] and still["change_norm_leaf"] == "b"
    assert still["change_norm_gap"] == 1.0
    twice = check.compare_steps(
        ref, steps_of(ref_g, dict(ref_p, a=2 * ref_p["a"])), p0)
    assert not twice["ok"] and twice["change_norm_gap"] == 1.0
    late = check.compare_steps(ref, steps_of(ref_g, ref_p, (1.0, 0.9, 0.9)),
                               p0)
    # the third cost 0.1 off where the reference's loss went 0.2 in all:
    # reported, and no limit holds it
    assert late["ok"] and late["step_loss_err"] == pytest.approx(0.5)
    flat = steps_of(ref_g, ref_p, (1.0, 1.0, 1.0))     # a loss that stays:
    held = check.compare_steps(flat, steps_of(ref_g, ref_p, (1.0, 1.0, 1.001)),
                               p0)                     # the floor counts
    assert held["step_loss_err"] == pytest.approx(1.0, rel=1e-3)


@pytest.mark.parametrize("what", ["loss", "gradient", "parameter"])
def test_a_reading_that_is_no_number_fails_compare_steps(what):
    p0 = {"a": np.zeros(4), "b": np.zeros(4)}
    g = {"a": np.ones(4), "b": np.ones(4)}
    p = {"a": -np.ones(4), "b": -np.ones(4)}
    nan = np.full(4, np.nan)
    got = {"loss": steps_of(g, p, (1.0, float("nan"), 0.8)),
           "gradient": steps_of(dict(g, a=nan), p),
           "parameter": steps_of(g, dict(p, b=nan))}[what]
    out = check.compare_steps(steps_of(g, p), got, p0)
    assert not out["ok"]
    assert check.compare_steps(steps_of(g, p), steps_of(g, p), p0)["ok"]


def test_named_matches_whole_path_components():
    leaves = check.by_path({"block1": {"moe": {"w1": 1, "w10": 2}},
                            "block10": {"b": 3}, "embed": {"w": 4}})
    assert set(leaves) == {"block1/moe/w1", "block1/moe/w10", "block10/b",
                           "embed/w"}
    assert set(check.named(leaves, ["block1"])) == {"block1/moe/w1",
                                                    "block1/moe/w10"}
    assert set(check.named(leaves, ["block1/moe/w1", "embed"])) == {
        "block1/moe/w1", "embed/w"}
    assert check.named(leaves, ["block1/moe/w"]) == {}


class FakeLoader:
    def next_train_batch(self, count):
        return {"x": np.full((2, 3), count), "y": np.full((2, 3), -count)}


class FakeModel:
    def __init__(self, opt_state):
        self.params = {"w": np.zeros(2)}
        self.data = FakeLoader()
        self.step_state = {"params": {"w": np.ones((1, 2))},
                           "opt_state": opt_state}


def test_first_steps_taps_the_loader_for_three_steps_and_lets_go():
    model = FakeModel({"m": {"w": np.full((1, 2), 0.1)}})
    first = harness.FirstSteps(model, 1)
    for step in (1, 2, 3):
        model.data.next_train_batch(step)
        first.after(step)
    assert "next_train_batch" not in vars(model.data)       # the tap is gone
    model.data.next_train_batch(4)
    assert [int(x[0, 0]) for x, _ in first.batches] == [1, 2, 3]
    assert first.first_moment["w"] == pytest.approx([0.1, 0.1])
    assert first.params["w"].tolist() == [1.0, 1.0]
    assert first.params0 is model.params


@pytest.mark.parametrize("why", ["steps_per_call", "no_first_moment",
                                 "no_optimizer_stated"])
def test_what_the_training_comparison_cannot_follow_is_refused(lm, why):
    cell, ref_mod, _ = lm
    if why == "steps_per_call":
        with pytest.raises(harness.Refused, match="single steps"):
            harness.FirstSteps(FakeModel({}), 4)
    elif why == "no_first_moment":      # momentum SGD keeps a velocity tree
        first = harness.FirstSteps(FakeModel({"w": np.zeros((1, 2))}), 1)
        with pytest.raises(harness.Refused, match="first moment"):
            first.after(1)
    else:
        import dataclasses
        bare = dataclasses.replace(cell, config=dict(
            cell.config, check={"grad_leaves": ["embed"]}))
        with pytest.raises(harness.Refused, match="check.optimizer"):
            harness.train_check(ref_mod, bare, None, [], {})


def test_softmax_loss_of_any_rank_is_the_flattened_one():
    import jax.numpy as jnp
    r = np.random.RandomState(0)
    logits = jnp.asarray(r.randn(3, 5, 7).astype(np.float32))
    labels = jnp.asarray(r.randint(0, 7, (3, 5)).astype(np.int32))
    flat = plain_ops.softmax_loss(logits.reshape(15, 7), labels.reshape(15))
    assert float(plain_ops.softmax_loss(logits, labels)) == float(flat)
    four = plain_ops.softmax_loss(logits.reshape(3, 5, 1, 7),
                                  labels.reshape(3, 5, 1))
    assert float(four) == float(flat)
    # the 2-D result is the parent's, to the bit
    logz = jnp.log(jnp.sum(jnp.exp(
        logits[0] - logits[0].max(-1, keepdims=True)), axis=-1)) \
        + logits[0].max(-1)
    parent = jnp.mean(logz - logits[0][jnp.arange(5), labels[0]])
    assert float(plain_ops.softmax_loss(logits[0], labels[0])) \
        == float(parent)


def test_readings_prints_a_cells_numbers_seed_by_seed(toy_lm_manifest,
                                                      tmp_path, capsys):
    """``benchmarks/readings.py``: how the limits were read, and how the PR
    that brings a cell reads its own; here the control on two seeds."""
    import json

    from benchmarks import readings
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(toy_lm_manifest))
    assert readings.main(["--manifest", str(path), "--workload", CELL,
                          "--seeds", "2", "--first-seed", "2147483800",
                          "--seconds", "0.3", "--control",
                          "compute_dtype=float8_e4m3fn"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["seed"] for x in lines] == [2147483800, 2147483801]
    for x in lines:
        assert x["correct"] is False and x["platform"] == "cpu"
        assert x["control"] == {"compute_dtype": "float8_e4m3fn"}
        value, limit = x["compared"]["grad_norm_gap"]
        assert value > 3 * limit == 3 * check.GRAD_NORM_TOL


# -- added by files alone ----------------------------------------------------------

def test_the_fixture_only_appends(manifest, toy_manifest, toy_lm_manifest):
    for before, after in ((manifest, toy_manifest),
                          (toy_manifest, toy_lm_manifest)):
        assert set(before) == set(after)
        for key, had in before.items():
            if isinstance(had, list):
                assert after[key][:len(had)] == had, key
            else:
                assert after[key] == had, key
    new = {c["name"]: c for c in toy_lm_manifest["configs"]}["toy_lm"]
    assert new["file"].startswith(TOY_DIR + "/")
    cell = harness.load_cell(toy_lm_manifest, CELL)
    for sub, name, ext in (("traffic", cell.traffic["name"], ".json"),
                           ("flops", cell.config["flops"], ".py"),
                           ("reference", cell.config["reference"], ".py")):
        path = harness.find_file(toy_lm_manifest, sub, name, ext)
        assert os.path.relpath(path, harness.ROOT).startswith(TOY_DIR + "/")
    # every per-layer entry that names no cell applies to the new one
    unlisted = [m["name"] for m in manifest["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 16
    assert set(unlisted) <= {m["name"] for m in cell.per_layer}


def test_the_reference_imports_nothing_of_the_program():
    for name in ("toy_lm.py", "toy.py"):
        with open(os.path.join(harness.ROOT, TOY_DIR, "reference",
                               name)) as f:
            text = f.read()
        assert "import theanompi_tpu" not in text
        assert "from theanompi_tpu" not in text
