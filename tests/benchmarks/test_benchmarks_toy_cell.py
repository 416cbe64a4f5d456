"""One toy cell end to end on the CPU mesh through the harness's functions,
the refusal without a TPU, and the reference check's tolerance.

The toy configuration, traffic mix, reference and per-layer metric are files
under ``tests/benchmarks/toy`` plus entries added to a copy of the manifest
(``conftest.toy_manifest``): what a later PR does to add a cell."""

import importlib.util
import json
import math
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import check

HERE = os.path.dirname(os.path.abspath(__file__))
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
# the readers of the program's span ring that need no device plane
RING_ON_CPU = {"xla_compile_s", "produce_ms", "device_put_ms",
               "batch_queue_wait_ms", "pool_busy_share",
               "unready_dequeue_share", "train_call_ms", "small_programs_ms"}
EARLY = {"import_jax": 1.0, "import_program": 3.0, "runtime_start": 5.0}


@pytest.fixture(scope="module")
def runs(toy_manifest):
    """The three rehearsals, run once: one device, the four-device mesh, and
    one device traced."""
    out = {}
    for key, cell, traced in (("one", "toy-b8-bsp-1chip", False),
                              ("four", "toy-b8-bsp-4chip", False),
                              ("traced", "toy-b8-bsp-1chip", True)):
        # "one" is told what run.py times before the harness starts
        early = dict(EARLY) if key == "one" else None
        out[key] = harness.run_cell(
            toy_manifest, cell, seed=3, seconds=2.0, trace=traced,
            t_process_start=early and time.time() - sum(early.values()),
            early_phases=early)
    return out


@pytest.mark.parametrize("key", ["one", "four", "traced"])
def test_toy_cell_runs_and_is_correct(runs, key):
    run = runs[key]
    assert run.problems == []
    assert run.correct and run.failed == 0
    assert run.attempted == run.window.steps > 10
    assert run.compiles_in_window == 0
    assert abs(run.first_cost - math.log(10)) < harness.FIRST_COST_TOL
    assert run.reference["ok"]
    assert run.reference["logit_rel_err"] < check.LOGIT_REL_TOL
    assert run.global_batch == 8 * run.cell.chips
    assert run.setup_s > 0 and run.setup_phases["first_step"] > 0


def test_setup_counts_every_phase_but_the_runtimes_start(runs):
    """``setup_s`` is process start to the start of the clock less the
    phases in ``OUTSIDE_SETUP``: the imports count, the runtime's start-up
    (seconds of the machine's, different from process to process) does not."""
    run = runs["one"]
    assert harness.OUTSIDE_SETUP == ("runtime_start",)
    assert set(EARLY) < set(run.setup_phases)
    counted = sum(v for k, v in run.setup_phases.items()
                  if k not in harness.OUTSIDE_SETUP)
    assert run.setup_s == pytest.approx(counted, abs=0.05)
    assert run.setup_s > EARLY["import_jax"] + EARLY["import_program"]


@pytest.mark.parametrize("key", ["one", "four", "traced"])
def test_a_cpu_line_has_the_keys_and_no_device_metric(runs, toy_manifest,
                                                      key):
    run = runs[key]
    line = harness.result_line(toy_manifest, run, trace=key == "traced")
    assert set(line) == LINE_KEYS             # no breakdown off the chip
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # every number `correct` compared beside its limit, as the last key
    assert list(line)[-1] == "checks"
    checks = line["checks"]
    assert {"logit_rel_err", "loss_err", "first_cost_gap",
            "costs_not_finite", "compiles_in_window",
            "layout_faults"} <= set(checks)
    assert checks["logit_rel_err"] == [run.reference["logit_rel_err"],
                                       check.LOGIT_REL_TOL]
    assert all(value <= limit for value, limit in checks.values())
    json.dumps(line)


def test_a_reading_that_is_no_number_leaves_the_line_json(runs,
                                                          toy_manifest):
    import dataclasses
    run = dataclasses.replace(runs["one"], compared=dict(
        runs["one"].compared, first_cost_gap=[float("nan"), 0.25],
        grad_norm_gap=[float("inf"), 0.1]))
    line = harness.result_line(toy_manifest, run, trace=False)
    assert line["checks"]["first_cost_gap"] == [None, 0.25]
    assert line["checks"]["grad_norm_gap"] == [None, 0.1]
    assert "NaN" not in json.dumps(line) and "Infinity" not in json.dumps(line)


def test_window_counts_steps_and_recorder_buckets(runs):
    w = runs["one"].window
    assert 1.9 < w.seconds < 4.0
    assert set(w.buckets) == set(harness.RECORDER_BUCKETS)
    assert w.buckets["train"] > 0 and w.buckets["load"] >= 0
    assert sum(w.buckets.values()) < w.seconds


def test_traced_run_maps_host_rows_onto_the_traces_clock(runs):
    run = runs["traced"]
    assert run.traced is not None and run.traced.steps > 0
    assert 0 < run.traced.seconds <= run.window.seconds
    # the traced stretch, read off the host's clock, on the trace's clock
    lo, hi = run.trace_window
    assert lo > 0
    assert (hi - lo) / 1e9 == pytest.approx(run.traced.seconds, rel=0.05)
    # the host rows: the harness's spans, and the recorder's brackets
    # inside train_iter; all inside the profiling session
    labels = {r[0] for r in run.host_rows}
    assert {"load", "stage", "train", "train_iter", "exchange",
            "print_train_info"} <= labels
    t = run.tables
    assert all(0 < t.on_trace_clock(s) <= t.on_trace_clock(e)
               for _, s, e in run.host_rows)
    inside = [r for r in run.host_rows if r[0] == "train_iter"
              and lo <= t.on_trace_clock(r[1]) and t.on_trace_clock(r[2]) <= hi]
    assert abs(len(inside) - run.traced.steps) <= 1
    # a CPU trace has no TPU plane: device readers find nothing to read
    assert t.devices == []


def test_every_reader_runs_on_the_traced_toy_run(runs, toy_manifest):
    run = runs["traced"]
    got = harness.read_metrics(toy_manifest, run.cell.per_layer,
                               "layer_metrics", run)
    # host-side readers read, device readers return nothing (no TPU plane)
    assert {"compile_s", "first_step_s", "load_wait_share",
            "host_dispatch_ms", "toy_traced_steps"} | RING_ON_CPU == set(got)
    assert got["toy_traced_steps"]["value"] == run.traced.steps
    assert 0 <= got["load_wait_share"]["value"] <= 100
    e2e = harness.read_metrics(toy_manifest, run.cell.end_to_end,
                               "end_to_end", run)
    assert "mfu" not in e2e and "peak_hbm" not in e2e   # no peaks, no stats


def test_untraced_run_gives_the_layer_readers_nothing(runs, toy_manifest):
    run = runs["one"]
    got = harness.read_metrics(toy_manifest, run.cell.per_layer,
                               "layer_metrics", run)
    assert set(got) == {"compile_s", "first_step_s"}


def test_the_traced_run_keeps_the_train_programs_scopes(runs):
    """After the window the traced run joins the compiled train program's
    instructions to their ``op_name``; the untraced run does not pay it."""
    from benchmarks import trace
    assert runs["one"].scopes == {} and runs["four"].scopes == {}
    assert runs["one"].after_window_s == {}
    scopes = runs["traced"].scopes
    # its seconds are kept apart: after the window, in no metric
    assert runs["traced"].after_window_s["scope_join"] > 0
    assert runs["traced"].scope_join_error is None
    assert len(scopes) > 50
    ways = {trace.direction(v) for v in scopes.values()}
    assert ways == {"forward", "backward", "other"}
    convs = {trace.direction(v) for v in scopes.values()
             if v.endswith("/conv_general_dilated")}
    assert convs == {"forward", "backward"}
    assert any(trace.in_scope(v, "per_worker") for v in scopes.values())


def test_a_join_that_cannot_be_made_is_said_and_the_run_goes_on():
    class NoText:
        steps_per_call = 1

        class train_fn:
            @staticmethod
            def lower(*avals):
                raise RuntimeError("no lowering here")

        def _train_input_avals(self, spc, exchanger):
            return ()

    scopes, error = harness.train_scopes(NoText(), None)
    assert scopes == {} and "no lowering here" in error


def test_an_image_model_is_checked_as_on_the_parent(runs, toy_manifest):
    """A reference module without ``batch`` or ``train_loss`` goes through
    the parent's statements: the same fields, the same values."""
    import jax
    import jax.numpy as jnp

    ref_mod = harness.load_module(toy_manifest, "reference", "toy")
    assert not hasattr(ref_mod, "batch") and not hasattr(ref_mod,
                                                         "train_loss")
    cfg = runs["one"].cell.config

    from theanompi_tpu.worker import WORKERS
    config = dict(cfg["worker_config"], rule="bsp", batch_size=8,
                  n_workers=1, seed=3, synthetic_batches=4)
    model = WORKERS["bsp"](config).build_model(cfg["modelfile"],
                                               cfg["modelclass"])

    def parent(ref_mod, config, model, seed):   # PR 26's reference_check
        x, y = check.image_batch(config, np.random.RandomState(seed), 8)
        params, bn = model.canonical_host_params(), model.bn_state

        def system(params, bn, x, y):
            logits, _ = model.apply_model(params, model.stage_input(x),
                                          train=False, rng=None, state=bn)
            cost, _ = model.val_metrics(params, bn, {"x": x, "y": y})
            return logits.astype(jnp.float32), cost

        def reference(params, x, y):
            logits = ref_mod.forward(params, x)
            return logits, check.plain_softmax_loss(logits, y)

        sys_logits, sys_loss = jax.jit(system)(params, bn, x, y)
        with jax.default_matmul_precision("highest"):
            ref_logits, ref_loss = jax.jit(reference)(params, x, y)
        return check.compare(np.asarray(ref_logits), np.asarray(sys_logits),
                             float(ref_loss), float(sys_loss))

    now = harness.reference_check(ref_mod, cfg, model, 3)
    assert now == parent(ref_mod, cfg, model, 3)
    assert list(now) == ["ok", "logit_rel_err", "logit_scale", "loss_err",
                         "loss_tol", "ref_loss", "sys_loss"]
    assert now == runs["one"].reference     # and what the run itself held


def test_four_device_layout_check_sees_a_broken_replica(runs):
    """The check that guards BSP's replicas fails when one is perturbed."""
    import jax

    class Broken:
        pass

    run = runs["four"]
    cell = run.cell
    n = cell.chips
    from theanompi_tpu.parallel.mesh import worker_mesh
    from theanompi_tpu.parallel import steps
    mesh = worker_mesh(n)
    good = steps.replicate_tree({"w": np.ones((3, 2), np.float32)}, n, mesh)
    bad_host = np.ones((n, 3, 2), np.float32)
    bad_host[2, 0, 0] = 2.0
    bad = steps.place_boxed({"w": bad_host}, mesh)
    m = Broken()
    m.step_state = {"params": good}
    assert harness.layout_problems(m, cell) == []
    m.step_state = {"params": bad}
    assert any("differ" in p for p in harness.layout_problems(m, cell))
    m.step_state = {"params": {"w": jax.device_put(bad_host,
                                                   jax.devices()[0])}}
    assert any("lies on 1 of 4" in p
               for p in harness.layout_problems(m, cell))


def test_too_few_devices_is_refused(toy_manifest):
    m = dict(toy_manifest)
    m["workloads"] = toy_manifest["workloads"] + [
        {"name": "toy-64", "config": "toy", "traffic": "toy-b8-bsp",
         "chips": 64, "why": "more chips than any test machine has"}]
    with pytest.raises(harness.Refused, match="needs 64 chip"):
        harness.run_cell(m, "toy-64", seed=0, seconds=1.0, trace=False)


def _run_py():
    spec = importlib.util.spec_from_file_location(
        "_bench_run_py", os.path.join(harness.ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_command_refuses_without_a_tpu(capsys):
    rc = _run_py().main(["--workload", "vgg16-b384-bsp-1chip", "--seed",
                         "0", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert "'cpu'" in out.err and "TPU" in out.err
    assert out.out == ""                      # no result line


def test_the_command_refuses_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own directories there is no system to measure."""
    shutil.copy(harness.MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(harness.ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "vgg16-b384-bsp-1chip", "--seed", "0", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "cannot import the system under test" in p.stderr


# -- the tolerance: bf16 passes, an 8-bit float does not ---------------------

def _rounded(tree, dtype):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda a: jnp.asarray(a, jnp.float32).astype(dtype).astype(
            jnp.float32), tree)


@pytest.mark.parametrize("dtype,passes", [("bfloat16", True),
                                          ("float8_e4m3fn", False)])
def test_tolerance_separates_bf16_from_an_8_bit_float(runs, toy_manifest,
                                                      dtype, passes):
    """Rounding only the parameters and the input to the format (less error
    than computing in it throughout) already decides the comparison."""
    import jax
    import jax.numpy as jnp
    ref_mod = harness.load_module(toy_manifest, "reference", "toy")
    cfg = runs["one"].cell.config
    x, y = check.image_batch(cfg, np.random.RandomState(5), 8)
    params = _toy_params()
    with jax.default_matmul_precision("highest"):
        ref = ref_mod.forward(params, x)
        low = ref_mod.forward(_rounded(params, getattr(jnp, dtype)),
                              _rounded(x, getattr(jnp, dtype)))
        got = check.compare(np.asarray(ref), np.asarray(low),
                            float(check.plain_softmax_loss(ref, y)),
                            float(check.plain_softmax_loss(low, y)))
    assert got["ok"] is passes, got


def _toy_params():
    r = np.random.RandomState(11)
    return {"conv1": {"w": r.randn(3, 3, 3, 8).astype(np.float32) * 0.001,
                      "b": np.zeros(8, np.float32)},
            "fc2": {"w": r.randn(512, 32).astype(np.float32) * 0.01,
                    "b": np.zeros(32, np.float32)},
            "softmax": {"w": r.randn(32, 10).astype(np.float32) * 0.01,
                        "b": np.zeros(10, np.float32)}}


def test_compare_rejects_non_finite_and_wrong_logits():
    ref = np.linspace(-1, 1, 80, dtype=np.float32).reshape(8, 10)
    assert check.compare(ref, ref + 0.001, 2.3, 2.3005)["ok"]
    assert not check.compare(ref, ref + 0.05, 2.3, 2.3)["ok"]
    assert not check.compare(ref, ref, 2.3, 2.4)["ok"]
    bad = ref.copy()
    bad[0, 0] = np.nan
    assert not check.compare(ref, bad, 2.3, 2.3)["ok"]
