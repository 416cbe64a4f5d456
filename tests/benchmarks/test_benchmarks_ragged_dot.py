"""The grouped products' own readers (``ragged_dot_ms``,
``ragged_dot_roofline``): the kernels by their instruction name on
hand-made rows, in a loop and straight-line alike, over the device's own
divisor; the share against a hand count at the routed cell's size; their
manifest entries, appended and listed for the routed cell alone."""

import os
import types

import pytest

from benchmarks import harness, phases, trace

CELL = "laguna-s-2.1-t8192-b1-bsp-1chip"
READERS = [
    {"name": "ragged_dot_ms", "unit": "ms/step", "better": "lower",
     "source": "device_trace", "layer": "expert layer",
     "moves": "train_throughput", "workloads": [CELL]},
    {"name": "ragged_dot_roofline", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "expert layer", "moves": "mfu",
     "workloads": [CELL]}]

P = "jit(per_worker)/"
SCOPES = {
    "while.4": P + "jvp(block1)/moe/while",
    "fusion.s": P + "jvp(block1)/moe/while/body/scatter-add",
    "fusion.c": P + "jvp(block1)/moe/experts/convert_element_type",
    # as the compiler writes a grouped product's: the path dropped
    "ragged-dot-none.3": "ragged-dot-none",
    "ragged-dot-none.11": "ragged-dot-none",
}


def ev(name, s, e):
    return (f"%{name} = f32[4096,1024]{{1,0}} custom-call(bf16[4096,3072]"
            f"{{1,0}} %p), custom_call_target=\"tpu_custom_call\"", s, e)


def mod(s, e, name="jit_per_worker(7)"):
    return (name, s, e)


def a_step(at):
    """A step of 1,000,000 ns: a product of 30,000 inside a loop (with a
    scatter-add beside it), one of 50,000 straight-line, a cast."""
    return [ev("while.4", at, at + 200_000),
            ev("ragged-dot-none.3", at + 10_000, at + 40_000),
            ev("fusion.s", at + 40_000, at + 150_000),
            ev("fusion.c", at + 300_000, at + 350_000),
            ev("ragged-dot-none.11", at + 400_000, at + 450_000)]


def fake_run(ops, modules, window, steps_per_call=1, config=None):
    tables = trace.TraceTables([trace.DeviceTables(0, modules, ops)], 0)
    return types.SimpleNamespace(
        tables=tables, trace_window=window, scopes=SCOPES,
        steps_per_call=steps_per_call, after_window_s={},
        peaks={"bf16_flops_per_s": 197e12}, global_batch=1,
        cell=types.SimpleNamespace(config=config, chips=1),
        # what the host counted: the readers must not look at it
        traced=types.SimpleNamespace(steps=1))


def reader(manifest, name):
    return harness.load_module(manifest, "layer_metrics", name).read


def three_steps():
    ops = [r for at in (0, 1_000_000, 2_000_000) for r in a_step(at)]
    # the module events end early, as the routed cell's do: starts count
    modules = [mod(at, at + 600_000) for at in (0, 1_000_000, 2_000_000)]
    return ops, modules


# -- the time --------------------------------------------------------------------

def test_products_count_in_a_loop_and_straight_line_alike(manifest):
    """Two whole periods between the three starts hold two of each
    product; the third step's lie past the last start."""
    ops, modules = three_steps()
    run = fake_run(ops, modules, (-5, 3_000_000))
    assert phases.whole_periods(modules, run.trace_window) == (
        (0, 2_000_000), 2)
    assert reader(manifest, "ragged_dot_ms")(run) == pytest.approx(
        (30_000 + 50_000) / 1e6)
    # the scope readers hold the loop (its product with it) and the cast,
    # not the straight-line product: that is why
    assert trace.scope_busy_ns(ops, (0, 2_000_000), SCOPES, "moe") \
        == 2 * (200_000 + 50_000)
    assert trace.scope_busy_ns(ops, (0, 2_000_000), SCOPES, "experts") \
        == 2 * 50_000                              # the cast alone


def test_the_divisor_is_the_devices_and_the_calls_steps(manifest):
    ops, modules = three_steps()
    run = fake_run(ops, modules, (-5, 3_000_000), steps_per_call=4)
    run.traced.steps = 17                           # the host's count: unread
    assert reader(manifest, "ragged_dot_ms")(run) == pytest.approx(
        80_000 / 4 / 1e6)


def test_a_kernel_is_clipped_to_the_whole_periods(manifest):
    ops, modules = three_steps()
    ops.append(ev("ragged-dot-none.3", -20_000, 10_000))     # half before
    run = fake_run(ops, modules, (-50_000, 3_000_000))
    assert reader(manifest, "ragged_dot_ms")(run) == pytest.approx(
        (2 * 80_000 + 10_000) / 2 / 1e6)


@pytest.mark.parametrize("case", ["no trace", "no window", "one execution",
                                  "no such kernel"])
def test_nothing_to_read_is_none_and_no_error(manifest, case):
    """A program with no grouped product, or a run with no trace, leaves
    the metric out of the line."""
    ops, modules = three_steps()
    window = (-5, 3_000_000)
    if case == "one execution":
        modules = modules[:1]
    if case == "no such kernel":
        ops = [r for r in ops if "ragged-dot" not in r[0]]
    run = fake_run(ops, modules, None if case == "no window" else window)
    if case == "no trace":
        run.tables = None
    for name in ("ragged_dot_ms", "ragged_dot_roofline"):
        assert reader(manifest, name)(run) is None


# -- the share -------------------------------------------------------------------

def test_the_share_is_the_required_flops_over_the_kernels_time(manifest):
    """The routed cell: four routed layers, 2,560 expected pairs a layer,
    three products of 3,072 x 1,024, forward and two backward: 0.58 TFLOP
    a step, 2.94 ms at 197 TFLOP/s; over 0.08 ms a step of kernels the
    share would be far over 100, over 13 ms it is 22.6."""
    config = harness.load_cell(manifest, CELL).config
    flops = 4 * 2560 * 3 * 3072 * 1024 * 2 * 3
    assert flops == 579_820_584_960
    ops, modules = three_steps()
    run = fake_run(ops, modules, (-5, 3_000_000), config=config)
    ms = reader(manifest, "ragged_dot_ms")(run)
    assert reader(manifest, "ragged_dot_roofline")(run) == pytest.approx(
        100 * flops / 197e12 / (ms / 1e3))
    stretched = [(n, s * 163, e * 163) for n, s, e in ops]   # 13.04 ms
    run = fake_run(stretched, [(n, s * 163, e * 163) for n, s, e in modules],
                   (-5, 3_000_000 * 163), config=config)
    assert reader(manifest, "ragged_dot_roofline")(run) == pytest.approx(
        22.57, abs=0.01)


def test_a_configuration_without_the_counter_reads_no_share(manifest):
    """The looped model's FLOP counter has no routed experts."""
    config = harness.load_cell(manifest, "ouro-2.6b-t4096-b2-bsp-1chip").config
    ops, modules = three_steps()
    run = fake_run(ops, modules, (-5, 3_000_000), config=config)
    assert reader(manifest, "ragged_dot_ms")(run) is not None
    assert reader(manifest, "ragged_dot_roofline")(run) is None


# -- the manifest ----------------------------------------------------------------

def test_the_readers_are_appended_and_listed_for_the_routed_cell(manifest):
    """Behind the five phases' readers; nothing here pins what a later PR
    appends behind them."""
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("ragged_dot_ms")
    assert at > names.index("exchange_scope_ms")
    assert manifest["per_layer"][at:at + 2] == READERS
    for cell in (w["name"] for w in manifest["workloads"]):
        got = {m["name"] for m in harness.load_cell(manifest, cell).per_layer}
        assert ({"ragged_dot_ms", "ragged_dot_roofline"} <= got) \
            == (cell == CELL)


@pytest.mark.parametrize("entry", READERS, ids=lambda r: r["name"])
def test_a_readers_entry_has_a_layer_a_metric_and_a_file(manifest, entry):
    assert entry["moves"] in {m["name"] for m in manifest["end_to_end"]}
    with open(os.path.join(harness.ROOT, "PERF.md")) as f:
        perf = f.read()
    assert f"| {entry['layer']} |" in perf and f"`{entry['name']}`" in perf
    assert callable(reader(manifest, entry["name"]))
