"""A step's device time by phase (``benchmarks/phases.py``): the partition on
hand-made rows, the device's own divisor, the five readers, and -- on four
virtual devices -- that the train program really names its wire ``exchange``
and its optimizer ``update`` for the readers to find."""

import random
import types

import jax
import pytest

from benchmarks import harness, phases, trace

READERS = {"forward_ms": "forward", "backward_ms": "backward",
           "recompute_ms": "recompute", "update_ms": "update",
           "exchange_scope_ms": "exchange"}
TOKEN_CELLS = ["ouro-2.6b-t4096-b2-bsp-1chip",
               "laguna-s-2.1-t8192-b1-bsp-1chip"]

P = "jit(per_worker)/shard_map/"
SCOPES = {
    "while.1": P + "jvp(ut_loop)/while",
    "while.2": P + "transpose(jvp(ut_loop))/while",
    "fusion.f": P + "jvp(ut_loop)/while/body/closed_call/block0/attn/dot_general",
    "fusion.b": P + "transpose(jvp(ut_loop))/while/body/block0/mlp/dot_general",
    "fusion.r": P + "transpose(jvp(ut_loop))/while/body/closed_call/block0/"
                    "block0/checkpoint/rematted_computation/mlp/dot_general",
    "fusion.u": P + "update/mul",
    "all-reduce.1": P + "exchange/psum_invariant",
    "async-collective-start.1": P + "transpose(jvp(fc6))/exchange/all_gather",
    "async-collective-done.1": P + "transpose(jvp(fc6))/exchange/all_gather",
    "copy.1": P + "broadcast_in_dim",
}


def ev(name, s, e):
    """A trace row as the chip writes it: the name is the instruction."""
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop", s, e)


# -- the phase of an op_name ---------------------------------------------------

@pytest.mark.parametrize("op_name,phase", [
    (P + "jvp(conv1_1)/conv_general_dilated", "forward"),
    (P + "transpose(jvp(conv1_1))/conv_general_dilated", "backward"),
    (SCOPES["fusion.r"], "recompute"),
    # jax writes the part in a checkpoint's transposition alone: enough
    ("block0/block0/checkpoint/rematted_computation/norm1/reduce_sum",
     "recompute"),
    (P + "update/mul", "update"),
    (P + "exchange/psum_invariant", "exchange"),
    # precedence: a gather inside the backward pass is the wire's
    (P + "transpose(jvp(fc6))/exchange/all_gather", "exchange"),
    # ... and a collective the optimizer makes would still be the wire's
    (P + "update/exchange/psum", "exchange"),
    # a whole part, not a substring
    (P + "jvp(exchange_rate)/mul", "forward"),
    (P + "jvp(updates)/mul", "forward"),
    (P + "broadcast_in_dim", "other"),
    ("", "other"),
    (None, "other"),
])
def test_phase_of_reads_scopes_before_transforms(op_name, phase):
    assert phases.phase_of(op_name) == phase


# -- the partition ---------------------------------------------------------------

def test_a_while_gives_its_time_to_its_bodys_instructions():
    ops = [ev("while.1", 0, 100), ev("fusion.b", 10, 30),
           ev("fusion.u", 40, 60)]
    got = phases.phase_ns(ops, (0, 100), SCOPES)
    # the while keeps what lies between its children, as forward
    assert got == {"forward": 60, "backward": 20, "recompute": 0,
                   "exchange": 0, "update": 20, "other": 0}
    assert sum(got.values()) == trace.busy_ns(ops, (0, 100)) == 100


def test_an_async_pair_leaves_what_runs_inside_it_its_own_phase():
    ops = [ev("async-collective-start.1", 0, 5), ev("fusion.b", 5, 50),
           ev("async-collective-done.1", 50, 60)]
    got = phases.phase_ns(ops, (0, 80), SCOPES)
    assert (got["exchange"], got["backward"]) == (15, 45)
    assert sum(got.values()) == 60               # 20 ns idle are no phase's


def test_two_instructions_that_overlap_without_nesting():
    """The later start wins while it is open; the earlier one takes over
    again only if it outlasts it."""
    ops = [ev("all-reduce.1", 0, 10), ev("fusion.b", 5, 15)]
    got = phases.phase_ns(ops, (0, 20), SCOPES)
    assert (got["exchange"], got["backward"]) == (5, 10)
    ops = [ev("all-reduce.1", 0, 30), ev("fusion.b", 5, 15)]
    got = phases.phase_ns(ops, (0, 30), SCOPES)
    assert (got["exchange"], got["backward"]) == (20, 10)


def test_an_instruction_the_join_does_not_know_belongs_where_it_runs():
    """Inside a ``while`` it is the while's work; at the top level, or where
    it only overlaps another, it is ``other``."""
    ops = [ev("while.2", 0, 50), ev("fusion.nameless", 10, 20),
           ev("copy.1", 20, 25),                    # a path, no transform
           ev("copy.1", 60, 70), ev("fusion.nameless", 80, 90),
           ev("fusion.b", 100, 110), ev("fusion.nameless", 105, 115)]
    got = phases.phase_ns(ops, (0, 120), SCOPES)
    assert got["backward"] == 50 + 5 and got["other"] == 10 + 10 + 10
    # a nameless loop's nameless body, inside a named one
    ops = [ev("while.1", 0, 100), ev("while.nameless", 10, 90),
           ev("fusion.nameless", 20, 30), ev("fusion.u", 40, 50)]
    got = phases.phase_ns(ops, (0, 100), SCOPES)
    assert (got["forward"], got["update"], got["other"]) == (90, 10, 0)
    assert phases.phase_ns(ops, (0, 100), {})["other"] == 100


def test_rows_are_clipped_to_the_window():
    ops = [ev("fusion.f", -10, 10), ev("fusion.u", 90, 120),
           ev("fusion.b", 200, 210)]
    got = phases.phase_ns(ops, (0, 100), SCOPES)
    assert (got["forward"], got["update"], got["backward"]) == (10, 10, 0)


def _brute(rows, window, nameless=None):
    """The rule, an instant at a time: rows in order of start (the longer
    first), each nameless one renamed after the nearest row before it that
    is still open and holds it whole."""
    rows = sorted(rows, key=lambda r: (r[1], -r[2]))
    rows = [(key, max(s, window[0]), min(e, window[1])) for key, s, e in rows]
    rows = [r for r in rows if r[2] > r[1]]     # what the window shows
    named = []
    for i, (key, s, e) in enumerate(rows):
        if key == nameless:
            around = [j for j in range(i) if rows[j][2] > s]
            if around and rows[around[-1]][2] >= e:
                key = named[around[-1]]
        named.append(key)
    acc = {}
    for t in range(*window):
        open_ = [i for i, (_, s, e) in enumerate(rows) if s <= t < e]
        if open_:
            acc[named[open_[-1]]] = acc.get(named[open_[-1]], 0) + 1
    return acc


@pytest.mark.parametrize("seed", range(6))
def test_no_instant_is_counted_twice_and_the_phases_sum_to_busy(seed):
    rng = random.Random(seed)
    names = list(SCOPES) + ["fusion.nameless"]
    rows = []
    for _ in range(60):                 # nested, overlapping, touching, equal
        s = rng.randrange(0, 400)
        rows.append((rng.choice(names), s, s + rng.randrange(1, 90)))
    window = (20, 380)
    assert phases.innermost_ns(rows, window) == _brute(rows, window)
    assert phases.innermost_ns(rows, window, "copy.1") == \
        _brute(rows, window, "copy.1")
    ops = [ev(name, s, e) for name, s, e in rows]
    got = phases.phase_ns(ops, window, SCOPES)
    assert set(got) == set(phases.PHASES)
    assert sum(got.values()) == trace.busy_ns(ops, window)


# -- the divisor -------------------------------------------------------------------

def mod(s, e, name="jit_per_worker(7)"):
    return (name, s, e)


def test_the_sub_window_runs_from_the_first_start_to_the_last():
    """Three executions start in the window; their ends are early, as the
    looped cell's are (PERF.md section 7 (j)), and do not matter."""
    modules = [mod(-100, -20), mod(0, 60), mod(100, 130), mod(200, 290),
               mod(300, 360), ("jit_mean(1)", 150, 155)]
    assert phases.whole_periods(modules, (-10, 290)) == ((0, 200), 2)
    assert phases.whole_periods(modules, (-10, 350)) == ((0, 300), 3)
    assert phases.whole_periods(modules, (50, 150)) is None      # n = 1
    assert phases.whole_periods([], (0, 100)) is None


def fake_run(ops, modules, window, scopes=SCOPES, steps_per_call=1):
    tables = trace.TraceTables([trace.DeviceTables(0, modules, ops)], 0)
    return types.SimpleNamespace(
        tables=tables, trace_window=window, scopes=scopes,
        steps_per_call=steps_per_call, after_window_s={},
        # what the host counted: the readers must not look at it
        traced=types.SimpleNamespace(steps=1))


def a_step(at):
    """100 ns of a step that runs past its module event's end."""
    return [ev("while.1", at, at + 20), ev("fusion.f", at + 5, at + 15),
            ev("while.2", at + 20, at + 70), ev("fusion.r", at + 25, at + 35),
            ev("fusion.b", at + 35, at + 60),
            ev("all-reduce.1", at + 70, at + 78),
            ev("fusion.u", at + 78, at + 90), ev("copy.1", at + 90, at + 94)]


def test_phases_are_divided_by_the_periods_between_the_starts(capsys):
    modules = [mod(at, at + 40) for at in (0, 100, 200, 300)]
    ops = [r for at in (0, 100, 200, 300) for r in a_step(at)]
    run = fake_run(ops, modules, (-5, 360))
    got = phases.phases_ms_per_step(run)
    # 3 periods between 4 starts: the fourth step, cut by the stretch's
    # end, is not in the sum, and the host's count of 1 is not the divisor
    assert got == pytest.approx({
        "forward": 20e-6, "recompute": 10e-6, "backward": 40e-6,
        "exchange": 8e-6, "update": 12e-6, "other": 4e-6, "period": 100e-6})
    assert sum(got[p] for p in phases.PHASES) == pytest.approx(94e-6)
    assert "phases" in run.after_window_s
    assert "benchmarks: phases over 32 events" in capsys.readouterr().err
    assert phases.phases_ms_per_step(run) is got         # one sweep a run


def test_steps_per_call_divides_once_more():
    modules = [mod(at, at + 40) for at in (0, 100, 200)]
    ops = [r for at in (0, 100, 200) for r in a_step(at)]
    got = phases.phases_ms_per_step(fake_run(ops, modules, (-5, 290),
                                             steps_per_call=4))
    assert got["period"] == pytest.approx(25e-6)
    assert got["update"] == pytest.approx(3e-6)


@pytest.mark.parametrize("name,phase", sorted(READERS.items()))
def test_a_reader_reads_its_phase(manifest, name, phase):
    modules = [mod(at, at + 40) for at in (0, 100, 200)]
    ops = [r for at in (0, 100, 200) for r in a_step(at)]
    read = harness.load_module(manifest, "layer_metrics", name).read
    run = fake_run(ops, modules, (-5, 290))
    assert read(run) == pytest.approx(
        phases.phases_ms_per_step(run)[phase]) and read(run) > 0
    # nothing to read: no trace, no start time, no join, one execution
    for blind in (fake_run(ops, modules, None), fake_run(ops, modules,
                  (-5, 290), scopes={}), fake_run(ops, modules, (90, 190))):
        assert read(blind) is None
    blind = fake_run(ops, modules, (-5, 290))
    blind.tables = None
    assert read(blind) is None


def test_a_program_without_the_scopes_leaves_their_metrics_out(manifest):
    """The parent of the PR that brought ``update`` and ``exchange``: the
    same instructions, named by transform alone."""
    bare = dict(SCOPES, **{"fusion.u": P + "mul", "fusion.r": P + "mul",
                           "all-reduce.1": P + "psum_invariant"})
    modules = [mod(at, at + 40) for at in (0, 100, 200)]
    ops = [r for at in (0, 100, 200) for r in a_step(at)]
    got = {name: harness.load_module(manifest, "layer_metrics", name).read(
        fake_run(ops, modules, (-5, 290), scopes=bare)) for name in READERS}
    assert got["update_ms"] is None and got["exchange_scope_ms"] is None \
        and got["recompute_ms"] is None
    assert got["forward_ms"] > 0 and got["backward_ms"] > 0


def test_a_recorded_v5e_trace_is_partitioned_whole():
    """Chip 0 of a four-chip run (``benchmarks/testdata``): two executions
    start in the cut, so one whole period lies between their starts, 344.7
    ms where the module events read 349.2; the phases of its 1,280 events sum
    to the chip's busy time to the nanosecond."""
    import json
    import os
    data = os.path.join(harness.ROOT, "benchmarks", "testdata",
                        "v5e_vgg16_b384_bsp_4chip")
    tables = trace.load_text_proto(data + ".pbtxt")
    with open(data + ".json") as f:
        window = tuple(json.load(f)["window"])
    with open(data + ".hlo.txt") as f:
        scopes = trace.scopes_from_hlo(f.read())
    dev = tables.devices[0]
    sub, periods = phases.whole_periods(dev.modules, window)
    assert periods == 1 and sub[1] - sub[0] == 344_733_378
    got = phases.phase_ns(dev.ops, sub, scopes)
    assert sum(got.values()) == trace.busy_ns(dev.ops, sub)
    assert got["forward"] > 0 and got["backward"] > 0
    # the hand-made text names twelve instructions: the rest is `other`
    assert got["other"] > got["forward"] + got["backward"]


# -- the manifest ------------------------------------------------------------------

def test_the_five_readers_stand_behind_the_twenty_five(manifest):
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("forward_ms")
    assert at >= 25 and names[at:at + 5] == [
        "forward_ms", "backward_ms", "recompute_ms", "update_ms",
        "exchange_scope_ms"]
    for m in manifest["per_layer"][at:at + 5]:
        assert (m["unit"], m["better"], m["source"]) == (
            "ms/step", "lower", "device_trace")
        assert m["moves"] == ("mfu" if m["name"] == "recompute_ms"
                              else "train_throughput")
    by = {m["name"]: m for m in manifest["per_layer"]}
    assert by["update_ms"]["layer"] == "optimizer"
    assert by["exchange_scope_ms"]["layer"] == "exchange"
    assert by["recompute_ms"]["workloads"] == TOKEN_CELLS
    assert by["exchange_scope_ms"]["workloads"] == ["vgg16-b384-bsp-4chip"]
    # the readers of every cell list every cell the benchmark has today
    # (test_benchmarks_laguna.py pins the unlisted metrics at sixteen)
    cells = [w["name"] for w in manifest["workloads"]]
    for name in ("forward_ms", "backward_ms", "update_ms"):
        assert by[name]["workloads"][:4] == cells[:4]
    # the opcode readers stay beside the scope's
    assert "exchange_device_ms" in by and "exchange_exposed_ms" in by


@pytest.mark.parametrize("cell", ["vgg16-b384-bsp-1chip",
                                  "vgg16-b384-bsp-4chip"] + TOKEN_CELLS)
def test_each_cell_reads_the_phases_that_exist_in_it(manifest, cell):
    got = {m["name"] for m in harness.load_cell(manifest, cell).per_layer}
    assert {"forward_ms", "backward_ms", "update_ms"} <= got
    assert ("recompute_ms" in got) == (cell in TOKEN_CELLS)
    assert ("exchange_scope_ms" in got) == cell.endswith("4chip")


# -- the program names its wire and its optimizer -----------------------------

COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute")

TOYS = {
    "vgg16": ("theanompi_tpu.models.vggnet_16", "VGGNet_16", dict(
        batch_size=2, n_class=16, synthetic_train=64, synthetic_val=32,
        synthetic_batches=1)),
    "looped_lm": ("theanompi_tpu.models.looped_lm", "LoopedLM", dict(
        batch_size=2, vocab=128, d_model=64, n_head=2, n_layer=2, d_ff=96,
        seq_len=16, loop_steps=3, learning_rate=3e-4, synthetic_val=4,
        attn_impl="reference")),
}


@pytest.fixture(scope="module", params=sorted(TOYS))
def bsp_program(request):
    """``(model name, compiled text, scopes)`` of the BSP train step on four
    virtual devices, the optimizer's own call marked by a probe scope."""
    from theanompi_tpu.worker import WORKERS

    modelfile, modelclass, sizes = TOYS[request.param]
    worker = WORKERS["bsp"](dict(sizes, n_workers=4, seed=3, rule="bsp",
                                 verbose=False))
    model = worker.build_model(modelfile, modelclass)
    worker.exchanger.gather_min_bytes = 0     # the gathered path, toy shapes
    update = model.opt.update

    def probed(*args):
        with jax.named_scope("opt_update_probe"):
            return update(*args)

    model.opt = model.opt._replace(update=probed)
    try:
        model.compile_iter_fns(worker.exchanger)
        text = model.train_fn.lower(
            *model._train_input_avals(1, worker.exchanger)).compile().as_text()
    finally:
        stop = getattr(model.data, "_shutdown", None)
        if stop is not None:
            stop()
    return request.param, text, trace.scopes_from_hlo(text)


def _instructions(text):
    """``(name, opcode, line)`` of every instruction of the module."""
    for line in text.splitlines():
        m = trace._INSTRUCTION.match(line)
        if m:
            yield m.group(2).lstrip("%"), trace.opcode(line.strip()), line


def test_every_collective_of_the_bsp_step_is_under_exchange(bsp_program):
    name, text, scopes = bsp_program
    wire = [(n, op) for n, op, _ in _instructions(text)
            if trace.collective_base(op) or op.startswith("async-")]
    assert {op for _, op in wire} >= {"all-reduce", "all-gather"} \
        if name == "vgg16" else wire
    for n, op in wire:
        assert phases.phase_of(scopes.get(n)) == "exchange", \
            (n, op, scopes.get(n))
    # the gathers lie in the backward pass of the layer that owns them
    if name == "vgg16":
        assert any("transpose(jvp(fc6))/exchange/all_gather" in scopes[n]
                   for n, _ in wire)


def test_every_instruction_of_the_optimizer_is_under_update(bsp_program):
    _, _, scopes = bsp_program
    probed = [op for op in scopes.values() if "opt_update_probe" in op]
    assert len(probed) > 10
    for op_name in probed:
        assert phases.phase_of(op_name) == "update", op_name
    # and the update is the optimizer's alone: no layer's work is in it
    assert not [op for op in scopes.values()
                if phases.phase_of(op) == "update"
                and ("jvp(" in op or "transpose(" in op)]


def test_the_partition_has_every_phase_the_model_runs(bsp_program):
    name, _, scopes = bsp_program
    seen = {phases.phase_of(op) for op in scopes.values()}
    assert {"forward", "backward", "exchange", "update"} <= seen
    # the looped stack applies its layers under jax.checkpoint; the image
    # model keeps its activations
    assert ("recompute" in seen) == (name == "looped_lm")
    if name == "looped_lm":
        assert any("checkpoint/rematted_computation" in op
                   and "transpose(jvp(ut_loop))" in op
                   for op in scopes.values())


def test_the_instructions_end_in_a_layer(bsp_program):
    name, _, scopes = bsp_program
    tails = {trace.scope_tail(op, 48) for op in scopes.values()}
    if name == "looped_lm":             # its scopes are older than this file
        assert any(t.endswith("block0/attn/dot_general") for t in tails)
        return
    assert "transpose(jvp(conv3_1))/conv_general_dilated" in tails
    assert "jvp(conv1_1)/conv_general_dilated" in tails
    assert "jvp(fc6)/dot_general" in tails
    # a ReLU moved behind its pool runs under the pool's key
    assert any("jvp(pool1)/jit(relu)" in op for op in scopes.values())
