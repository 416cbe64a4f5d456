"""The trace reduction on a recorded TPU v5e trace kept with the benchmark.

``benchmarks/testdata/<name>.pbtxt`` is a real ``.xplane.pb`` of a
benchmark run, cut down to chip 0, two steps and the session's start time,
as an XSpace text proto (``jax.profiler.ProfileData.from_text_proto`` reads
it with the code that reads the binary).  ``<name>.json`` beside it holds the
cut's window and what a person read off the file when it was recorded."""

import glob
import json
import os

import pytest

from benchmarks import harness, trace

DATA = os.path.join(harness.ROOT, "benchmarks", "testdata")
NAMES = sorted(os.path.splitext(os.path.basename(p))[0]
               for p in glob.glob(os.path.join(DATA, "*.pbtxt")))


@pytest.fixture(scope="module", params=NAMES)
def recorded(request):
    with open(os.path.join(DATA, request.param + ".json")) as f:
        expect = json.load(f)
    tables = trace.load_text_proto(os.path.join(DATA,
                                                request.param + ".pbtxt"))
    return tables, tuple(expect["window"]), expect


def test_a_recorded_trace_is_kept():
    assert NAMES, "no recorded trace under benchmarks/testdata"


def test_tables_hold_chip_0_and_the_sessions_start(recorded):
    tables, window, expect = recorded
    assert [d.index for d in tables.devices] == [0]
    assert tables.start_unix_ns == expect["start_unix_ns"] > 1.7e18
    dev = tables.devices[0]
    assert len(dev.ops) == expect["ops"] and len(dev.modules) > 2
    assert trace.train_program(dev.modules) == "jit_per_worker"


def test_steps_and_device_time(recorded):
    tables, window, expect = recorded
    steps, ops = trace.steps_and_ops(tables, window)
    assert len(steps) == expect["steps"]
    ms = trace.mean_step_ns(tables, window) / 1e6
    assert ms == pytest.approx(expect["step_device_ms"], rel=1e-3)
    # instructions run one at a time inside a step; on four chips they do
    # not fill it (the program also waits: 19% of this cut is idle)
    inside = trace.total(trace.union((s, e) for _, s, e in ops))
    assert 0.75 * trace.total(steps) < inside <= trace.total(steps)
    assert sum(e - s for _, s, e in ops) == pytest.approx(inside, rel=0.02)


def test_idle_share_of_the_cut(recorded):
    tables, window, expect = recorded
    idle = 100 * trace.idle_share(tables.devices[0].ops, window)
    assert idle == pytest.approx(expect["idle_share_pct"], abs=0.05)
    busy = trace.union((s, e) for _, s, e in tables.devices[0].ops)
    free = trace.gaps(busy, window)
    assert trace.total(free) + trace.busy_ns(tables.devices[0].ops, window) \
        == window[1] - window[0]


def test_opcodes_parse_on_every_instruction(recorded):
    tables, window, expect = recorded
    by = {}
    for name, s, e in tables.devices[0].ops:
        op = trace.opcode(name)
        assert op and op[0].isalpha() and "[" not in op and "%" not in op, \
            name[:120]
        by[op] = by.get(op, 0) + 1
    assert by["fusion"] > 100
    for op, n in expect["opcode_events"].items():
        assert by.get(op, 0) == n, op


def test_collectives_of_the_cut(recorded):
    tables, window, expect = recorded
    steps, ops = trace.steps_and_ops(tables, window)
    coll = trace.collective_intervals(ops)
    assert len(coll) == expect["collectives_per_step"] * len(steps)
    if not coll:
        return
    device = trace.collective_ns(ops) / len(steps) / 1e6
    exposed = trace.exposed_collective_ns(ops) / len(steps) / 1e6
    assert device == pytest.approx(expect["exchange_device_ms"], rel=1e-3)
    assert exposed == pytest.approx(expect["exchange_exposed_ms"], rel=1e-3)
    assert 0 < exposed <= device


def test_breakdown_names_instructions(recorded):
    tables, window, expect = recorded
    top = trace.top_device_ops(tables.devices[0].ops, window)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    assert all(" %" in name for name, _ in top)


# -- the scope join: the recorded instructions against a compiled text --------------

SCOPED = {   # instruction -> (op_name, how the join finds it)
    "broadcast_maximum_fusion":
        "jit(per_worker)/jvp()/conv_general_dilated",           # its own
    "select_and_scatter.54":
        "jit(per_worker)/transpose(jvp())/select_and_scatter",  # its own
    "fusion.606":
        "jit(per_worker)/transpose(jvp())/conv_general_dilated",
    "psum_invariant.219": "jit(per_worker)/psum_invariant",
    # no metadata of its own; the root is a tuple the compiler made, and
    # the nearest instruction behind it is the update's `add`
    "fusion.185": "jit(per_worker)/add",
    # no metadata of its own; behind the root's tuple, the ReLU
    "broadcast_maximum_fusion.remat": "jit(per_worker)/jvp()/jit(relu)/max",
}


@pytest.fixture(scope="module")
def scopes():
    with open(os.path.join(DATA, "v5e_vgg16_b384_bsp_4chip.hlo.txt")) as f:
        return trace.scopes_from_hlo(f.read())


def _named(ops, *names):
    want = {"%" + n for n in names}
    return [r for r in ops if trace.instruction_name(r[0]) in want]


def test_an_instruction_gets_its_own_op_name_a_fusion_its_roots(scopes):
    for name, op_name in SCOPED.items():
        assert scopes[name] == op_name, name
    # in the text, without metadata anywhere behind them: unscoped
    for name in ("copy-start.145", "copy-done.145", "slice_bitcast_fusion.9",
                 "tuple.1"):
        assert name not in scopes
    # instructions inside fused computations are in the map too (their
    # names are unique in a module and no trace event carries them)
    assert scopes["maximum.7"] == "jit(per_worker)/jvp()/jit(relu)/max"


def test_the_recorded_events_find_their_scopes(recorded, scopes):
    tables, window, _ = recorded
    ops = tables.devices[0].ops
    for name in SCOPED:
        rows = _named(ops, name)
        assert len(rows) == 2, name                 # two steps in the cut
        assert trace.scope_of(scopes, rows[0][0]) == SCOPED[name]
    # in the text without metadata, and absent from the text: unscoped
    assert trace.scope_of(scopes, _named(ops, "copy-done.145")[0][0]) is None
    assert "fusion.607" not in scopes
    assert trace.scope_of(scopes, _named(ops, "fusion.607")[0][0]) is None


def test_scope_busy_time_of_the_recorded_cut(recorded, scopes):
    tables, window, _ = recorded
    ops = tables.devices[0].ops
    by_hand = lambda *names: trace.total(trace.union(trace.clip(  # noqa: E731
        ((s, e) for _, s, e in _named(ops, *names)), window)))
    assert trace.scope_busy_ns(ops, window, scopes, "per_worker") \
        == by_hand(*SCOPED) > 100e6
    assert trace.scope_busy_ns(ops, window, scopes, "per_worker",
                               "backward") \
        == by_hand("select_and_scatter.54", "fusion.606") > 50e6
    assert trace.scope_busy_ns(ops, window, scopes, "relu") \
        == trace.scope_busy_ns(ops, window, scopes, "relu", "forward") \
        == by_hand("broadcast_maximum_fusion.remat")
    assert trace.scope_busy_ns(ops, window, scopes, "relu", "backward") == 0
    assert trace.scope_busy_ns(ops, window, scopes, "per_work") == 0
    assert trace.scope_busy_ns(ops, window, {}, "per_worker") == 0
    # most of this trace is absent from the short text
    share = trace.unscoped_share(ops, window, scopes)
    assert share == pytest.approx(
        1 - by_hand(*SCOPED) / trace.busy_ns(ops, window))
    assert 0.5 < share < 1
    assert trace.unscoped_share(ops, window, {}) == 1.0
    assert trace.unscoped_share([], window, scopes) is None


def test_breakdown_names_gain_the_tail_of_the_path(recorded, scopes):
    tables, window, _ = recorded
    ops = tables.devices[0].ops
    bare = trace.top_device_ops(ops, window)
    top = trace.top_device_ops(ops, window, scopes=scopes)
    assert [v for _, v in top] == [v for _, v in bare]
    assert all(len(name) <= trace.TAIL_CHARS for name, _ in top)
    names = [name for name, _ in top]
    assert names[0] == \
        "fusion %fusion.606 transpose(jvp())/conv_general_dilated"
    assert "fusion %fusion.185 add" in names
    assert "fusion %broadcast_maximum_fusion.remat jvp()/jit(relu)/max" \
        in names
    assert "fusion %broadcast_maximum_fusion jvp()/conv_general_dilated" \
        in names
    # 64 characters: the direction stays, the primitive is cut
    assert "select-and-scatter %select_and_scatter.54 " \
           "transpose(jvp())/selec" in names
    assert "fusion %fusion.607" in names            # unscoped: as it was
    for (was, _), (now, _) in zip(bare, top):
        assert now == was or now.startswith(was + " ")
