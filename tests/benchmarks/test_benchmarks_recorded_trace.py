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
