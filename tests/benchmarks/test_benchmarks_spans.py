"""The nine per-layer metrics that read the program's always-on span ring
(PR 25): their manifest entries, each reader on the toy cell's CPU
rehearsal, and each reader on a hand-built run -- the recorded four-chip
trace's tables plus a few synthetic main-thread and pool rows -- against
values worked out by hand.  A program without the ring gives every reader
nothing."""

import json
import os
from collections import deque

import pytest

from benchmarks import harness, spans, trace
from theanompi_tpu.utils import telemetry

NEW = ["xla_compile_s", "produce_ms", "device_put_ms", "batch_queue_wait_ms",
       "pool_busy_share", "unready_dequeue_share", "train_call_ms",
       "small_programs_ms", "idle_in_call_share"]
CELLS = ["vgg16-b384-bsp-1chip", "vgg16-b384-bsp-4chip"]
TOY = "toy-b8-bsp-1chip"
RECORDED = os.path.join(harness.ROOT, "benchmarks", "testdata",
                        "v5e_vgg16_b384_bsp_4chip")
MS = 1_000_000


def _reader(manifest, name):
    return harness.load_module(manifest, "layer_metrics", name).read


# -- the manifest --------------------------------------------------------------

def test_new_entries_are_appended_and_apply_to_every_cell(manifest):
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-len(NEW):] == NEW
    assert names[:9] == ["compile_s", "first_step_s", "load_wait_share",
                         "host_dispatch_ms", "step_device_ms",
                         "step_roofline_share", "exchange_device_ms",
                         "exchange_exposed_ms", "device_idle_share"]
    for m in manifest["per_layer"][-len(NEW):]:
        # unlisted since PR 27: a later cell reads them with no edit to
        # these entries (a listed metric would have to be appended to)
        assert "workloads" not in m
        assert m["source"] == ("program_counter"
                               if m["name"] == "unready_dequeue_share"
                               else "program_span")
        assert m["moves"] == ("setup_s" if m["name"] == "xla_compile_s"
                              else "train_throughput")
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    assert layers["xla_compile_s"] == "compile"
    assert layers["idle_in_call_share"] == "device"
    assert {layers[n] for n in ("train_call_ms", "small_programs_ms")} == \
        {"worker loop"}
    assert {layers[n] for n in NEW[1:6]} == {"input pipeline"}


# -- the toy cell's CPU rehearsal ----------------------------------------------

@pytest.fixture(scope="module")
def span_manifest(toy_manifest):
    """The toy manifest as it is: the nine entries name no cell, so they
    apply to the toy's as to every other."""
    assert all(TOY in m.get("workloads", [TOY])
               for m in toy_manifest["per_layer"] if m["name"] in NEW)
    return toy_manifest


@pytest.fixture(scope="module")
def traced(span_manifest):
    # the ring's totals are the process's: what tests before this one
    # compiled in the same process is in them already
    return harness.run_cell(span_manifest, TOY, seed=5, seconds=2.0,
                            trace=True)


@pytest.fixture(scope="module")
def compiled_before():
    return telemetry.totals().get("compile.xla", (0, 0))[1] / 1e9


def test_span_readers_read_the_ring_on_the_cpu_rehearsal(compiled_before,
                                                         traced,
                                                         span_manifest):
    assert traced.problems == []
    got = harness.read_metrics(span_manifest, traced.cell.per_layer,
                               "layer_metrics", traced)
    # every reader that needs no device plane reads; a CPU trace has none
    assert set(NEW) - set(got) == {"idle_in_call_share"}
    v = {k: got[k]["value"] for k in NEW if k in got}
    assert all(x == x and abs(x) != float("inf") for x in v.values())
    assert 0 < v["xla_compile_s"] - compiled_before < traced.setup_s
    assert 0 < v["device_put_ms"] < v["produce_ms"]
    assert v["batch_queue_wait_ms"] >= 0
    assert 0 < v["pool_busy_share"] <= 100
    assert 0 <= v["unready_dequeue_share"] <= 100
    assert 0 < v["small_programs_ms"] and 0 < v["train_call_ms"]
    # the parts of the `train` bracket do not outgrow the bracket
    dispatch = got["host_dispatch_ms"]["value"]
    assert v["train_call_ms"] + v["small_programs_ms"] <= dispatch * 1.05
    # the stretch on the Unix clock brackets the harness's own count
    (lo, hi), rows = spans.rows_in_stretch(traced)
    assert (hi - lo) / 1e9 == pytest.approx(traced.traced.seconds, rel=0.05)
    calls = [r for r in rows if r[0] == "train.call" and lo <= r[2]
             and r[3] <= hi]
    assert abs(len(calls) - traced.traced.steps) <= 1
    assert len(spans.dequeued(rows, (lo, hi))) == pytest.approx(
        traced.traced.steps, abs=1)


def test_without_tables_every_span_reader_returns_nothing(traced,
                                                          span_manifest):
    bare = harness.Run(traced.cell, dict(traced.device))
    for name in NEW:
        assert _reader(span_manifest, name)(bare) is None, name


def test_a_program_without_the_ring_gives_nothing(traced, span_manifest,
                                                  monkeypatch):
    """The parent commit's ``telemetry`` has no ``spans``: the readers must
    return None there, not raise (the driver lays these files over the
    parent's checkout for its traced runs)."""
    monkeypatch.delattr(telemetry, "spans")
    assert spans.ring() is None
    for name in NEW:
        assert _reader(span_manifest, name)(traced) is None, name


# -- a hand-built run on the recorded four-chip tables --------------------------

@pytest.fixture
def recorded(manifest, monkeypatch):
    """A run whose device side is the recorded v5e trace (chip 0, two steps,
    a window of 698.815265 ms of which 18.78% idle) and whose ring holds
    what each test puts there, on the Unix clock."""
    with open(RECORDED + ".json") as f:
        expect = json.load(f)
    run = harness.Run(harness.load_cell(manifest, CELLS[1]),
                      {"platform": "tpu", "kind": "TPU v5 lite", "count": 4})
    run.tables = trace.load_text_proto(RECORDED + ".pbtxt")
    run.trace_window = tuple(expect["window"])
    run.traced = harness.Stretch(seconds=0.698815265, steps=2)
    ring = deque(maxlen=64)
    monkeypatch.setattr(telemetry, "_ring", ring)
    monkeypatch.setattr(telemetry, "_totals", {})
    lo = expect["start_unix_ns"] + expect["window"][0]

    def put(name, tid, t0_ms, t1_ms, batch=None):
        ring.append((name, tid, lo + int(t0_ms * MS), lo + int(t1_ms * MS),
                     None, batch))

    return run, put, expect


def test_stretch_on_the_unix_clock(recorded):
    run, put, expect = recorded
    lo, hi = spans.stretch(run)
    assert lo == expect["start_unix_ns"] + expect["window"][0]
    assert hi - lo == 698_815_265


def test_idle_in_call_share_by_hand(recorded, manifest):
    run, put, expect = recorded
    read = _reader(manifest, "idle_in_call_share")
    assert read(run) is None                         # no train.call row
    # the main thread inside train.call for the whole stretch (and beyond):
    # all of the chip's idle time is idle in the call
    put("train.call", 1, -5, 800)
    assert read(run) == pytest.approx(expect["idle_share_pct"], abs=0.01)
    # ... inside it for none of the stretch: nothing is
    telemetry._ring.clear()
    put("train.call", 1, -50, 0)                     # ends as it begins
    assert read(run) == pytest.approx(0.0, abs=1e-9)
    telemetry._ring.clear()
    put("train.call", 1, 700, 750)                   # after it: no row
    assert read(run) is None
    # two calls that split the stretch at 300 ms: the parts add up, and each
    # is the idle share of its part of the window
    w = run.trace_window
    mid = w[0] + 300 * MS
    ops = run.tables.devices[0].ops
    first = 100 * trace.idle_share(ops, (w[0], mid)) * (mid - w[0]) \
        / (w[1] - w[0])
    telemetry._ring.clear()
    put("train.call", 1, 0, 300)
    assert read(run) == pytest.approx(first, abs=0.01)
    assert 0 < first < expect["idle_share_pct"]
    put("train.call", 1, 300, 698.815265)
    put("train.reduce", 1, 100, 600)                 # another span: ignored
    assert read(run) == pytest.approx(expect["idle_share_pct"], abs=0.01)


def _pool_rows(put):
    """Two pool threads and the consumer.  Thread 7 materializes over
    [0, 300) ms; thread 8 stages over [100, 200) ms and materializes over
    [648.815265, 748.815265) ms, 50 ms of it inside the stretch: 450 ms of
    pool time.  Three batches are dequeued in the stretch."""
    put("input.materialize", 7, 0, 300, batch=11)
    put("input.device_put", 8, 100, 200, batch=10)
    put("input.materialize", 8, 648.815265, 748.815265, batch=12)
    put("input.device_put", 7, -40, -30, batch=9)    # before the stretch
    put("load.dequeue", 1, 10, 12, batch=9)          # waited 40 ms
    put("load.dequeue", 1, 290, 291, batch=10)       # waited 90 ms
    put("load.dequeue", 1, 500, 620, batch=11)       # never staged: no wait
    put("load.dequeue", 1, 690, 700, batch=12)       # ends after the stretch


def test_pool_busy_share_and_produce_ms_by_hand(recorded, manifest):
    run, put, expect = recorded
    for name in ("pool_busy_share", "produce_ms", "device_put_ms",
                 "batch_queue_wait_ms"):
        assert _reader(manifest, name)(run) is None, name
    _pool_rows(put)
    stretch_ms = 698.815265
    assert _reader(manifest, "pool_busy_share")(run) == pytest.approx(
        100 * 450 / (2 * stretch_ms), rel=1e-6)      # 32.197...%
    assert _reader(manifest, "produce_ms")(run) == pytest.approx(450 / 3)
    assert _reader(manifest, "device_put_ms")(run) == pytest.approx(100 / 3)
    # batch 9 waited 10-(-30) = 40 ms, batch 10 waited 290-200 = 90 ms
    assert _reader(manifest, "batch_queue_wait_ms")(run) == \
        pytest.approx((40 + 90) / 2)
    # a third pool thread seen in the stretch widens the denominator
    put("input.device_put", 9, 600, 601, batch=13)
    assert _reader(manifest, "pool_busy_share")(run) == pytest.approx(
        100 * 451 / (3 * stretch_ms), rel=1e-6)


def test_worker_loop_and_counter_readers_by_hand(recorded, manifest,
                                                 monkeypatch):
    run, put, expect = recorded
    for name in ("train_call_ms", "small_programs_ms",
                 "unready_dequeue_share", "xla_compile_s"):
        assert _reader(manifest, name)(run) is None, name
    put("train.args", 1, 0, 2)
    put("train.call", 1, 2, 300)
    put("train.reduce", 1, 300, 301)
    put("train.args", 1, 349, 351)
    put("train.call", 1, 351, 720)               # 347.815265 ms inside
    assert _reader(manifest, "train_call_ms")(run) == pytest.approx(
        (298 + 347.815265) / 2)
    assert _reader(manifest, "small_programs_ms")(run) == pytest.approx(
        (2 + 1 + 2) / 2)
    monkeypatch.setattr(telemetry, "_totals", {
        ("input.dequeues", 1): [40, 0],
        ("input.unready_dequeues", 1): [10, 0],
        # 2.5 s on the main thread and 0.5 s on another, of which the
        # ring still holds a 0.25 s compile that ended inside the stretch
        ("compile.xla", 1): [3, 2_500 * MS], ("compile.xla", 5): [1, 500 * MS],
        ("compile.cache_load", 1): [3, 900 * MS]})
    assert _reader(manifest, "unready_dequeue_share")(run) == \
        pytest.approx(25.0)
    assert _reader(manifest, "xla_compile_s")(run) == pytest.approx(3.0)
    put("compile.xla", 5, -100, 150)
    assert _reader(manifest, "xla_compile_s")(run) == pytest.approx(2.75)
    put("compile.xla", 1, -900, -400)            # before the stretch: stays
    assert _reader(manifest, "xla_compile_s")(run) == pytest.approx(2.75)
