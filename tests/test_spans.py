"""The always-on host span ring in ``utils/telemetry`` (PR 25): the ring
itself, the sites that feed it (recorder, loader, ``train_iter``, the
``jax.monitoring`` listener), and ``devprof``'s use of it beside a capture
(``host_spans.jsonl``, idle by host span, the recorded v5e trace)."""

import json
import os
import threading
import time
import timeit
from collections import deque

import numpy as np
import pytest

import theanompi_tpu as tmpi
from theanompi_tpu.models.data.imagenet import ImageNet_data
from theanompi_tpu.models.data.prefetch import PrefetchLoader
from theanompi_tpu.utils import devprof, telemetry
from theanompi_tpu.utils.recorder import Recorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(REPO, "benchmarks", "testdata",
                        "v5e_vgg16_b384_bsp_4chip")


@pytest.fixture(autouse=True)
def fresh_ring(monkeypatch):
    """Each test reads only its own rows and totals."""
    monkeypatch.setattr(telemetry, "_ring",
                        deque(maxlen=telemetry.RING_SPANS))
    monkeypatch.setattr(telemetry, "_totals", {})
    yield
    telemetry.init({})


def rows(name=None):
    return [r for r in telemetry.spans() if name is None or r[0] == name]


# -- the ring ----------------------------------------------------------------

def test_nesting_reentrancy_parent_and_self_time():
    with telemetry.span("train.call", batch=7):
        time.sleep(0.002)
        with telemetry.span("train.args", batch=7):
            time.sleep(0.002)
            with telemetry.span("train.args"):       # re-entrant
                pass
    got = telemetry.spans()
    assert [r[0] for r in got] == ["train.args", "train.args", "train.call"]
    inner2, inner, outer = got
    assert outer[4] is None and inner[4] == "train.call" \
        and inner2[4] == "train.args"
    assert outer[5] == 7 and inner[5] == 7 and inner2[5] is None
    assert len({r[1] for r in got}) == 1            # one thread
    assert outer[2] <= inner[2] <= inner[3] <= outer[3]
    # self time: a span's length less its children's
    self_ns = (outer[3] - outer[2]) - (inner[3] - inner[2])
    assert 1e6 < self_ns < (outer[3] - outer[2])
    tot = telemetry.totals()
    assert tot["train.call"][0] == 1 and tot["train.args"][0] == 2
    assert tot["train.call"][1] == outer[3] - outer[2]


def test_an_exception_closes_the_span_and_unwinds_the_stack():
    with pytest.raises(ValueError):
        with telemetry.span("train.call"):
            with telemetry.span("train.args"):
                raise ValueError("x")
    with telemetry.span("print"):
        pass
    assert [(r[0], r[4]) for r in telemetry.spans()] == [
        ("train.args", "train.call"), ("train.call", None),
        ("print", None)]


def test_rows_from_several_threads_keep_their_own_parents():
    barrier = threading.Barrier(4)

    def work(i):
        barrier.wait()
        for _ in range(50):
            with telemetry.span("input.materialize", batch=i):
                with telemetry.span("input.device_put", batch=i):
                    telemetry.count("input.bytes_put", 10)

    # a short switch interval makes a lost update likely if the totals
    # had two writers to a cell
    import sys
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        with telemetry.span("train.call"):
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    got = telemetry.spans()
    assert len(got) == 4 * 50 * 2 + 1
    assert len({r[1] for r in got}) == 5
    # a pool thread's span never takes the main thread's as parent
    assert {r[4] for r in got if r[0] == "input.device_put"} == \
        {"input.materialize"}
    assert {r[4] for r in got if r[0] == "input.materialize"} == {None}
    by_thread = {}
    for r in rows("input.materialize"):
        by_thread.setdefault(r[1], set()).add(r[5])
    assert sorted(map(sorted, by_thread.values())) == [[0], [1], [2], [3]]
    tot = telemetry.totals()
    assert tot["input.materialize"][0] == 200       # no lost update: one
    assert tot["input.bytes_put"] == (2000, 0)      # writer per cell
    assert devprof.thread_classes(got)[threading.get_ident()] == "main"


def test_eviction_at_maxlen_leaves_the_totals_intact(monkeypatch):
    monkeypatch.setattr(telemetry, "_ring", deque(maxlen=8))
    for i in range(20):
        with telemetry.span("train.call", batch=i):
            pass
    got = telemetry.spans()
    assert [r[5] for r in got] == list(range(12, 20))
    assert telemetry.totals()["train.call"][0] == 20
    assert telemetry.RING_SPANS >= 20 * 20 * 30     # 20 s, 20 steps/s, 30


def test_spans_filters_by_interval_and_maps_onto_the_trace_clock():
    with telemetry.span("load.dequeue"):
        pass
    mid = time.time_ns()
    time.sleep(0.001)
    with telemetry.span("train.call"):
        pass
    assert [r[0] for r in telemetry.spans(t1_ns=mid)] == ["load.dequeue"]
    assert [r[0] for r in telemetry.spans(t0_ns=mid)] == ["train.call"]
    r = rows("train.call")[0]
    assert abs(r[2] - time.time_ns()) < 5e9          # the Unix clock
    start = r[2] - 1234
    assert telemetry.on_trace_clock(r[2], start) == 1234
    assert telemetry.on_trace_clock(r[3], start) == 1234 + r[3] - r[2]


def test_a_span_costs_microseconds_and_needs_no_registry():
    assert telemetry.active() is telemetry.DISABLED

    def one():
        with telemetry.span("train.call", 3):
            pass

    n = 20000
    per_span = timeit.timeit(one, number=n) / n
    assert per_span < 20e-6, per_span       # ~1.3 us measured; 30 a step
    per_count = timeit.timeit(lambda: telemetry.count("input.dequeues"),
                              number=n) / n
    assert per_count < 10e-6, per_count


def test_vocabulary_is_one_tuple_beside_the_phases():
    assert not set(telemetry.SPANS) & set(telemetry.PHASES)
    assert set(telemetry.COMPILE_EVENTS.values()) <= set(telemetry.SPANS)
    assert len(set(telemetry.SPANS)) == len(telemetry.SPANS) == 13
    assert len(set(telemetry.COUNTS)) == len(telemetry.COUNTS) == 26


# -- the recorder, the registry, the listener --------------------------------

def test_recorder_end_writes_a_row_and_parents_the_spans_inside():
    rec = Recorder({"verbose": False})
    rec.start()
    with telemetry.span("train.call"):
        time.sleep(0.001)
    dt = rec.end("train")
    got = telemetry.spans()
    assert [(r[0], r[4]) for r in got] == [("train.call", "train"),
                                           ("train", None)]
    assert dt == pytest.approx((got[1][3] - got[1][2]) / 1e9)
    assert rec.t_sec_total["train"] == rec.t_sec["train"] == dt
    # a second start() supersedes the first; nothing stays on the stack
    rec.start()
    rec.start()
    rec.end("load")
    with telemetry.span("print"):
        pass
    assert [(r[0], r[4]) for r in telemetry.spans()[2:]] == [
        ("load", None), ("print", None)]
    with pytest.raises(AssertionError):
        rec.end("load")


def test_print_span_wraps_only_the_materialising_branch():
    rec = Recorder({"verbose": False, "printFreq": 4})
    for count in range(1, 9):
        rec.start()
        rec.end("train")
        rec.train_error(count, 1.0, 0.5, 8)
        rec.print_train_info(count)
    assert len(rows("print")) == 2 and len(rows("train")) == 8


def test_enabled_registry_gets_phase_samples_for_the_new_names():
    tm = telemetry.init({"telemetry": True})
    with telemetry.span("input.materialize", batch=3):
        pass
    rec = Recorder({"verbose": False})
    rec.telemetry = tm
    rec.start()
    rec.end("train")
    assert tm.hists["phase.input.materialize"].count == 1
    assert tm.hists["phase.train"].count == 1       # once, not twice
    evs = [e for e in tm.tail(8) if e["ev"] == "phase"]
    assert [e["sec"] for e in evs] == ["input.materialize", "train"]
    row = rows("input.materialize")[0]
    assert evs[0]["t0"] == row[2] and evs[0]["tid"] == row[1]
    assert evs[1]["t0"] == rows("train")[0][2]
    # disabled again: the ring goes on, the registry sees nothing
    telemetry.init({})
    with telemetry.span("input.materialize"):
        pass
    assert len(rows("input.materialize")) == 2
    assert tm.hists["phase.input.materialize"].count == 1


def test_monitoring_listener_turns_a_compile_into_a_span():
    import jax
    import jax.numpy as jnp
    telemetry.watch_compiles()
    telemetry.watch_compiles()                       # registers once
    with telemetry.span("train.call"):
        jax.jit(lambda x: x * 3.25 + len(rows()))(jnp.ones(7)) \
            .block_until_ready()
    got = rows("compile.xla")
    assert len(got) >= 1
    call = rows("train.call")[0]
    assert all(r[4] == "train.call" for r in got)
    assert all(r[3] - r[2] > 0 and r[3] <= call[3] for r in got)
    n = len(got)
    jax.jit(lambda x: x * 3.25 + 1)(jnp.ones(7)).block_until_ready()
    assert len(rows("compile.xla")) == n + 1


# -- the loader ---------------------------------------------------------------

def _imagenet(batch_size=4):
    return ImageNet_data({"synthetic_batches": 64, "size": 1, "rank": 0,
                          "n_class": 10}, batch_size, crop=16)


@pytest.mark.parametrize("producer", ["serial", "pooled", "window"])
def test_every_producer_leaves_the_same_input_spans(producer):
    import jax
    put = lambda b: jax.tree.map(jax.device_put, b)    # noqa: E731
    loader = PrefetchLoader(_imagenet(), device_put_fn=put,
                            n_workers=1 if producer == "serial" else 3)
    if producer == "window":
        loader.set_window(2, put)
    loader.shuffle_data(0)
    try:
        seen, returned = [], {}
        for i in range(1, 7):
            if producer == "window":
                loader.next_train_window(2 * i)
            else:
                loader.next_train_batch(i)
            seen.append(loader.last_batch_id)
            returned[loader.last_batch_id] = time.time_ns()
    finally:
        loader._shutdown()
    assert seen == sorted(set(seen)) and len(seen) == 6
    got = telemetry.spans()
    names = {r[0] for r in got}
    assert {"input.plan", "input.materialize", "input.device_put",
            "input.enqueue", "load.dequeue"} <= names
    classes = devprof.thread_classes(got)
    main = threading.get_ident()
    assert classes[main] == "main"
    assert list(classes.values()).count("producer") == 1
    assert ("pool" in classes.values()) == (producer != "serial")
    # the consumer's batch was planned, materialized, staged and enqueued
    # under the same id, on other threads
    for bid in seen:
        mine = [r for r in got if r[5] == bid]
        assert {"input.plan", "input.materialize", "input.device_put",
                "input.enqueue", "load.dequeue"} <= {r[0] for r in mine}
        put_end = max(r[3] for r in mine if r[0] == "input.device_put")
        deq = next(r for r in mine if r[0] == "load.dequeue")
        # in hand once the loader's call returns: after the dequeue and,
        # where the pool hands over a future, after its result
        assert deq[1] == main and deq[3] <= returned[bid]
        assert put_end <= returned[bid]
    tot = telemetry.totals()
    assert tot["input.dequeues"][0] == 6
    assert 0 <= tot.get("input.unready_dequeues", (0, 0))[0] <= 6
    per_put = 4 * 16 * 16 * 3 + 4 * 4              # uint8 x, int32 y
    assert tot["input.bytes_put"][0] >= 6 * per_put * \
        (2 if producer == "window" else 1)
    assert tot["input.bytes_put"][0] % per_put == 0


def test_a_producers_error_surfaces_at_the_dequeue():
    class Broken:
        n_batch_train = 4

        def shuffle_data(self, seed):
            pass

        def next_train_batch(self, count):
            raise RuntimeError("disk gone")

    loader = PrefetchLoader(Broken())
    loader.shuffle_data(0)
    with pytest.raises(RuntimeError, match="disk gone"):
        loader.next_train_batch(1)
    loader._shutdown()
    assert [r[5] for r in rows("load.dequeue")] == [None]


def test_one_batch_id_runs_from_the_plan_to_the_step():
    """A session with the parallel loader on: the id of the batch a step
    trained on names that batch's spans on the producer, the pool and the
    main thread."""
    rule = tmpi.BSP()
    rule.init(devices=2, modelfile="tests.benchmarks.bench_toy_model",
              modelclass="ToyNet", epochs=1, batch_size=4, n_class=10,
              synthetic_batches=6, para_load=True, para_load_workers=2,
              verbose=False, scale_lr=False, printFreq=3)
    rule.wait()
    got = telemetry.spans()
    calls = rows("train.call")
    assert len(calls) == 6 and all(r[5] is not None for r in calls)
    for call in calls:
        mine = {r[0]: r for r in got if r[5] == call[5]}
        assert {"input.plan", "input.materialize", "input.device_put",
                "input.enqueue", "load.dequeue", "train.args",
                "train.call", "train.reduce"} <= set(mine)
        # in hand (dequeued and, a future, its result there) before the
        # step's arguments are made
        assert mine["input.plan"][2] <= mine["input.materialize"][2] \
            <= mine["input.device_put"][3] \
            <= mine["train.args"][2] <= mine["train.call"][2] \
            <= mine["train.reduce"][3]
        assert mine["load.dequeue"][2] <= mine["train.args"][2]
        assert mine["train.call"][4] == "train"
        assert mine["load.dequeue"][4] == "load"
        assert len({mine["input.plan"][1], mine["input.materialize"][1],
                    mine["train.call"][1]}) == 3
    # the first call compiled the step program; the listener saw it there
    xla = rows("compile.xla")
    assert any(r[4] == "train.call" and calls[0][2] <= r[2]
               and r[3] <= calls[0][3] for r in xla)
    place = rows("compile.place")
    assert len(place) == 1 and place[0][4] == "compile"
    assert len(rows("print")) == 2
    tot = telemetry.totals()
    assert tot["input.dequeues"][0] == 6
    assert tot.get("input.unready_dequeues", (0, 0))[0] <= 6


# -- devprof: the capture's host side -----------------------------------------

def _op(ts_us, dur_us, name, lane="XLA Ops"):
    return {"ph": "X", "_src": 0, "pid": "/device:TPU:0", "tid": lane,
            "ts": float(ts_us), "dur": float(dur_us), "name": name,
            "args": {"hlo_op": name, "hlo_module": "jit_per_worker"}}


def test_idle_by_span_per_thread_class_worked_by_hand():
    """Chip 0 busy [0,100) and [300,400) us of a [0,500) us interval: 300
    us idle.  Main sits in train.call (inside train) over [50,450): 250 us
    of the idle, the other 50 under no span.  One pool thread
    materializes over [100,300): 200 us; the other is in device_put over
    [200,500): 200 us; mean over the two pool threads."""
    start = 1_000_000_000
    us = lambda t: start + t * 1000                 # noqa: E731
    host = [("train", 1, us(0), us(450), None, 5),
            ("train.call", 1, us(50), us(450), "train", 5),
            ("print", 1, us(450), us(500), None, None),
            ("input.plan", 2, us(0), us(10), None, 6),
            ("input.enqueue", 2, us(10), us(500), None, 6),
            ("input.materialize", 3, us(100), us(300), None, 6),
            ("input.device_put", 4, us(200), us(500), None, 7)]
    events = [_op(0, 100, "fusion.1"), _op(300, 100, "all-reduce")]
    idle = devprof.idle_by_span(events, host, start)
    assert idle["idle_secs"] == pytest.approx(300e-6)
    assert idle["window_secs"] == pytest.approx(500e-6)
    assert idle["threads"] == {"main": 1, "producer": 1, "pool": 2}
    by = {cls: dict(v) for cls, v in idle["by_class"].items()}
    assert by["main"] == pytest.approx({"train.call": 250e-6,
                                        "print": 50e-6})
    assert by["producer"] == pytest.approx({"input.enqueue": 300e-6})
    assert by["pool"] == pytest.approx({
        "input.materialize": 100e-6, "input.device_put": 100e-6,
        "(none)": 100e-6})
    for v in by.values():
        assert sum(v.values()) == pytest.approx(300e-6)
    prof = devprof.attribute(events, host)
    assert prof["train_dispatches"] == 1
    prof["idle_by_span"] = idle
    text = devprof.format_profile(prof)
    assert "device idle by host span" in text
    assert "pool (2 thread(s))" in text and "train.call" in text


def test_recorded_v5e_trace_reads_through_profile_data():
    """The four-chip VGG-16 trace kept with the benchmark, through
    ``ProfileData.from_text_proto`` and ``xplane_events``: TPU planes,
    opcodes parsed from the instruction text, the three synchronous
    all-reduces of each step wholly exposed."""
    from jax.profiler import ProfileData
    with open(RECORDED + ".json") as f:
        expect = json.load(f)
    with open(RECORDED + ".pbtxt") as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    events, start_ns = devprof.xplane_events(raw)
    assert start_ns == expect["start_unix_ns"]
    assert len(events) == expect["ops"]
    assert {e["pid"] for e in events} == {"/device:TPU:0"}
    assert "jit_per_worker" in {e["args"]["hlo_module"] for e in events}
    by = {}
    for e in events:
        by[e["name"]] = by.get(e["name"], 0) + 1
    assert by["all-reduce"] == expect["opcode_events"]["all-reduce"]
    assert not any(n.startswith("%") or " = " in n for n in by)
    lo, hi = (t / 1e3 for t in expect["window"])
    inside = [e for e in events
              if e["args"]["hlo_module"] == "jit_per_worker"
              and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]
    prof = devprof.attribute(inside)
    steps = expect["steps"]
    assert prof["comm_secs"] / steps * 1e3 == pytest.approx(
        expect["exchange_device_ms"], rel=0.02)
    assert prof["exposed_comm_secs"] == pytest.approx(prof["comm_secs"])
    assert prof["overlap_ratio"] == pytest.approx(0.0, abs=1e-3)
    assert prof["lanes"] == 1 and prof["compute_secs"] > 0.4
    top = {o["op"]: o for o in prof["top_ops"]}
    assert top["all-reduce"]["comm"] and top["fusion"]["secs"] > 0.3


def test_cpu_capture_names_collectives_by_opcode(tmp_path):
    """On the CPU backend an op event is named after the jax primitive
    (``psum_invariant.7``); the opcode comes from the HloProto embedded in
    the trace."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from theanompi_tpu.jax_compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:2]), ("workers",))
    g = jax.jit(shard_map(lambda x: jax.lax.psum(x * 2.0, "workers"),
                          mesh=mesh, in_specs=P("workers"), out_specs=P()))
    x = jnp.arange(8.0)
    g(x).block_until_ready()
    with devprof.capture(str(tmp_path)) as cap:
        with telemetry.span("train.call"):
            g(x).block_until_ready()
    path = devprof.find_xplane_files(str(tmp_path))[0]
    with open(path, "rb") as f:
        raw = f.read()
    opcodes = devprof._hlo_opcodes(raw)
    assert "all-reduce" in opcodes.values()
    events, start_ns = devprof.xplane_events(raw)
    assert start_ns is not None and abs(start_ns - time.time_ns()) < 60e9
    assert any(e["name"] == "all-reduce"
               and e["args"]["hlo_op"].startswith("psum") for e in events)
    assert cap.profile["train_dispatches"] == 1      # off the ring
    assert cap.profile["comm_secs"] > 0


def test_worker_trace_dir_leaves_host_spans_on_the_traces_clock(tmp_path,
                                                                capsys):
    trace_dir = str(tmp_path / "trace")
    rule = tmpi.BSP()
    rule.init(devices=2, modelfile="tests.benchmarks.bench_toy_model",
              modelclass="ToyNet", epochs=1, batch_size=4, n_class=10,
              synthetic_batches=10, para_load=True, para_load_workers=2,
              verbose=True, scale_lr=False, printFreq=4,
              trace_dir=trace_dir, trace_start=3, trace_iters=4)
    rule.wait()
    out = capsys.readouterr().out
    path = os.path.join(trace_dir, devprof.HOST_SPANS_FILE)
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    head, body = recs[0], recs[1:]
    assert head["t0"] < head["t1"]
    assert abs(head["profile_start_time"] - head["t0"]) < 30e9
    assert all(r["t1"] >= head["t0"] and r["t0"] <= head["t1"]
               for r in body)
    names = {r["name"] for r in body}
    assert {"train", "train.call", "load.dequeue", "input.materialize",
            "input.device_put"} <= names
    assert 3 <= sum(r["name"] == "train.call" for r in body) <= 6
    assert devprof.read_host_spans(trace_dir)[0][0] == body[0]["name"]
    prof = devprof.profile_dir(trace_dir)
    assert prof["train_dispatches"] == \
        sum(r["name"] == "train.call" for r in body)
    assert prof["compute_secs"] > 0 and prof["comm_secs"] > 0
    assert set(prof["idle_by_span"]["by_class"]) == {"main", "producer",
                                                     "pool"}
    assert "device idle by host span" in out
    opts = devprof.profile_options()
    assert opts.python_tracer_level == 0             # CPU: the op events
    assert opts.host_tracer_level != 0               # are host events


def test_report_draws_ring_spans_on_their_threads_tracks(tmp_path):
    """``telemetry_report --trace``: a ``phase`` event with ``t0``/``tid``
    (a ring span) starts at its own ``t0`` and, from a thread other than
    the recorder's, lies on a track of its own."""
    import subprocess
    import sys
    d = tmp_path / "rec"
    d.mkdir()
    t0 = 2000.0
    ns = lambda s: int((t0 + s) * 1e9)              # noqa: E731
    events = [
        {"ts": t0, "ev": "run_start", "schema": 1},
        {"ts": t0 + 1.0, "ev": "phase", "sec": "train", "dt": 0.5,
         "t0": ns(0.5), "tid": 111},
        {"ts": t0 + 0.9, "ev": "phase", "sec": "train.call", "dt": 0.3,
         "t0": ns(0.6), "tid": 111},
        {"ts": t0 + 0.95, "ev": "phase", "sec": "input.materialize",
         "dt": 0.6, "t0": ns(0.35), "tid": 222},
        {"ts": t0 + 0.99, "ev": "phase", "sec": "input.device_put",
         "dt": 0.2, "t0": ns(0.79), "tid": 333},
        {"ts": t0 + 1.2, "ev": "phase", "sec": "comm", "dt": 0.2}]
    with open(d / "telemetry_rank0.jsonl", "w") as f:
        for ev in events:
            f.write(json.dumps({"run": "r", "rank": 0, **ev}) + "\n")
    out = str(tmp_path / "trace.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/telemetry_report.py"),
         str(d), "--trace", out], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "input.materialize" in r.stdout           # the phase table
    evs = json.load(open(out))["traceEvents"]
    x = {e["name"]: e for e in evs if e.get("ph") == "X"}
    assert x["train"]["tid"] == x["train.call"]["tid"] == x["comm"]["tid"] \
        == 0
    assert {x["input.materialize"]["tid"], x["input.device_put"]["tid"]} \
        == {10, 11}
    assert x["input.materialize"]["ts"] == pytest.approx(0.35e6, abs=1e3)
    assert x["input.materialize"]["dur"] == pytest.approx(0.6e6, abs=1e3)
    assert x["comm"]["ts"] == pytest.approx(1.0e6, abs=1e3)
    names = {e["tid"]: e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    assert names[10] == "host thread 1" and names[11] == "host thread 2"
