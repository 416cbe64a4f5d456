"""Device-time attribution (ISSUE 7 tentpole): the devprof trace parser
and interval math, the live capture→attribution round-trip on a psum
program, the training sentry, the Perfetto trace export, and the
compile-cache cost manifests + explain CLI."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import theanompi_tpu as tmpi
from theanompi_tpu.utils import devprof, sentry, telemetry
from theanompi_tpu.utils.sentry import TrainingSentry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    yield
    telemetry.init({})


def _op(ts, dur, name, pid=1, tid=1, module="jit_step"):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": float(ts),
            "dur": float(dur), "name": name,
            "args": {"hlo_op": name, "hlo_module": module}}


# -- attribution math -------------------------------------------------------

def test_attribute_exposed_comm_and_overlap():
    """One lane: compute [0,50], comm [40,60] → 10us of the 20us
    collective is exposed, overlap ratio 0.5."""
    prof = devprof.attribute([
        _op(0, 50, "fusion.1"),
        _op(40, 20, "all-reduce.1"),
    ])
    assert prof["compute_secs"] == pytest.approx(50e-6)
    assert prof["comm_secs"] == pytest.approx(20e-6)
    assert prof["exposed_comm_secs"] == pytest.approx(10e-6)
    assert prof["overlap_ratio"] == pytest.approx(0.5)
    assert prof["lanes"] == 1 and prof["n_op_events"] == 2


def test_attribute_nested_and_multi_lane():
    """Nested compute spans union-merge (no double count); lanes are
    independent — lane A's compute can't hide lane B's collective."""
    prof = devprof.attribute([
        _op(0, 100, "while.2"),                 # outer
        _op(10, 20, "fusion.3"),                # nested inside — no extra
        _op(0, 40, "all-reduce.1", tid=2),      # other lane: fully exposed
    ])
    assert prof["compute_secs"] == pytest.approx(100e-6)
    assert prof["comm_secs"] == pytest.approx(40e-6)
    assert prof["exposed_comm_secs"] == pytest.approx(40e-6)
    assert prof["overlap_ratio"] == pytest.approx(0.0)
    assert prof["lanes"] == 2


def test_attribute_cross_host_lane_ids_do_not_collide():
    """Per-host capture files reuse the same small pid/tid integers —
    profile_dir tags each file's events with _src, and attribute() keys
    lanes on it, so host A's compute can't mask host B's collective as
    overlap (it stays fully exposed)."""
    prof = devprof.attribute([
        dict(_op(0, 100, "fusion.1"), _src=0),
        dict(_op(0, 40, "all-reduce.1"), _src=1),   # same pid/tid, host B
    ])
    assert prof["lanes"] == 2
    assert prof["exposed_comm_secs"] == pytest.approx(40e-6)
    assert prof["overlap_ratio"] == pytest.approx(0.0)


def test_attribute_fully_hidden_comm_and_async_names():
    """An async-pair collective entirely under compute → overlap 1.0;
    -start/-done forms classify as comm and MERGE into one interval
    spanning the whole in-flight window (start-begin → done-end), so
    comm_secs counts the collective's true 60us, not two slivers."""
    prof = devprof.attribute([
        _op(0, 100, "fusion.1"),
        _op(10, 5, "all-gather-start.2"),
        _op(60, 10, "all-gather-done.2"),
    ])
    assert prof["comm_secs"] == pytest.approx(60e-6)
    assert prof["exposed_comm_secs"] == pytest.approx(0.0)
    assert prof["overlap_ratio"] == pytest.approx(1.0)
    comm_ops = {o["op"] for o in prof["top_ops"] if o["comm"]}
    assert comm_ops == {"all-gather-start", "all-gather-done"}


def test_attribute_async_pair_on_dedicated_stream_counts_once():
    """The round-9 lane-classification fix: a runtime that parks the
    ``-done`` on a dedicated async-collective stream (its own tid, no
    compute) must not read as a SECOND, fully-exposed collective — the
    pair merges into ONE start-to-done interval on the ISSUING lane,
    where the overlapping compute hides it."""
    prof = devprof.attribute([
        _op(0, 100, "fusion.1"),                       # compute, lane 1
        _op(10, 5, "all-reduce-start.3"),              # issued on lane 1
        _op(60, 10, "all-reduce-done.3", tid=2),       # waited on stream
    ])
    # one merged interval [10, 70] on lane 1, fully under compute
    assert prof["comm_secs"] == pytest.approx(60e-6)
    assert prof["exposed_comm_secs"] == pytest.approx(0.0)
    assert prof["overlap_ratio"] == pytest.approx(1.0)
    assert prof["lanes"] == 2                 # the stream is still a lane
    assert prof["compute_lanes"] == 1         # ...but carries no compute


def test_attribute_async_two_lane_trace_pairs_in_order():
    """Synthetic two-lane async trace (the regression shape): two
    bucketed pairs whose halves live on a dedicated stream pair
    k-th-start ↔ k-th-done in ts order and merge per pair — NOT into one
    giant span, and never double-counted across the two lanes."""
    prof = devprof.attribute([
        _op(0, 100, "fusion.1"),
        # bucket A in flight [5, 45]; bucket B in flight [50, 90] — both
        # halves of each pair on the dedicated stream (tid=2)
        _op(5, 5, "all-reduce-start.1", tid=2),
        _op(40, 5, "all-reduce-done.1", tid=2),
        _op(50, 5, "all-reduce-start.2", tid=2),
        _op(85, 5, "all-reduce-done.2", tid=2),
    ])
    # two merged intervals, 40us each, on the stream lane
    assert prof["comm_secs"] == pytest.approx(80e-6)
    # per-lane model: the stream lane has no compute, so the merged
    # windows read exposed there (the start-lane assignment only applies
    # to CROSS-lane pairs, where the issuing lane is known)
    assert prof["exposed_comm_secs"] == pytest.approx(80e-6)
    # an unpaired start keeps its own sliver (no phantom done invented)
    prof2 = devprof.attribute([_op(5, 5, "all-reduce-start.9")])
    assert prof2["comm_secs"] == pytest.approx(5e-6)


def test_attribute_no_comm_yields_none_ratio_and_module_split():
    prof = devprof.attribute([
        _op(0, 10, "fusion.1", module="jit_a"),
        _op(20, 10, "convolution.4", module="jit_b"),
    ])
    assert prof["comm_secs"] == 0.0
    assert prof["overlap_ratio"] is None
    assert set(prof["modules"]) == {"jit_a", "jit_b"}
    assert prof["modules"]["jit_a"]["compute_secs"] == pytest.approx(10e-6)


def test_dispatch_anchors_counted_host_junk_ignored():
    """Dispatches are counted off the span ring's rows of the capture
    interval (``train.call``, ``exchange``), not off host events in the
    trace: a chip capture holds none."""
    rows = [("train", 9, 0, 9, None, 1),
            (devprof.TRAIN_DISPATCH_SPAN, 9, 0, 5, "train", 1),
            (devprof.TRAIN_DISPATCH_SPAN, 9, 6, 11, "train", 2),
            (devprof.EXCHANGE_SPAN, 9, 12, 14, None, None),
            ("input.materialize", 7, 0, 99, None, 3)]
    prof = devprof.attribute([
        {"ph": "X", "pid": 9, "tid": 9, "ts": 0, "dur": 99,
         "name": "$builtins isinstance"},        # host python span: ignored
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "x"}},
        _op(0, 10, "fusion.1"),
    ], rows)
    assert (devprof.TRAIN_DISPATCH_SPAN, devprof.EXCHANGE_SPAN) == \
        ("train.call", "exchange")
    assert prof["train_dispatches"] == 2
    assert prof["exchange_dispatches"] == 1
    assert prof["n_op_events"] == 1


def test_comm_op_classification():
    assert devprof.is_comm_op("all-reduce.17")
    assert devprof.is_comm_op("reduce-scatter.1")
    assert devprof.is_comm_op("collective-permute-start.3")
    assert not devprof.is_comm_op("reduce.5")          # plain reduce ≠ comm
    assert not devprof.is_comm_op("broadcast_multiply_fusion")
    assert devprof.op_class("all-reduce.17") == "all-reduce"


def test_profile_dir_empty_and_truncated(tmp_path):
    assert devprof.profile_dir(str(tmp_path)) is None
    sess = tmp_path / "plugins" / "profile" / "2026_01_01"
    sess.mkdir(parents=True)
    with open(sess / "host.xplane.pb", "wb") as f:
        f.write(b"\x0a\xff\x7f/device:TPU:0")   # truncated capture
    assert devprof.profile_dir(str(tmp_path)) is None


# -- live capture round-trip (acceptance: psum step on CPU) -----------------

def test_capture_round_trip_psum(tmp_path):
    """A captured profile of a psum-containing step round-trips: nonzero
    compute AND comm breakdown, ratio in range, all-reduce in the top op
    classes — the acceptance path for attribution on this backend."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from theanompi_tpu.jax_compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:4]), ("workers",))

    def f(x):
        return jax.lax.psum(x * 2.0, "workers")

    g = jax.jit(shard_map(f, mesh=mesh, in_specs=P("workers"),
                          out_specs=P()))
    x = jnp.arange(32.0)
    g(x).block_until_ready()                  # compile outside the window
    with devprof.capture(str(tmp_path / "trace")) as cap:
        for _ in range(3):
            r = g(x)
        r.block_until_ready()
    prof = cap.profile
    assert prof is not None, "no usable capture emitted"
    assert prof["comm_secs"] > 0 and prof["compute_secs"] > 0
    assert prof["exposed_comm_secs"] <= prof["comm_secs"] + 1e-9
    assert 0.0 <= prof["overlap_ratio"] <= 1.0
    assert any(o["comm"] and o["op"].startswith("all-reduce")
               for o in prof["top_ops"])
    assert prof["lanes"] >= 1 and prof["n_op_events"] > 0


def test_feed_telemetry_emits_device_gauges():
    prof = devprof.attribute([_op(0, 50, "fusion.1"),
                              _op(40, 20, "all-reduce.1")])
    tm = telemetry.Telemetry(rank=0, run_id="t")
    devprof.feed_telemetry(prof, tm)
    assert set(tm.gauges) == set(devprof.DEVICE_GAUGES)
    assert tm.gauges["device.overlap_ratio"] == pytest.approx(0.5)
    evs = [e for e in tm.tail(4) if e["ev"] == devprof.PROFILE_EVENT]
    assert evs and evs[-1]["top_ops"]
    # disabled registry: feed is a no-op, not an error
    devprof.feed_telemetry(prof, telemetry.DISABLED)


def test_bubble_fraction_per_lane_idle_gaps():
    """ISSUE 14 satellite (ROADMAP item 2's bench column): per-lane idle
    gaps between compute intervals inside the dispatch window, span-
    weighted across compute lanes."""
    prof = devprof.attribute([
        # lane 1: compute [0,10] + [20,30] → span 30, busy 20, idle 10
        _op(0, 10, "fusion.1"),
        _op(20, 10, "fusion.2"),
        # lane 2: compute [0,40] → span 40, no idle
        _op(0, 40, "while.1", tid=2),
    ])
    assert prof["bubble_fraction"] == pytest.approx(10.0 / 70.0, abs=1e-4)
    # a collective inside the gap does NOT fill the bubble: from the
    # compute pipeline's perspective an exposed comm stall is a stall
    prof2 = devprof.attribute([
        _op(0, 10, "fusion.1"),
        _op(20, 10, "fusion.2"),
        _op(0, 40, "while.1", tid=2),
        _op(12, 6, "all-reduce.1"),
    ])
    assert prof2["bubble_fraction"] == pytest.approx(10.0 / 70.0,
                                                     abs=1e-4)
    # no compute at all → None
    prof3 = devprof.attribute([_op(0, 5, "all-reduce.1")])
    assert prof3["bubble_fraction"] is None
    # a perfectly packed single lane is bubble-free
    assert devprof.attribute([_op(0, 50, "fusion.1")])[
        "bubble_fraction"] == pytest.approx(0.0)


# -- training sentry --------------------------------------------------------

def _rec(i, cost=1.0, ips=100.0):
    return {"iter": i, "cost": cost, "images_per_sec": ips}


def test_sentry_nan_loss():
    tm = telemetry.Telemetry(rank=0, run_id="s")
    s = TrainingSentry({"verbose": False}, telemetry=tm)
    assert s.observe_record(_rec(1)) is None
    assert s.observe_record(_rec(2, cost=float("nan"))) == "nan_loss"
    assert s.observe_record(_rec(3, cost=float("inf"))) == "nan_loss"
    evs = [e for e in tm.tail(8) if e["ev"] == sentry.ANOMALY_EVENT]
    assert len(evs) == 2 and evs[-1]["kind"] == "nan_loss"
    assert tm.counters["sentry.anomalies"] == 2
    assert tm.counters["sentry.nan_loss"] == 2


def test_sentry_loss_spike_robust_to_its_own_baseline():
    s = TrainingSentry({"verbose": False, "sentry_min_records": 4,
                        "sentry_loss_spike": 6.0},
                       telemetry=telemetry.DISABLED)
    for i in range(8):
        assert s.observe_record(_rec(i, cost=1.0 + 0.01 * (i % 3))) is None
    assert s.observe_record(_rec(9, cost=50.0)) == "loss_spike"
    # the spike did NOT enter the window: an immediately repeated spike
    # still reads as a spike (the baseline wasn't poisoned)
    assert s.observe_record(_rec(10, cost=50.0)) == "loss_spike"
    # back to normal is healthy
    assert s.observe_record(_rec(11, cost=1.01)) is None


def test_sentry_flat_window_tolerates_noise():
    """A perfectly flat cost window (MAD 0) must not flag float noise —
    the 5%-of-median floor absorbs it."""
    s = TrainingSentry({"verbose": False, "sentry_min_records": 4},
                       telemetry=telemetry.DISABLED)
    for i in range(6):
        assert s.observe_record(_rec(i, cost=2.0)) is None
    assert s.observe_record(_rec(7, cost=2.02)) is None


def test_sentry_throughput_regression():
    s = TrainingSentry({"verbose": False, "sentry_min_records": 4,
                        "sentry_tput_drop": 0.5},
                       telemetry=telemetry.DISABLED)
    for i in range(6):
        assert s.observe_record(_rec(i, ips=1000.0 + i)) is None
    assert s.observe_record(_rec(7, ips=100.0)) == "throughput_regression"
    assert s.observe_record(_rec(8, ips=990.0)) is None
    assert [k for k, _ in s.anomalies] == ["throughput_regression"]


def test_sentry_discontinuity_skips_one_throughput_sample():
    """The first record after a val/ckpt boundary spans dead wall time —
    notice_discontinuity() makes the sentry neither judge nor learn from
    its throughput, so a healthy run doesn't flag once per epoch."""
    s = TrainingSentry({"verbose": False, "sentry_min_records": 4,
                        "sentry_tput_drop": 0.5},
                       telemetry=telemetry.DISABLED)
    for i in range(6):
        assert s.observe_record(_rec(i, ips=1000.0)) is None
    s.notice_discontinuity()
    # spans the val epoch: would be a regression without the notice
    assert s.observe_record(_rec(7, ips=100.0)) is None
    assert 100.0 not in s._tputs                 # not learned either
    # the NEXT record is judged normally again
    assert s.observe_record(_rec(8, ips=100.0)) == "throughput_regression"
    # loss detection is unaffected by the notice
    s2 = TrainingSentry({"verbose": False}, telemetry=telemetry.DISABLED)
    s2.notice_discontinuity()
    assert s2.observe_record(_rec(1, cost=float("nan"))) == "nan_loss"


def test_sentry_dumps_flight_once_per_kind(tmp_path):
    d = str(tmp_path)
    tm = telemetry.Telemetry(rank=0, run_id="s", stream_dir=d)
    s = TrainingSentry({"verbose": False}, telemetry=tm)
    s.observe_record(_rec(1))
    assert s.observe_record(_rec(2, cost=float("nan"))) == "nan_loss"
    flight = os.path.join(d, "flight_rank0.jsonl")
    assert os.path.exists(flight)
    first = open(flight).read()
    assert "sentry nan_loss" in first.splitlines()[0]
    # second nan: event recorded, but the dump (the lead-in trail) stays
    s.observe_record(_rec(3, cost=float("nan")))
    assert open(flight).read() == first
    tm.close()


def test_sentry_wired_into_worker_healthy_run():
    """Session run with telemetry on: the worker builds a sentry, feeds it
    every print record, and a healthy run raises nothing; sentry=false
    opts out."""
    rule = tmpi.BSP()
    rule.init(devices=4, modelfile="tests.conftest", modelclass="TinyModel",
              epochs=1, batch_size=8, n_train=64, verbose=False,
              scale_lr=False, telemetry=True, printFreq=2)
    rule.wait()
    s = rule.worker.sentry
    assert s is not None and s.records_seen >= 1
    assert s.anomalies == []
    rule2 = tmpi.BSP()
    rule2.init(devices=4, modelfile="tests.conftest", modelclass="TinyModel",
               epochs=1, batch_size=8, n_train=64, verbose=False,
               scale_lr=False, telemetry=True, sentry=False)
    rule2.wait()
    assert rule2.worker.sentry is None


def test_worker_trace_capture_feeds_device_gauges(tmp_path):
    """The worker's trace_dir capture now runs attribution: after the
    traced window the process registry carries the device.* gauges and a
    device_profile event, with nonzero comm (the BSP step psums)."""
    trace_dir = str(tmp_path / "trace")
    rule = tmpi.BSP()
    rule.init(devices=4, modelfile="tests.conftest", modelclass="TinyModel",
              epochs=1, batch_size=8, n_train=64, verbose=False,
              scale_lr=False, telemetry=True,
              trace_dir=trace_dir, trace_start=2, trace_iters=2)
    rule.wait()
    tm = rule.worker.telemetry
    assert set(devprof.DEVICE_GAUGES) <= set(tm.gauges), sorted(tm.gauges)
    assert tm.gauges["device.comm_secs"] > 0
    assert tm.gauges["device.compute_secs"] > 0
    assert 0.0 <= tm.gauges["device.overlap_ratio"] <= 1.0
    evs = [e for e in tm.tail(64) if e["ev"] == devprof.PROFILE_EVENT]
    assert evs and evs[-1]["train_dispatches"] >= 1


# -- Perfetto trace export --------------------------------------------------

def _write_stream(d, rank, events):
    with open(os.path.join(d, f"telemetry_rank{rank}.jsonl"), "w") as f:
        for ev in events:
            f.write(json.dumps({"run": "r1", "rank": rank, **ev}) + "\n")


def test_telemetry_report_trace_export(tmp_path):
    """--trace emits Chrome trace-event JSON: one process track per rank,
    monotonic non-negative spans, counter tracks, anomaly markers."""
    d = str(tmp_path / "rec")
    os.makedirs(d)
    t0 = 1000.0
    _write_stream(d, 0, [
        {"ts": t0, "ev": "run_start", "schema": 1},
        {"ts": t0 + 1.0, "ev": "phase", "sec": "train", "dt": 0.5},
        {"ts": t0 + 1.2, "ev": "phase", "sec": "comm", "dt": 0.2},
        {"ts": t0 + 1.3, "ev": "gauges", "hbm_bytes_in_use": 1024,
         "prefetch.queue_depth": 2},
        {"ts": t0 + 1.5, "ev": "train_record", "iter": 4,
         "images_per_sec": 512.0},
        {"ts": t0 + 1.8, "ev": "val_record", "iter": 4, "val_cost": 1.25},
        {"ts": t0 + 2.0, "ev": "anomaly", "kind": "loss_spike", "iter": 6},
        {"ts": t0 + 2.5, "ev": "device_profile", "compute_secs": 1.0,
         "comm_secs": 0.5, "exposed_comm_secs": 0.1, "overlap_ratio": 0.8,
         "lanes": 4, "train_dispatches": 2},
    ])
    _write_stream(d, 1, [
        {"ts": t0 + 0.5, "ev": "phase", "sec": "train", "dt": 0.4},
        {"ts": t0 + 1.1, "ev": "phase", "sec": "train", "dt": 0.5},
    ])
    out = str(tmp_path / "trace.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/telemetry_report.py"),
         d, "--trace", out], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "Perfetto" in r.stdout
    trace = json.load(open(out))
    evs = trace["traceEvents"]
    # one process track per rank
    procs = {e["pid"]: e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert procs == {0: "rank 0", 1: "rank 1"}
    spans = [e for e in evs if e.get("ph") == "X"]
    assert {(s["pid"], s["name"]) for s in spans} == \
        {(0, "train"), (0, "comm"), (1, "train")}
    # monotonic, non-negative, ts-ordered within the body
    assert all(s["dur"] >= 0 and s["ts"] >= 0 for s in spans)
    body = [e for e in evs if e.get("ph") != "M"]
    assert [e["ts"] for e in body] == sorted(e["ts"] for e in body)
    # phase span is [ts-dt, ts]: rank 0's train span starts at 0.5s rel
    tr0 = next(s for s in spans if s["pid"] == 0 and s["name"] == "train")
    assert tr0["ts"] == pytest.approx(0.5e6, abs=1e3)
    assert tr0["dur"] == pytest.approx(0.5e6, abs=1e3)
    counters = {e["name"] for e in evs if e.get("ph") == "C"}
    assert counters == {"hbm_bytes_in_use", "prefetch.queue_depth",
                        "images_per_sec", "val_cost",
                        "device.overlap_ratio"}
    instants = [e for e in evs if e.get("ph") == "i"]
    assert instants and instants[0]["name"] == "anomaly:loss_spike"
    # anomalies AND the device attribution also surface in the plain report
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/telemetry_report.py"),
         d], capture_output=True, text=True)
    assert "sentry anomalies" in r2.stdout and "loss_spike" in r2.stdout
    assert "device-time attribution" in r2.stdout
    assert "80.0% overlap" in r2.stdout
