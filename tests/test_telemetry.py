"""Run-wide structured telemetry (ISSUE 4 tentpole): registry semantics,
the JSONL event stream, the flight recorder + launcher sweep, the
off-by-default cost contract, and the cross-worker run report."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import theanompi_tpu as tmpi
from theanompi_tpu.utils import telemetry
from theanompi_tpu.utils.telemetry import DISABLED, Histogram, Telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    """Every test leaves the process-wide registry disabled."""
    yield
    telemetry.init({})


# -- registry ---------------------------------------------------------------

def test_histogram_percentiles_and_bounded_reservoir():
    h = Histogram()
    for v in range(1, 1001):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 1000 and s["min"] == 1.0 and s["max"] == 1000.0
    assert abs(s["p50"] - 500) <= 10
    assert abs(s["p95"] - 950) <= 15
    assert abs(s["p99"] - 990) <= 15
    # past the cap the reservoir thins but count/sum/extrema stay exact
    h2 = Histogram(cap=128)
    for v in range(20000):
        h2.observe(float(v))
    assert h2.count == 20000 and h2.max == 19999.0
    assert len(h2._samples) <= 128
    assert h2.percentile(99) > 15000          # tail stays representative


def test_registry_counters_gauges_events_and_ring():
    tm = Telemetry(rank=3, run_id="r", flight_events=16)
    tm.counter("a")
    tm.counter("a", 2)
    tm.gauge("g", 7.5)
    tm.observe("h", 0.25)
    for i in range(40):
        tm.event("e", i=i)
    assert tm.counters["a"] == 3 and tm.gauges["g"] == 7.5
    assert tm.hists["h"].count == 1
    tail = tm.tail(4)
    assert len(tail) == 4 and tail[-1]["i"] == 39
    assert all(ev["rank"] == 3 and ev["run"] == "r" for ev in tail)
    # ring is bounded: only the last 16 events survive
    assert len(tm.tail(100)) == 16


def test_stream_summary_and_flight_dump(tmp_path):
    d = str(tmp_path)
    tm = Telemetry(rank=1, run_id="rx", stream_dir=d)
    tm.phase("train", 0.01)
    tm.event("beat", ring_only=True, label="iter 1")   # ring, not stream
    tm.counter("c")
    path = tm.dump_flight(reason="test dump")
    tm.close()
    evs = [json.loads(line)
           for line in open(os.path.join(d, "telemetry_rank1.jsonl"))]
    assert [e["ev"] for e in evs] == ["run_start", "phase"]
    assert evs[1]["sec"] == "train"
    flight = [json.loads(line) for line in open(path)]
    assert flight[0]["ev"] == "flight_dump"
    assert flight[0]["reason"] == "test dump"
    assert any(e["ev"] == "beat" for e in flight)      # ring-only included
    summ = json.load(open(os.path.join(d, "telemetry_summary_rank1.json")))
    assert summ["counters"]["c"] == 1
    assert summ["hist"]["phase.train"]["count"] == 1
    # closed instance is inert: stale references become no-ops, not errors
    assert not tm.enabled
    tm.event("late")
    tm.counter("late")


def test_init_resolution_rules(tmp_path):
    assert telemetry.init({}) is DISABLED                  # off by default
    assert telemetry.init({"telemetry": False,
                           "record_dir": str(tmp_path)}) is DISABLED
    tm = telemetry.init({"telemetry": True})               # in-memory
    assert tm.enabled and tm.stream_dir is None
    tm2 = telemetry.init({"record_dir": str(tmp_path), "rank": 2,
                          "run_id": "rid"})
    assert not tm.enabled                  # re-init closed the previous one
    assert tm2.stream_dir == str(tmp_path) and tm2.rank == 2
    assert telemetry.active() is tm2
    telemetry.init({})
    assert telemetry.active() is DISABLED


def test_aggregate_memory_stats_multi_device():
    """ISSUE 7 satellite: HBM gauges aggregate across ALL local devices —
    summed in-use, max peak, min limit, worst-device headroom — so
    multi-chip pressure can't hide behind device 0."""
    stats = [
        {"bytes_in_use": 100, "peak_bytes_in_use": 900, "bytes_limit": 1000},
        {"bytes_in_use": 300, "peak_bytes_in_use": 400, "bytes_limit": 1000},
        None,                                    # a device with no stats
        {"bytes_in_use": 50},                    # partial stats
    ]
    vals = telemetry.aggregate_memory_stats(stats)
    assert vals["hbm_bytes_in_use"] == 450          # summed
    assert vals["hbm_peak_bytes"] == 900            # max (hottest chip)
    assert vals["hbm_bytes_limit"] == 1000          # min (binding budget)
    assert vals["hbm_min_headroom_bytes"] == 100    # worst device: 1000-900
    assert telemetry.aggregate_memory_stats([None, None]) == {}
    assert telemetry.aggregate_memory_stats([]) == {}


def test_system_snapshot_emits_device_count_and_queue_depth():
    tm = telemetry.init({"telemetry": True})
    tm.gauge("prefetch.queue_depth", 3)
    vals = tm.system_snapshot(iter=7)
    # the 8-device CPU mesh: count emitted even though CPU has no
    # memory_stats; the loader's queue-depth gauge is sampled into the
    # stream (the Perfetto counter track reads it from gauges events)
    assert vals["device_count"] == 8
    assert vals["prefetch.queue_depth"] == 3
    assert vals["iter"] == 7


# -- the cost contract ------------------------------------------------------

def test_disabled_registry_is_noop_and_cheap():
    """Disabled ≡ one attribute check: every method is a no-op and the
    guarded hot-path pattern adds no measurable per-iteration cost."""
    tm = DISABLED
    assert not tm.enabled
    tm.counter("x")
    tm.gauge("x", 1)
    tm.observe("x", 1.0)
    tm.phase("train", 0.1)
    tm.event("x", a=1)
    assert tm.tail() == [] and tm.summary() == {}
    assert tm.dump_flight(reason="r") is None
    assert tm.counters == {} and tm.hists == {}

    N = 200_000

    def bare():
        t0 = time.perf_counter()
        acc = 0
        for i in range(N):
            acc += i
        return time.perf_counter() - t0

    def guarded():
        t0 = time.perf_counter()
        acc = 0
        for i in range(N):
            if tm.enabled:                      # the whole hot-path cost
                tm.phase("train", 0.1)
            acc += i
        return time.perf_counter() - t0

    b = min(bare() for _ in range(3))
    g = min(guarded() for _ in range(3))
    per_iter = max(0.0, g - b) / N
    assert per_iter < 2e-6, (
        f"disabled telemetry costs {per_iter * 1e9:.0f} ns/iter "
        f"(bare {b:.3f}s vs guarded {g:.3f}s)")


def test_enabled_telemetry_does_not_perturb_training():
    """Telemetry only reads clocks: the same seeded run with the registry
    on (in-memory) and off must produce bit-identical parameters."""
    import jax

    def run(**extra):
        rule = tmpi.BSP()
        rule.init(devices=4, modelfile="tests.conftest",
                  modelclass="TinyModel", epochs=1, batch_size=8,
                  n_train=64, verbose=False, scale_lr=False, seed=5, **extra)
        rule.wait()
        return jax.device_get(rule.model.step_state["params"])

    a = run()
    b = run(telemetry=True)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(la, lb)


# -- component threading ----------------------------------------------------

def test_prefetch_exports_queue_depth_and_producer_gauges():
    from tests.conftest import SyntheticData
    from theanompi_tpu.models.data.prefetch import PrefetchLoader

    tm = telemetry.init({"telemetry": True})
    data = PrefetchLoader(SyntheticData(batch_size=8, n_train=64))
    data.shuffle_data(0)
    for i in range(1, 9):
        data.next_train_batch(i)
    assert tm.counters["prefetch.dequeues"] == 8
    assert tm.hists["prefetch.queue_depth"].count == 8
    assert tm.hists["phase.input.materialize"].count >= 1
    assert "prefetch.queue_depth" in tm.gauges
    # a consumer outrunning the producer leaves starved dequeues behind
    assert tm.counters.get("prefetch.starved_dequeues", 0) <= 8


def test_exchanger_records_per_exchange_histograms():
    """Unfused EASGD: each exchange lands one sample in the dispatch
    histogram and one in phase.comm (via the recorder bridge) — full
    per-exchange distributions, not bare sums."""
    rule = tmpi.EASGD()
    rule.init(devices=4, modelfile="tests.conftest", modelclass="TinyModel",
              epochs=1, batch_size=8, n_train=64, verbose=False,
              scale_lr=False, sync_freq=1, telemetry=True)
    rule.wait()
    tm = rule.worker.telemetry
    assert tm.counters["exchange.count"] >= 1
    assert tm.counters["exchange.count.easgd"] == tm.counters["exchange.count"]
    assert tm.hists["exchange.dispatch_secs"].count == \
        tm.counters["exchange.count"]
    assert tm.hists["phase.comm"].count == tm.counters["exchange.count"]
    assert tm.hists["phase.train"].count >= 1


def test_watchdog_stall_message_includes_flight_tail(capfd):
    from theanompi_tpu.utils.watchdog import StallWatchdog

    telemetry.init({"telemetry": True})
    wd = StallWatchdog(timeout_s=10)
    wd.beat("epoch 0 iter 7")
    wd.beat("epoch 0 iter 8")
    wd._default_handler(12.0, "epoch 0 iter 8")
    err = capfd.readouterr().err
    assert "last telemetry events" in err
    assert "epoch 0 iter 7" in err and "epoch 0 iter 8" in err


# -- the acceptance path: run → streams → report ----------------------------

def test_two_worker_run_streams_and_report(tmp_path):
    """A two-worker launcher run with telemetry on: per-rank JSONL streams
    appear, and telemetry_report.py merges them into a report with phase
    p50/p95, a straggler ranking, and queue-depth gauges."""
    from theanompi_tpu import launcher

    rec = str(tmp_path / "run")
    rc = launcher.main([
        "--rule", "bsp", "--modelfile", "tests.conftest",
        "--modelclass", "TinyModel", "--n-workers", "2",
        "--record-dir", rec,
        "platform=cpu", "epochs=2", "batch_size=8", "n_train=64",
        "verbose=false", "scale_lr=false", "para_load=true", "printFreq=2",
    ])
    assert rc == 0
    stream = os.path.join(rec, "telemetry_rank0.jsonl")
    assert os.path.exists(stream)
    evs = [json.loads(line) for line in open(stream)]
    kinds = {e["ev"] for e in evs}
    assert {"run_start", "train_begin", "phase", "train_record",
            "val_record", "gauges", "train_end"} <= kinds
    # one shared run id, launcher-stamped
    assert len({e["run"] for e in evs}) == 1
    # host gauges always present (HBM joins on TPU via memory_stats)
    gauges = [e for e in evs if e["ev"] == "gauges"]
    assert gauges and "host_rss_bytes" in gauges[-1]
    assert os.path.exists(
        os.path.join(rec, "telemetry_summary_rank0.json"))

    out_json = str(tmp_path / "report.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/telemetry_report.py"),
         rec, "--json", out_json],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "phase breakdown" in r.stdout
    assert "straggler ranking" in r.stdout
    rep = json.load(open(out_json))
    for sec in ("train", "load", "compile"):
        assert rep["phases"][sec]["p95"] is not None
        assert rep["phases"][sec]["p50"] is not None
    assert rep["straggler_ranking"] and \
        rep["straggler_ranking"][0]["p95_train_secs"] is not None
    # para_load=true → the prefetch queue-depth gauges reach the report
    pf = rep["flags"]["prefetch"]["0"] if "0" in rep["flags"].get(
        "prefetch", {}) else rep["flags"]["prefetch"][0]
    assert pf["min_queue_depth"] is not None
    assert rep["throughput_timeline"]


def test_worker_sigterm_dumps_flight(tmp_path):
    """ISSUE 7 satellite — the fatal-signal path of the PR 4 flight
    recorder, previously only exercised by the stall path: a CLI worker
    SIGTERM'd mid-run leaves a flight_rank0.jsonl that parses and ends
    with the fatal_signal event, and the process dies with the honest
    signal exit.

    ISSUE 19 rides the same run: with ``numerics=true`` and an absurd
    ratio floor every numerics report trips ``update_ratio_collapse``,
    so the dumped ring must carry the §25 numerics report events AND the
    numerics-detector anomaly — the end-to-end proof that the new
    detectors reach the flight/post-mortem plane."""
    import signal

    rec = str(tmp_path / "rec")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "theanompi_tpu.worker",
         "bsp", "tests.conftest", "TinyModel",
         "platform=cpu", "epochs=999", "batch_size=8", "n_train=64",
         "verbose=false", "scale_lr=false", "printFreq=2",
         "numerics=true", "sentry_ratio_floor=1000000",
         f"record_dir={rec}"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        # wait until training is demonstrably mid-run: the per-rank stream
        # carries at least one phase bracket
        stream = os.path.join(rec, "telemetry_rank0.jsonl")
        deadline = time.time() + 120
        seen_phase = False
        while time.time() < deadline and not seen_phase:
            if os.path.exists(stream):
                with open(stream) as f:
                    seen_phase = any('"ev": "phase"' in ln for ln in f)
            if proc.poll() is not None:
                break
            if not seen_phase:
                time.sleep(0.25)
        assert seen_phase, (proc.poll(),
                            proc.stderr.read()[-2000:] if proc.poll()
                            is not None else "no phase event within 120s")
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.communicate()
    # the hook re-raises with the default handler: honest signal exit
    assert proc.returncode == -signal.SIGTERM
    flight_path = os.path.join(rec, "flight_rank0.jsonl")
    assert os.path.exists(flight_path), os.listdir(rec)
    flight = [json.loads(line) for line in open(flight_path)]  # parses
    assert flight[0]["ev"] == "flight_dump"
    assert "signal" in flight[0]["reason"]
    assert flight[-1]["ev"] == "fatal_signal"
    assert flight[-1]["signum"] == int(signal.SIGTERM)
    # the trail shows the run was mid-training when the signal landed
    assert any(e["ev"] in ("phase", "beat", "train_record")
               for e in flight[1:])
    # §25 end-to-end: the ring carries the numerics reports and the
    # numerics-detector anomaly the rigged ratio floor forced
    numerics_evs = [e for e in flight[1:] if e["ev"] == "numerics"]
    assert numerics_evs, "no numerics report reached the flight ring"
    assert all(e["grad_norm"] > 0 for e in numerics_evs)
    anoms = [e for e in flight[1:] if e["ev"] == "anomaly"]
    assert any(e["kind"] == "update_ratio_collapse" for e in anoms), anoms


def test_crash_dumps_flight_and_launcher_sweeps(tmp_path):
    """A forced mid-run crash leaves flight_rank*.jsonl (dumped by the
    dying worker) which the supervising launcher sweeps into a crash_
    directory before restarting; the resumed run completes."""
    from theanompi_tpu import launcher

    rec = str(tmp_path / "rec")
    ckpt = str(tmp_path / "ckpt")
    marker = str(tmp_path / "crashed")
    # 4 iters/epoch; crash_at=5 fires in epoch 1, after epoch 0's ckpt
    rc = launcher.main([
        "--supervise", "2", "--rule", "bsp",
        "--modelfile", "tests.conftest", "--modelclass", "CrashOnceModel",
        "--record-dir", rec,
        "platform=cpu", "epochs=2", "batch_size=8", "n_train=256",
        "n_workers=8", "verbose=false", "scale_lr=false",
        f"ckpt_dir={ckpt}", f"crash_marker={marker}", "crash_at=5",
    ])
    assert rc == 0
    assert os.path.exists(marker)               # the crash really happened
    swept = [d for d in os.listdir(rec) if d.startswith("crash_")]
    assert swept, f"no swept crash dir in {os.listdir(rec)}"
    flight_path = os.path.join(rec, swept[0], "flight_rank0.jsonl")
    assert os.path.exists(flight_path)
    flight = [json.loads(line) for line in open(flight_path)]
    assert flight[0]["ev"] == "flight_dump"
    assert "injected crash" in flight[0]["reason"]
    # the trail shows what the rank was doing: beats + phases + the crash
    kinds = {e["ev"] for e in flight}
    assert "beat" in kinds and "crash" in kinds
    # the dump itself was NOT left in record_dir root (swept aside)
    assert not os.path.exists(os.path.join(rec, "flight_rank0.jsonl"))
    # the resumed run's stream appended to the same per-rank file
    evs = [json.loads(line)
           for line in open(os.path.join(rec, "telemetry_rank0.jsonl"))]
    assert any(e["ev"] == "train_end" for e in evs)
    assert any(e["ev"] == "crash" for e in evs)
    # and the resumed run's recorder LOADED the pre-crash records before
    # its first save, so the final JSONL holds BOTH epochs' val records
    # (the Recorder.load round-trip running on the path it exists for)
    recs = [json.loads(line)
            for line in open(os.path.join(rec, "inforec_rank0.jsonl"))]
    assert len([x for x in recs if "val_cost" in x]) == 2
