"""Recorder edge cases (ISSUE 4 satellites): stride gating when printFreq
is not a multiple of the dispatch stride, zero-elapsed throughput windows,
and the lossless save/load round-trip (epoch records included)."""

import json
import os

import numpy as np

from theanompi_tpu.utils.recorder import RECORD_KEYS, SECTIONS, Recorder
from theanompi_tpu.utils.telemetry import PHASES


def _drive(r, counts, stride):
    fired = []
    for c in counts:
        r.start()
        r.end("train")
        r.train_error(c, 1.0, 0.5, 8 * stride)
        if r.print_train_info(c, stride=stride):
            fired.append(c)
    return fired


def test_stride_gate_when_printfreq_not_divisible():
    """printFreq=5, stride=3: the old residue gate (count % printFreq <
    stride) fired twice inside one window (counts 12 AND 15) and skipped
    another entirely; the dispatch-ordinal gate fires exactly once every
    ceil(5/3)=2 dispatches."""
    r = Recorder({"verbose": False, "printFreq": 5})
    counts = [3 * i for i in range(1, 11)]          # 3, 6, ..., 30
    fired = _drive(r, counts, stride=3)
    assert fired == [6, 12, 18, 24, 30]             # every 2nd dispatch
    # never less than printFreq steps between consecutive prints
    assert all(b - a >= 5 for a, b in zip(fired, fired[1:]))
    assert len(r._all_records) == len(fired)


def test_stride_gate_divisible_unchanged():
    """The common case (stride | printFreq) keeps the historical cadence:
    one print per printFreq steps, on the window boundary."""
    r = Recorder({"verbose": False, "printFreq": 4})
    fired = _drive(r, [2 * i for i in range(1, 11)], stride=2)
    assert fired == [4, 8, 12, 16, 20]
    # and the per-step cadence (stride=1) fires on exact multiples
    r1 = Recorder({"verbose": False, "printFreq": 2})
    fired1 = _drive(r1, list(range(1, 7)), stride=1)
    assert fired1 == [2, 4, 6]


def test_images_per_sec_zero_elapsed_window():
    """A zero (or negative, clock-step) elapsed window must not divide by
    zero: throughput reports 0 and the reference's headline unit inf."""
    r = Recorder({"verbose": False})
    r.n_images = 640
    r._last_print_wall = 9e18            # "now" is before the last print
    assert r.images_per_sec() == 0.0
    assert r.time_per_5120() == float("inf")
    # and the print path survives it (record carries the degenerate values)
    r.start()
    r.end("train")
    r.train_error(1, 1.0, 0.5, 8)
    assert r.print_train_info(40)
    assert r._all_records[-1]["images_per_sec"] == 0.0


def test_save_load_round_trip_is_lossless(tmp_path):
    """save → load → save must preserve BOTH record lists bit-for-bit: the
    old load() dropped epoch_records, so a resumed run's next save()
    rewrote the JSONL without the pre-resume epoch lines."""
    d = str(tmp_path)
    r = Recorder({"verbose": False, "printFreq": 1, "record_dir": d})
    for i in range(1, 4):
        r.start()
        r.end("train")
        r.train_error(i, 1.0 / i, 0.5, 8)
        assert r.print_train_info(i)
    r.val_error(3, 0.9, 0.4, 0.1)
    r.print_val_info(3)
    r.save()

    r2 = Recorder({"verbose": False, "record_dir": d})
    r2.load()
    assert r2._all_records == r._all_records
    assert r2.epoch_records == r.epoch_records      # the old resume hole

    # the resumed recorder's next save keeps the pre-resume epoch lines
    r2.save()
    with open(os.path.join(d, "inforec_rank0.jsonl")) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    assert [x for x in recs if "val_cost" in x] == r.epoch_records
    assert [x for x in recs if "val_cost" not in x] == r._all_records


def test_load_survives_truncated_jsonl(tmp_path):
    """A worker killed mid-save leaves a truncated last line; the resume
    path must skip it and keep the intact records, not crash-loop the
    supervisor with a JSONDecodeError on every retry."""
    d = str(tmp_path)
    r = Recorder({"verbose": False, "printFreq": 1, "record_dir": d})
    for i in (1, 2):
        r.start()
        r.end("train")
        r.train_error(i, 1.0, 0.5, 8)
        r.print_train_info(i)
    r.val_error(2, 0.9, 0.4, 0.1)
    r.print_val_info(2)
    r.save()
    path = os.path.join(d, "inforec_rank0.jsonl")
    with open(path) as f:
        whole = f.read()
    with open(path, "w") as f:
        f.write(whole[:-25])               # kill mid final (epoch) line
    r2 = Recorder({"verbose": False, "record_dir": d})
    r2.load()                              # must not raise
    assert r2._all_records == r._all_records
    assert r2.epoch_records == []          # the mangled line was dropped


def test_load_falls_back_to_npy(tmp_path):
    """Without the JSONL (legacy dirs) the .npy still restores the train
    records — epoch records are simply not in that format."""
    d = str(tmp_path)
    r = Recorder({"verbose": False, "printFreq": 1, "record_dir": d})
    r.start()
    r.end("train")
    r.train_error(1, 2.0, 0.5, 8)
    r.print_train_info(1)
    r.save()
    os.remove(os.path.join(d, "inforec_rank0.jsonl"))
    r2 = Recorder({"verbose": False, "record_dir": d})
    r2.load()
    assert len(r2._all_records) == 1
    assert r2._all_records[0]["cost"] == 2.0
    assert r2.epoch_records == []


def test_sections_and_record_keys_single_source_of_truth():
    """The drift-guard contract (the tpulint schema-drift checker runs
    the full live-object version in tier1.sh via scripts/lint.py):
    SECTIONS aliases telemetry.PHASES and the record keys derive from
    it."""
    assert tuple(SECTIONS) == tuple(PHASES)
    assert RECORD_KEYS == tuple("t_" + s for s in PHASES if s != "val")
    r = Recorder({"verbose": False, "printFreq": 1})
    r.start()
    r.end("compile")
    r.train_error(1, 1.0, 0.5, 8)
    r.print_train_info(1)
    rec = r._all_records[-1]
    assert {k for k in rec if k.startswith("t_")} == set(RECORD_KEYS)


def test_recorder_compile_bucket():
    rec = Recorder({"verbose": False, "printFreq": 1})
    rec.start()
    rec.end("compile")
    rec.start()
    rec.end("train")
    rec.train_error(1, 0.5, 0.1, 8)
    rec.print_train_info(1)
    r = rec._all_records[-1]
    assert r["t_compile"] >= 0 and "t_train" in r
    # bucket resets after the print, like every section
    assert rec.t_sec["compile"] == 0.0
    ep = rec.print_val_info(1)
    assert "t_compile" in ep        # cumulative, for resume-goes-to-~0
