"""Interleaved pipeline schedule TABLE (round 10, ISSUE 16): pure-python /
numpy pins on ``parallel.pipeline.build_schedule`` and everything that
consumes it — the devprof busy-count mirror and the predict_scaling
bubble and wire models.

Unlike tests/test_pipeline.py (slow: real meshes, real training), this file
never traces or compiles anything, so it rides the tier-1 gate and keeps
the schedule contract pinned on every run.
"""

import json
import os

import numpy as np
import pytest

from theanompi_tpu.parallel.pipeline import (_validate, build_schedule,
                                             stage_permutation)
from theanompi_tpu.utils import devprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (pp, v, m) grid: v=1 legacy shapes plus every interleave branch corner —
# pp|m, v up to pp, non-power-of-two pp
GRID = [(2, 1, 3), (4, 1, 8), (2, 2, 4), (4, 2, 8), (4, 4, 8), (3, 2, 6),
        (3, 3, 6), (2, 4, 2)]


# -- build_schedule: v=1 closed forms ---------------------------------------

@pytest.mark.parametrize("pp,m", [(2, 3), (4, 8), (3, 5)])
def test_v1_closed_forms(pp, m):
    s = build_schedule(pp, 1, m)
    assert s.ticks == m + pp - 1
    t = np.arange(s.ticks)[:, None]
    r = np.arange(pp)[None, :]
    u = t - r
    np.testing.assert_array_equal(np.asarray(s.real), (u >= 0) & (u < m))
    np.testing.assert_array_equal(np.asarray(s.micro), np.clip(u, 0, m - 1))
    np.testing.assert_array_equal(np.asarray(s.chunk), np.zeros_like(u))
    # v=1 keeps the legacy always-inject/clipped-index form bit-for-bit
    assert bool(np.all(np.asarray(s.inject)))
    np.testing.assert_array_equal(
        np.asarray(s.inject_idx), np.clip(np.arange(s.ticks), 0, m - 1))
    np.testing.assert_array_equal(
        np.asarray(s.collect), np.arange(s.ticks) >= pp - 1)
    np.testing.assert_array_equal(
        np.asarray(s.collect_idx),
        np.clip(np.arange(s.ticks) - (pp - 1), 0, m - 1))
    # partial shift, not a ring: last stage's activations stay put
    assert s.perm == tuple((i, i + 1) for i in range(pp - 1))


# -- build_schedule: interleaved invariants ---------------------------------

@pytest.mark.parametrize("pp,v,m", [g for g in GRID if g[1] > 1])
def test_interleaved_schedule_invariants(pp, v, m):
    s = build_schedule(pp, v, m)
    assert s.ticks == v * m + pp - 1
    assert s.perm == tuple((i, (i + 1) % pp) for i in range(pp))
    real = np.asarray(s.real)
    chunk = np.asarray(s.chunk)
    micro = np.asarray(s.micro)
    # every (global stage, microbatch) pair runs exactly once, and
    # consecutive stages of one microbatch run on consecutive ticks
    when = {}
    for t in range(s.ticks):
        for r in range(pp):
            if real[t, r]:
                stage = int(chunk[t, r]) * pp + r
                key = (stage, int(micro[t, r]))
                assert key not in when, f"{key} scheduled twice"
                when[key] = t
    S = pp * v
    assert len(when) == S * m
    for stage in range(S - 1):
        for j in range(m):
            assert when[(stage + 1, j)] == when[(stage, j)] + 1, \
                f"stage {stage}->{stage + 1} of micro {j} not adjacent"
    # each device is busy exactly v*m ticks (its v chunks x m microbatches)
    np.testing.assert_array_equal(real.sum(axis=0), np.full(pp, v * m))
    # injection: stage 0 (device 0, chunk 0) consumes each microbatch once
    inject = np.asarray(s.inject)
    inj_idx = np.asarray(s.inject_idx)
    assert sorted(inj_idx[inject].tolist()) == list(range(m))
    # collection: the last stage emits each microbatch once
    collect = np.asarray(s.collect)
    col_idx = np.asarray(s.collect_idx)
    assert sorted(col_idx[collect].tolist()) == list(range(m))


def test_build_schedule_interleaved_needs_pp_divisible_micros():
    with pytest.raises(ValueError, match="pp_microbatches"):
        build_schedule(4, 2, 6)


# -- stage_permutation ------------------------------------------------------

def test_stage_permutation_identity_at_v1():
    np.testing.assert_array_equal(stage_permutation(8, 4, 1), np.arange(8))


def test_stage_permutation_interleaves_chunks():
    # 8 layers, pp=4, v=2: device r holds global stages {r, r+pp} — layer
    # rows regroup so each device's rows are its two non-contiguous stages
    np.testing.assert_array_equal(stage_permutation(8, 4, 2),
                                  np.asarray([0, 4, 1, 5, 2, 6, 3, 7]))
    # always a permutation
    for (L, pp, v) in [(12, 2, 3), (16, 4, 2), (24, 3, 4)]:
        p = stage_permutation(L, pp, v)
        assert sorted(p.tolist()) == list(range(L))


def test_stage_permutation_divisibility_error():
    with pytest.raises(ValueError, match="pp_interleave"):
        stage_permutation(8, 4, 3)


# -- _validate: loud config-knob errors -------------------------------------

def test_validate_names_the_config_knobs():
    with pytest.raises(ValueError, match="pp_microbatches"):
        _validate(4, 1, 2, 4)            # m < pp
    with pytest.raises(ValueError, match="pp_microbatches"):
        _validate(4, 2, 6, 2)            # v>1 and m % pp != 0
    with pytest.raises(ValueError, match="pp_interleave"):
        _validate(4, 2, 8, 3)            # local layers not divisible by v
    _validate(4, 2, 8, 2)                # healthy config passes


# -- devprof mirror: stdlib busy counts == jax-side table -------------------

@pytest.mark.parametrize("pp,v,m", GRID)
def test_devprof_busy_counts_match_schedule(pp, v, m):
    """devprof._schedule_busy_counts is a stdlib replica of the schedule's
    per-tick busy-device count (devprof must stay importable without jax);
    this is the pin its docstring promises."""
    s = build_schedule(pp, v, m)
    mirror = devprof._schedule_busy_counts(pp, v, m)
    np.testing.assert_array_equal(
        np.asarray(s.real).sum(axis=1), np.asarray(mirror))
    # and the idle sequence is a palindrome — what makes
    # pipeline_schedule_report pass-structure-agnostic
    assert mirror == mirror[::-1]


# -- predict_scaling bubble model -------------------------------------------

def test_pipeline_bubble_model():
    from scripts.predict_scaling import PIPELINE_CONFIGS, pipeline_bubble
    # hop-free v=1 reduces to the classic GPipe bubble (pp-1)/(m+pp-1)
    b = pipeline_bubble(4, 1, 8)
    assert b["ticks"] == 11 and b["warmup_ticks"] == 3
    assert b["bubble_fraction"] == pytest.approx(3 / 11, abs=1e-4)
    # interleave monotonically shrinks the bubble at fixed (pp, m)
    fracs = [pipeline_bubble(4, v, 8)["bubble_fraction"] for v in (1, 2, 4)]
    assert fracs == sorted(fracs, reverse=True)
    assert fracs[0] > fracs[-1]
    # hop overhead can only make the measured bubble worse
    assert (pipeline_bubble(4, 2, 8, t_chunk=1.0, t_hop=0.25)
            ["bubble_fraction"]
            > pipeline_bubble(4, 2, 8)["bubble_fraction"])
    for (_, pp, v, m) in PIPELINE_CONFIGS:
        assert pipeline_bubble(pp, v, m)["bubble_fraction"] == \
            pytest.approx((pp - 1) / (v * m + pp - 1), abs=1e-4)


def test_powersgd_wire_bytes_uses_real_factorization():
    """The wire model must follow PowerSGD's own
    [prod(shape[:-1]), shape[-1]] per-leaf factorization gated by
    _compressible, plus a dense psum term for the rejected leaves."""
    from scripts.predict_scaling import wire_bytes
    with open(os.path.join(REPO, "model_param_counts.json")) as f:
        counts = json.load(f)
    vgg = counts["vgg16"]
    assert 60_000 < vgg["rows_plus_cols"] < 120_000, vgg
    assert vgg["powersgd_dense"] > 0
    wb = wire_bytes("powersgd4", vgg["params"], vgg["rows_plus_cols"], 8,
                    vgg["powersgd_dense"])
    ring = 2.0 * 7 / 8
    assert wb == ring * (4 * vgg["rows_plus_cols"]
                         + vgg["powersgd_dense"]) * 4
    assert wb < 0.05 * wire_bytes("allreduce", vgg["params"], 0, 8)


def test_pipeline_row_columns_distinct():
    # the report vocabularies must not collide: predict_scaling joins
    # them into one flat row
    cols = set(devprof.PIPELINE_ROW_COLUMNS)
    assert not cols & set(devprof.USHARD_ROW_COLUMNS)
    assert not cols & set(devprof.COMPRESS_ROW_COLUMNS)


# -- pipeline_schedule_report on synthetic traces ---------------------------

def _hop_events(pp, v, m, n_passes, tick_us=100.0):
    """Synthetic trace: every tick each of the pp devices hops once."""
    T = v * m + pp - 1
    evs = []
    for g in range(n_passes * T):
        for r in range(pp):
            evs.append({"ph": "X", "name": "collective-permute.7",
                        "pid": 1, "tid": r,
                        "args": {"hlo_op": f"collective-permute.{r}"},
                        "ts": g * tick_us + r, "dur": 3.0})
    return evs


def test_schedule_report_verified_and_exact():
    pp, v, m = 2, 2, 2                      # T = 5, bubble = 1/5
    rep = devprof.pipeline_schedule_report(
        _hop_events(pp, v, m, n_passes=2), pp=pp, v=v, m=m, passes=2)
    assert rep["ticks_per_pass"] == 5
    assert rep["n_hop_events"] == 20
    assert rep["measured_ticks"] == 10
    assert rep["schedule_verified"] is True
    assert rep["passes_detected"] == pytest.approx(2.0)
    assert rep["steps_detected"] == pytest.approx(1.0)
    assert rep["bubble_fraction_ticks"] == pytest.approx(0.2)
    # uniform tick spacing: duration weighting reproduces the tick model
    assert rep["bubble_fraction"] == pytest.approx(0.2, abs=1e-3)


def test_schedule_report_detects_wrong_tick_count():
    # a v=1 trace graded against the v=2 table: 28 hop events don't divide
    # into whole T=9 passes — the report must refuse to claim verification
    evs = _hop_events(2, 1, 6, n_passes=2)     # T = 7 -> 28 hop events
    rep = devprof.pipeline_schedule_report(evs, pp=2, v=2, m=4, passes=2)
    assert rep["ticks_per_pass"] == 9
    assert rep["schedule_verified"] is False


def test_schedule_report_ignores_done_halves_and_noise():
    pp, v, m = 2, 2, 2
    evs = _hop_events(pp, v, m, n_passes=2)
    extra = []
    for ev in evs:
        # async lowering emits a -done twin per hop; count one per hop
        extra.append({**ev, "name": "collective-permute-done.7",
                      "ts": ev["ts"] + 1.0})
        extra.append({**ev, "name": "fusion.12"})              # compute
        extra.append({**ev, "args": None})                     # malformed
    rep = devprof.pipeline_schedule_report(evs + extra,
                                           pp=pp, v=v, m=m)
    assert rep["n_hop_events"] == 20
    assert rep["schedule_verified"] is True


def test_schedule_report_empty_trace():
    rep = devprof.pipeline_schedule_report([], pp=4, v=2, m=8)
    assert rep["schedule_verified"] is False
    assert rep["bubble_fraction"] is None
    assert rep["bubble_fraction_ticks"] is None


# -- schedule_occupancy on synthetic lanes ----------------------------------

def test_schedule_occupancy_classifies_lanes():
    def ev(name, ts, dur, tid=0):
        return {"ph": "X", "name": name, "pid": 7, "tid": tid, "_src": "t0",
                "args": {"hlo_op": name}, "ts": ts, "dur": dur}

    events = [
        # lane 0: compute 0-10, exposed hop 10-15, compute 15-30 -> no idle
        ev("fusion.1", 0.0, 10.0), ev("collective-permute.2", 10.0, 5.0),
        ev("fusion.3", 15.0, 15.0),
        # lane 1: compute 0-10 and 20-30 with a 10us schedule gap
        ev("fusion.4", 0.0, 10.0, tid=1), ev("fusion.5", 20.0, 10.0, tid=1),
    ]
    occ = devprof.schedule_occupancy(events, min_gap_us=1.0, strip_width=12)
    assert occ["n_lanes"] == 2
    by_lane = {l["lane"]: l for l in occ["lanes"]}
    l0 = by_lane["t0:7/0"]
    assert l0["compute_secs"] == pytest.approx(25e-6)
    assert l0["hop_secs"] == pytest.approx(5e-6)
    assert l0["bubble_fraction"] == pytest.approx(0.0)
    l1 = by_lane["t0:7/1"]
    assert l1["n_slots"] == 2
    assert l1["idle_secs"] == pytest.approx(10e-6)
    assert l1["bubble_fraction"] == pytest.approx(1 / 3, abs=1e-3)
    assert "·" in l1["strip"] and "C" in l1["strip"]
    assert "H" in l0["strip"]
    # formatted view renders every lane plus the aggregate
    txt = devprof.format_schedule(occ)
    assert "t0:7/0" in txt and "bubble_fraction" in txt
