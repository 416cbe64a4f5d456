"""Numerics health plane (ISSUE 19, docs/design.md §25).

The acceptance contract, pinned here:

* **inertness** — per exchange rule (BSP grads, BSP fused spc>1, EASGD,
  onebit-compressed wire), the training stream with ``numerics=true`` is
  bit-identical (``assert_array_equal``, params/opt_state/extra AND the
  cost stream) to the same run with the plane off: the observer reads
  already-live values and changes no update math;
* **beacon semantics** — bit-identical BSP replicas produce bitwise-equal
  digests (divergence exactly 0.0), EASGD reports the exact ``‖w_i − c‖``
  distance, the EF-buffer norm streams for the compressed wires, and a
  corrupted per-rank digest shows as ``divergence > 0`` in the same
  report;
* **host plane** — ``host_report`` worst-rank aggregation, nan-safe
  divergence, no-sample/no-beacon None semantics; ``record`` covers the
  declared gauge/histogram/event vocabulary under one ``enabled`` check;
* **sentry detectors** — grad_overflow / replica_divergence /
  update_ratio_collapse ordering, the latest-sample-carry iter dedupe,
  and ``notice_discontinuity`` consuming exactly one report.
"""

import math

import numpy as np
import pytest

import jax

from tests.conftest import TinyModel
from theanompi_tpu.parallel.exchanger import (BSP_Exchanger,
                                              EASGD_Exchanger)
from theanompi_tpu.parallel.mesh import worker_mesh
from theanompi_tpu.utils import numerics, telemetry
from theanompi_tpu.utils.sentry import TrainingSentry

N = 4


def _build(exch_cls, spc=1, numerics_on=False, n=N, **cfg):
    mesh = worker_mesh(n)
    config = {"mesh": mesh, "size": n, "rank": 0, "verbose": False,
              "batch_size": 8, "steps_per_call": spc, **cfg}
    if numerics_on:
        config["numerics"] = True
    model = TinyModel(config)
    exch = exch_cls(config)
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    return model, exch


def _drive(model, exch, k=1, n_steps=8):
    """Worker-loop shape (test_fused_exchange idiom): count strides by
    steps_per_call, the standalone hook still called — fused exchangers
    stand down by themselves."""
    costs = []
    for count in range(k, n_steps + 1, k):
        model.train_iter(count, None)
        exch.exchange(None, count)
        costs.append(float(model.current_info["cost"]))
    return jax.device_get(model.step_state), costs


def _assert_state_equal(a, b):
    for part in ("params", "opt_state", "extra"):
        for x, y in zip(jax.tree_util.tree_leaves(a[part]),
                        jax.tree_util.tree_leaves(b[part])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=part)


# -- inertness: the tentpole guarantee ---------------------------------------

@pytest.mark.parametrize("exch_cls,spc,cfg", [
    (BSP_Exchanger, 1, {}),
    (BSP_Exchanger, 4, {}),                         # fused in-scan sampling
    (EASGD_Exchanger, 1, {"sync_freq": 2}),
    (BSP_Exchanger, 1, {"exch_strategy": "onebit"}),
], ids=["bsp", "bsp-fused-spc4", "easgd", "onebit"])
def test_numerics_observer_is_inert(exch_cls, spc, cfg):
    s_off, c_off = _drive(*_build(exch_cls, spc, **cfg), k=spc)
    s_on, c_on = _drive(*_build(exch_cls, spc, numerics_on=True, **cfg),
                        k=spc)
    _assert_state_equal(s_off, s_on)
    np.testing.assert_array_equal(np.asarray(c_off), np.asarray(c_on))


def test_numerics_off_exposes_no_aux():
    model, exch = _build(BSP_Exchanger)
    _drive(model, exch)
    assert model.numerics_aux is None


# -- beacon semantics (traced plane) -----------------------------------------

def test_bsp_digests_bitwise_equal_and_stats_live():
    model, exch = _build(BSP_Exchanger, numerics_on=True)
    _drive(model, exch, n_steps=6)
    aux = jax.device_get(model.numerics_aux)
    rep = numerics.host_report(aux)
    assert rep is not None and rep["iter"] == 6
    assert rep["n_workers"] == N
    # BSP post-exchange replicas are bit-identical → the per-rank digests
    # are EXACTLY equal floats, and the gathered divergence is exactly 0.0
    digests = rep["per_rank"]["digest"]
    assert all(d == digests[0] for d in digests), digests
    assert rep["divergence"] == 0.0
    assert all(b == 1.0 for b in rep["per_rank"]["beacon"])
    # the stats read live values: a real training step has nonzero norms
    assert rep["grad_norm"] > 0 and rep["param_norm"] > 0
    assert rep["update_norm"] > 0 and rep["update_ratio"] > 0
    assert rep["nonfinite"] == 0
    assert math.isfinite(rep["grad_max_abs"]) and rep["grad_max_abs"] > 0


def test_bsp_corrupted_digest_reads_as_divergence():
    model, exch = _build(BSP_Exchanger, numerics_on=True)
    _drive(model, exch, n_steps=4)
    aux = jax.device_get(model.numerics_aux)
    aux = {k: np.asarray(v).copy() for k, v in aux.items()}
    aux["digest"][2] += 1e-3            # one replica bit-desyncs
    rep = numerics.host_report(aux)
    # f32 digest arithmetic: the perturbation lands to ulp precision
    assert rep["divergence"] == pytest.approx(1e-3, rel=1e-2)


def test_easgd_reports_exact_distance_to_center():
    model, exch = _build(EASGD_Exchanger, numerics_on=True, sync_freq=2)
    # odd last step: the unfused sample reads the extra tree of ITS OWN
    # step (pre-exchange), so stop where no sync round follows and the
    # final state is exactly what the sample saw
    _drive(model, exch, n_steps=7)
    aux = jax.device_get(model.numerics_aux)
    rep = numerics.host_report(aux)
    # ‖w_i − c‖ — the central quantity of the source paper — recomputed
    # here against the live state the dispatch returned
    params = jax.device_get(model.step_state["params"])
    center = jax.device_get(model.step_state["extra"]["center"])
    for w in range(N):
        want = math.sqrt(sum(
            float(np.sum(np.square(
                np.asarray(p[w], np.float64) -
                np.asarray(c[w], np.float64))))
            for p, c in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(center))))
        got = rep["per_rank"]["dist_center"][w]
        np.testing.assert_allclose(got, want, rtol=1e-4)
    assert rep["dist_center"] == max(rep["per_rank"]["dist_center"])
    # the center copies must agree → the beacon digests them, divergence 0
    assert rep["divergence"] == 0.0


def test_onebit_streams_error_feedback_norm():
    model, exch = _build(BSP_Exchanger, numerics_on=True,
                         exch_strategy="onebit")
    _drive(model, exch, n_steps=6)
    rep = numerics.host_report(jax.device_get(model.numerics_aux))
    # the 1-bit quantizer always leaves a residual on a real gradient
    assert rep["ef_norm"] > 0


def test_cadence_spc1_documented_semantics():
    # spc=1 has no scan to carry a sample through: an off-cadence
    # dispatch returns the template and the host report skips it (§25) —
    # align numerics_every with the print cadence to see every sample
    model, exch = _build(BSP_Exchanger, numerics_on=True, numerics_every=4)
    _drive(model, exch, n_steps=6)
    assert numerics.host_report(jax.device_get(model.numerics_aux)) is None
    model, exch = _build(BSP_Exchanger, numerics_on=True, numerics_every=4)
    _drive(model, exch, n_steps=8)
    rep = numerics.host_report(jax.device_get(model.numerics_aux))
    assert rep is not None and rep["iter"] == 8


def test_cadence_fused_carries_latest_sample():
    # inside a fused window the scan carry holds the latest sample: the
    # spc=4 window ending at count 8 runs c = 5..8, only c=6 is on the
    # every=3 cadence, and THAT sample survives to the window's output
    model, exch = _build(BSP_Exchanger, spc=4, numerics_on=True,
                         numerics_every=3)
    _drive(model, exch, k=4, n_steps=8)
    rep = numerics.host_report(jax.device_get(model.numerics_aux))
    assert rep is not None and rep["iter"] == 6


# -- host report plane -------------------------------------------------------

def test_host_report_none_before_first_sample():
    assert numerics.host_report(None) is None
    aux = {k: [0.0, 0.0] for k in numerics.SAMPLE_KEYS}
    aux["iter"] = [-1.0, -1.0]
    assert numerics.host_report(aux) is None


def test_host_report_worst_rank_aggregation():
    aux = {k: [0.0, 0.0] for k in numerics.SAMPLE_KEYS}
    aux.update(iter=[8.0, 8.0], grad_norm=[1.0, 3.0],
               grad_max_abs=[0.5, 0.25], nonfinite=[1.0, 2.0],
               param_norm=[10.0, 20.0], update_norm=[0.1, 0.2],
               update_ratio=[0.01, 0.002], dist_center=[0.3, 0.7],
               ef_norm=[0.0, 0.4], digest=[5.0, 5.5], beacon=[1.0, 1.0])
    rep = numerics.host_report(aux)
    assert rep["grad_norm"] == 3.0 and rep["grad_max_abs"] == 0.5
    assert rep["nonfinite"] == 3.0                     # summed, not max'd
    assert rep["update_ratio"] == 0.002                # min: the collapse
    assert rep["dist_center"] == 0.7 and rep["ef_norm"] == 0.4
    assert rep["divergence"] == pytest.approx(0.5)


def test_host_report_divergence_nan_safe_and_beacon_gated():
    aux = {k: [0.0, 0.0] for k in numerics.SAMPLE_KEYS}
    aux.update(iter=[2.0, 2.0], digest=[1.0, float("nan")],
               beacon=[1.0, 1.0])
    # a corrupted replica whose digest went nan must still TRIP the
    # beacon, not slip through max() comparisons
    assert numerics.host_report(aux)["divergence"] == float("inf")
    aux["beacon"] = [1.0, 0.0]           # <2 valid beacons → no verdict
    assert numerics.host_report(aux)["divergence"] is None


def test_record_covers_declared_vocabulary():
    tm = telemetry.Telemetry(rank=0, run_id="numerics-test")
    numerics.record(tm, numerics.example_report(), rank=3)
    assert set(numerics.NUMERICS_GAUGES) <= set(tm.gauges)
    assert set(numerics.NUMERICS_HISTOGRAMS) <= set(tm.hists)
    evs = [e for e in tm.tail(4) if e["ev"] == numerics.NUMERICS_EVENT]
    assert len(evs) == 1 and evs[0]["rank"] == 3
    assert evs[0]["beacon"] == 1
    # divergence None (no beacon) still gauges 0.0 and events as None
    rep = dict(numerics.example_report())
    rep["divergence"] = None
    rep["iter"] = 2
    numerics.record(tm, rep)
    assert tm.gauges["numerics.divergence"] == 0.0
    ev = [e for e in tm.tail(4) if e["ev"] == numerics.NUMERICS_EVENT][-1]
    assert ev["divergence"] is None and ev["beacon"] == 0


# -- sentry detectors --------------------------------------------------------

def _rep(**kw):
    rep = dict(numerics.example_report())
    rep.update(kw)
    return rep


def test_sentry_detector_order_and_kinds():
    s = TrainingSentry({"verbose": False}, telemetry=telemetry.DISABLED)
    # overflow wins even when the report ALSO diverges
    assert s.observe_numerics(_rep(iter=1, nonfinite=2.0,
                                   divergence=9.0)) == "grad_overflow"
    assert s.observe_numerics(_rep(iter=2, divergence=1e-6)) == \
        "replica_divergence"
    assert s.observe_numerics(_rep(iter=3, update_ratio=1e-15)) == \
        "update_ratio_collapse"
    assert s.observe_numerics(_rep(iter=4)) is None     # healthy
    # a non-finite grad_norm is an overflow even with nonfinite count 0
    assert s.observe_numerics(_rep(iter=5, grad_norm=float("inf"))) == \
        "grad_overflow"
    assert [k for k, _ in s.anomalies] == \
        ["grad_overflow", "replica_divergence", "update_ratio_collapse",
         "grad_overflow"]
    assert set(k for k, _ in s.anomalies) <= set(numerics.SENTRY_KINDS)


def test_sentry_iter_dedupe_latest_sample_carry():
    s = TrainingSentry({"verbose": False}, telemetry=telemetry.DISABLED)
    bad = _rep(iter=7, nonfinite=1.0)
    assert s.observe_numerics(bad) == "grad_overflow"
    # the aux is a latest-sample carry: the SAME sampled step surfacing
    # under the next print record must not double-count
    assert s.observe_numerics(bad) is None
    assert s.observe_numerics(_rep(iter=9, nonfinite=1.0)) == \
        "grad_overflow"


def test_sentry_discontinuity_consumes_one_report():
    s = TrainingSentry({"verbose": False}, telemetry=telemetry.DISABLED)
    s.notice_discontinuity()
    # first report after a val/ckpt/restore boundary: neither judged nor
    # learned from (a rejoin legitimately moves the beacon)
    assert s.observe_numerics(_rep(iter=1, divergence=5.0)) is None
    assert s.observe_numerics(_rep(iter=2, divergence=5.0)) == \
        "replica_divergence"


def test_sentry_thresholds_are_config_knobs():
    s = TrainingSentry({"verbose": False, "sentry_divergence_eps": 10.0},
                       telemetry=telemetry.DISABLED)
    assert s.observe_numerics(_rep(iter=1, divergence=5.0)) is None
    assert s.observe_numerics(_rep(iter=2, divergence=11.0)) == \
        "replica_divergence"
    s2 = TrainingSentry({"verbose": False, "sentry_ratio_floor": 0.5},
                        telemetry=telemetry.DISABLED)
    assert s2.observe_numerics(_rep(iter=3, update_ratio=0.4)) == \
        "update_ratio_collapse"
    assert s2.observe_numerics(_rep(iter=4, update_ratio=0.6)) is None


def test_sentry_none_report_is_noop():
    s = TrainingSentry({"verbose": False}, telemetry=telemetry.DISABLED)
    assert s.observe_numerics(None) is None
    assert s.anomalies == []
