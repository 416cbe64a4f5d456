"""Fused exchange cadence (ISSUE 1 tentpole): ``steps_per_call > 1`` for
EVERY rule — the exchange algebra runs IN-SCAN via ``lax.cond`` on the
step count, so one XLA dispatch covers k full steps including their
cadenced exchanges.

Contracts pinned here:

* bit-equivalence — k steps fused must equal k single-step dispatches
  driven through the Python exchange hook, for EASGD / ASGD / BSP (params
  mode, and grads mode on every error-fed or cast wire) exactly, for
  EASGD / ASGD with a demoted set, and for GoSGD given the same traced
  gossip draws (the fused path derives them from
  ``steps.fused_exchange_key``; the standalone run is handed the same
  base key);
* one dispatch per window — ``train_fn`` fires once per k-step window and
  the standalone ``_exchange_fn`` never fires;
* recorder sanity — with the exchange in-scan, its cost rides the
  ``train`` bucket and ``t_comm`` stays zero (nothing double-counts).
"""

import jax
import numpy as np
import pytest

from tests.conftest import TinyModel
from theanompi_tpu.parallel import steps
from theanompi_tpu.parallel.exchanger import (ASGD_Exchanger, BSP_Exchanger,
                                              EASGD_Exchanger,
                                              GOSGD_Exchanger)
from theanompi_tpu.parallel.mesh import worker_mesh
from theanompi_tpu.utils.recorder import Recorder


def _build(exch_cls, spc, n=4, active=None, **cfg):
    mesh = worker_mesh(n)
    config = {"mesh": mesh, "size": n, "rank": 0, "verbose": False,
              "batch_size": 8, "steps_per_call": spc, **cfg}
    model = TinyModel(config)
    exch = exch_cls(config)
    if active is not None:
        # demote BEFORE compile so both dispatch shapes trace the mask
        exch.mesh, exch.model, exch.size = mesh, model, n
        exch.set_active_ranks(active)
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    return model, exch


def _drive(model, exch, k, n_steps=4):
    """Worker-loop shape: count strides by steps_per_call; the Python hook
    is still CALLED (as the worker would for spc=1) — for fused exchangers
    it must stand down by itself."""
    for count in range(k, n_steps + 1, k):
        model.train_iter(count, None)
        exch.exchange(None, count)
    return jax.device_get(model.step_state)


def _assert_state_equal(a, b, parts=("params", "opt_state", "extra")):
    for part in parts:
        for x, y in zip(jax.tree_util.tree_leaves(a[part]),
                        jax.tree_util.tree_leaves(b[part])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=part)


@pytest.mark.parametrize("exch_cls,cfg", [
    (EASGD_Exchanger, {"sync_freq": 2, "alpha": 0.5}),
    (EASGD_Exchanger, {"sync_freq": 3}),     # freq not dividing k: the
    (ASGD_Exchanger, {"sync_freq": 1}),      # lax.cond gate must skip steps
    (ASGD_Exchanger, {"sync_freq": 2}),
    (BSP_Exchanger, {"exch_mode": "params"}),
    # grads mode: the wire runs inside every scanned step, and the
    # compressed wires carry their error feedback through the scan
    (BSP_Exchanger, {"exch_strategy": "allreduce"}),
    (BSP_Exchanger, {"exch_strategy": "nccl16"}),
    (BSP_Exchanger, {"exch_strategy": "onebit"}),
    (BSP_Exchanger, {"exch_strategy": "topk"}),
    (BSP_Exchanger, {"exch_strategy": "powersgd"}),
], ids=["easgd-f2", "easgd-f3", "asgd-f1", "asgd-f2", "bsp-params",
        "bsp-allreduce", "bsp-nccl16", "bsp-onebit", "bsp-topk",
        "bsp-powersgd"])
def test_fused_bit_equals_unfused(exch_cls, cfg):
    s1 = _drive(*_build(exch_cls, 1, **cfg), k=1)
    s4 = _drive(*_build(exch_cls, 4, **cfg), k=4)
    _assert_state_equal(s1, s4)


@pytest.mark.parametrize("exch_cls,cfg", [
    (EASGD_Exchanger, {"sync_freq": 1}),
    (EASGD_Exchanger, {"sync_freq": 2}),
    (ASGD_Exchanger, {"sync_freq": 1}),
], ids=["easgd-f1", "easgd-f2", "asgd-f1"])
def test_fused_demoted_set_bit_equals_unfused(exch_cls, cfg):
    """Ranks (0, 2) active: the membership mask traced into the scanned
    window gives the state two single dispatches give, bit for bit, and
    the mask is live (the full set ends elsewhere)."""
    s1 = _drive(*_build(exch_cls, 1, active=(0, 2), **cfg), k=1)
    s2 = _drive(*_build(exch_cls, 2, active=(0, 2), **cfg), k=2)
    _assert_state_equal(s1, s2)
    full = _drive(*_build(exch_cls, 2, **cfg), k=2)
    assert any(not np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(s2["params"]),
        jax.tree_util.tree_leaves(full["params"])))


@pytest.mark.parametrize("peers", ["perm", "shift", "iid"])
def test_fused_gosgd_bit_equal_given_same_draws(peers):
    """The GoSGD RNG contract: every gossip draw is a traced function of
    (base key, count).  Fused mode derives the base key as
    ``steps.fused_exchange_key(step_rng)``; hand the unfused run the same
    base key (instead of its host-split stream) and the two paths must
    agree bit-for-bit — send gates, routing picks, merges and all."""
    cfg = {"exch_prob": 0.7, "gosgd_peers": peers}
    model1, exch1 = _build(GOSGD_Exchanger, 1, **cfg)
    base = steps.fused_exchange_key(model1._step_rng)
    model1.next_exchange_key = lambda: base
    s1 = _drive(model1, exch1, k=1)
    s4 = _drive(*_build(GOSGD_Exchanger, 4, **cfg), k=4)
    _assert_state_equal(s1, s4)
    # α stays a conserved redistribution in fused mode too
    alpha = np.asarray(s4["extra"]["alpha"]).reshape(-1)
    np.testing.assert_allclose(alpha.sum(), 4.0, rtol=1e-5)


def test_one_dispatch_per_window_async_rules():
    """The acceptance criterion, counted: with steps_per_call=k an async
    rule costs ONE train_fn dispatch per k-step window and ZERO standalone
    _exchange_fn dispatches (the cadence lives inside the scan)."""
    model, exch = _build(EASGD_Exchanger, 4, sync_freq=2)
    calls = {"train": 0, "exch": 0}
    train_fn, exch_fn = model.train_fn, exch._exchange_fn

    def count_train(*a, **kw):
        calls["train"] += 1
        return train_fn(*a, **kw)

    def count_exch(*a, **kw):
        calls["exch"] += 1
        return exch_fn(*a, **kw)

    model.train_fn = count_train
    exch._exchange_fn = count_exch
    _drive(model, exch, k=4, n_steps=8)      # 2 windows of 4 steps
    assert calls == {"train": 2, "exch": 0}
    # same rule unfused: k dispatches + the due exchanges, for contrast
    model1, exch1 = _build(EASGD_Exchanger, 1, sync_freq=2)
    calls1 = {"train": 0, "exch": 0}
    train_fn1, exch_fn1 = model1.train_fn, exch1._exchange_fn
    model1.train_fn = lambda *a, **kw: (
        calls1.__setitem__("train", calls1["train"] + 1) or train_fn1(*a, **kw))
    exch1._exchange_fn = lambda *a, **kw: (
        calls1.__setitem__("exch", calls1["exch"] + 1) or exch_fn1(*a, **kw))
    _drive(model1, exch1, k=1, n_steps=8)
    assert calls1 == {"train": 8, "exch": 4}


def test_fused_worker_loop_skips_python_hook():
    """The worker loop's skip path: exchange() is a no-op while fused —
    state is untouched and no recorder comm section opens."""
    model, exch = _build(GOSGD_Exchanger, 2, exch_prob=1.0)
    assert exch.fused
    model.train_iter(2, None)
    before = jax.device_get(model.step_state["params"])
    rec = Recorder({"verbose": False})
    exch.exchange(rec, 2)
    after = jax.device_get(model.step_state["params"])
    for x, y in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(x, y)
    assert rec.t_sec_total["comm"] == 0.0


def test_recorder_t_comm_sane_when_fused():
    """With the exchange in-scan, its time lands in the train bucket:
    t_comm accumulates exactly zero over a fused run, t_train is positive,
    and the print path digests the stride without error."""
    model, exch = _build(EASGD_Exchanger, 2, sync_freq=2,
                         sync_each_iter=True)
    rec = Recorder({"verbose": False, "printFreq": 2, "size": 4})
    for count in (2, 4):
        model.train_iter(count, rec)
        exch.exchange(rec, count)
        rec.print_train_info(count, stride=2)
    assert rec.t_sec_total["comm"] == 0.0
    assert rec.t_sec_total["train"] > 0.0
    assert rec.n_images_total == 8 * 4 * 4   # rows/worker × workers × steps
    # contrast: the unfused cadence DOES book comm time when due
    model1, exch1 = _build(EASGD_Exchanger, 1, sync_freq=1,
                           sync_each_iter=True)
    rec1 = Recorder({"verbose": False, "printFreq": 2, "size": 4})
    for count in (1, 2):
        model1.train_iter(count, rec1)
        exch1.exchange(rec1, count)
    assert rec1.t_sec_total["comm"] > 0.0


def test_fused_easgd_center_still_canonical():
    """Validation semantics survive fusing: the center moves and
    begin_val snapshots it exactly as in the unfused cadence."""
    model, exch = _build(EASGD_Exchanger, 2, sync_freq=1, alpha=0.5)
    c0 = jax.device_get(exch.canonical_params(model.step_state))
    _drive(model, exch, k=2, n_steps=4)
    c1 = jax.device_get(exch.canonical_params(model.step_state))
    moved = any(not np.allclose(a, b)
                for a, b in zip(jax.tree_util.tree_leaves(c0),
                                jax.tree_util.tree_leaves(c1)))
    assert moved
    model.begin_val()
    model.val_iter(1, None)
    model.end_val()


def test_recompile_to_single_step_clears_fused_flag():
    """Recompiling the SAME exchanger back to steps_per_call=1 must clear
    the fused flag — a stale True would no-op exchange() forever and
    silently degrade the rule to local-only SGD."""
    mesh = worker_mesh(4)
    config = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
              "batch_size": 8, "steps_per_call": 2, "sync_freq": 1}
    exch = EASGD_Exchanger(config)
    model = TinyModel(config)
    model.compile_iter_fns(exch)
    assert exch.fused
    model2 = TinyModel({**config, "steps_per_call": 1})
    model2.compile_iter_fns(exch)
    assert not exch.fused
    model2.data.shuffle_data(0)
    model2.train_iter(1, None)
    before = jax.device_get(steps.unbox(model2.step_state["extra"])["center"])
    exch.exchange(None, 1)               # must actually run again
    after = jax.device_get(steps.unbox(model2.step_state["extra"])["center"])
    moved = any(not np.array_equal(a, b)
                for a, b in zip(jax.tree_util.tree_leaves(before),
                                jax.tree_util.tree_leaves(after)))
    assert moved


def test_legacy_exchanger_pattern_fails_loudly_under_spc():
    """An out-of-tree exchanger on the pre-round-6 pattern (jits
    _exchange_fn in prepare() without declaring has_exchange) must be
    REFUSED under steps_per_call > 1 — its cadence would neither fuse nor
    fire per-step from the spc-strided worker loop."""
    from theanompi_tpu.parallel.exchanger import Exchanger

    class LegacyExchanger(Exchanger):
        def prepare(self, mesh, model):
            super().prepare(mesh, model)
            self._exchange_fn = lambda state, key, count: state

    mesh = worker_mesh(4)
    cfg = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
           "batch_size": 8, "steps_per_call": 2}
    model = TinyModel(cfg)
    with pytest.raises(AssertionError, match="has_exchange"):
        model.compile_iter_fns(LegacyExchanger(cfg))
