"""ZeRO-1 sharded optimizer state (parallel/zero.py): bit-equal to the
replicated optimizer, with per-chip optimizer memory 1/N."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import TinyModel
from theanompi_tpu.models.transformer_lm import TransformerLM
from theanompi_tpu.parallel import steps
from theanompi_tpu.parallel.exchanger import BSP_Exchanger, get_exchanger
from theanompi_tpu.parallel.mesh import WORKER_AXIS, worker_mesh


def _train(model, exch, n_steps):
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    costs = []
    for i in range(n_steps):
        model.train_iter(i, None)
        costs.append(float(model.current_info["cost"]))
    return costs


def _make_tiny(zero, mesh, **kw):
    cfg = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
           "zero_opt": zero, **kw}
    return TinyModel(cfg), cfg


def test_zero1_ragged_chunking_is_explicit():
    """P=10, N=4 (the ragged case): chunk ceil(10/4)=3, padded length 12.
    Callers pad to ``padded_size`` EXPLICITLY before slicing — a ragged
    flat must never rely on a downstream implicit zero-fill (dynamic_slice
    would silently clamp an 11th-element read)."""
    from theanompi_tpu.parallel import zero as zero_lib
    assert zero_lib.chunk_size(10, 4) == 3
    assert zero_lib.padded_size(10, 4) == 12
    # and the boxed re-partition round-trips the ragged layout exactly
    flat = np.arange(10, dtype=np.float32)
    boxed4 = np.pad(flat, (0, 2)).reshape(4, 3)
    boxed2 = zero_lib.rechunk_boxed(boxed4, 2, 1, 10)
    assert boxed2.shape == (2, 5)
    np.testing.assert_array_equal(boxed2.reshape(-1)[:10], flat)
    back = zero_lib.rechunk_boxed(boxed2, 4, 1, 10)
    np.testing.assert_array_equal(back, boxed4)


def test_zero1_bit_equal_to_replicated(mesh4):
    """Same data, same seed: the ZeRO-sharded optimizer must trace the
    replicated optimizer's params EXACTLY (elementwise math on disjoint
    chunks; no reduction-order change)."""
    for optimizer in ("momentum", "adam"):
        base, _ = _make_tiny(False, mesh4, optimizer=optimizer)
        zero, _ = _make_tiny(True, mesh4, optimizer=optimizer)
        c0 = _train(base, BSP_Exchanger(base.config), 6)
        c1 = _train(zero, BSP_Exchanger(zero.config), 6)
        np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
        p0 = steps.unbox(jax.device_get(base.step_state["params"]))
        p1 = steps.unbox(jax.device_get(zero.step_state["params"]))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), p0, p1)


def test_zero1_state_is_sharded(mesh4):
    """Optimizer memory: each worker holds ONE ceil(P/N) chunk (adam: m, v,
    t per chunk) instead of a full replica."""
    model, _ = _make_tiny(True, mesh4, optimizer="adam")
    model.compile_iter_fns(BSP_Exchanger(model.config))
    n_params = model.n_params
    chunk = -(-n_params // 4)
    m = model.step_state["opt_state"]["opt"]["m"]
    assert m.shape == (4, chunk)                      # boxed = the partition
    assert m.sharding.spec == (WORKER_AXIS,)
    # the four chunks diverge once training starts (they cover different
    # parameter ranges)
    _train(model, model.exchanger, 3)
    mm = np.asarray(jax.device_get(model.step_state["opt_state"]["opt"]["m"]))
    assert not np.allclose(mm[0], mm[1])


def test_zero1_checkpoint_roundtrip(tmp_path, mesh4):
    model, _ = _make_tiny(True, mesh4, optimizer="momentum")
    _train(model, BSP_Exchanger(model.config), 3)
    model.save(str(tmp_path), epoch=0, count=3)
    # per-part dedup: params (bit-identical replicas) stored ONCE, only the
    # genuinely per-worker ZeRO chunks stored boxed
    import json, os
    with open(os.path.join(str(tmp_path), "ckpt_epoch0.json")) as f:
        meta = json.load(f)
    assert meta["boxed_parts"] == ["opt_state"], meta
    import numpy as np_
    data = np_.load(os.path.join(str(tmp_path), "ckpt_epoch0.npz"))
    p_leaf = data["params__0"]
    unboxed = steps.unbox(jax.device_get(model.step_state["params"]))
    assert p_leaf.shape == jax.tree.leaves(unboxed)[0].shape
    before = jax.device_get(steps.tree_to_host(model.step_state["opt_state"]))
    m2, _ = _make_tiny(True, mesh4, optimizer="momentum")
    m2.compile_iter_fns(BSP_Exchanger(m2.config))
    assert m2.load(str(tmp_path)) == 0
    after = jax.device_get(steps.tree_to_host(m2.step_state["opt_state"]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), before, after)
    m2.data.shuffle_data(0)
    m2.train_iter(3, None)


def test_zero1_rejects_async_rules(mesh4):
    """(tp composition is no longer rejected — round-4; see the tp tests
    below.)"""
    model, cfg = _make_tiny(True, mesh4, optimizer="momentum",
                            sync_freq=2)
    with pytest.raises(AssertionError, match="BSP grads"):
        model.compile_iter_fns(get_exchanger("easgd", cfg))
    # params mode / 'none' strategy never reduce grads — ZeRO would slice
    # UN-reduced per-worker grads and train silently wrong
    for bad in ({"exch_mode": "params"}, {"exch_strategy": "none"}):
        m, c = _make_tiny(True, mesh4, optimizer="momentum", **bad)
        with pytest.raises(AssertionError, match="grads"):
            m.compile_iter_fns(BSP_Exchanger(c))


def test_zero1_transformer_with_compressed_wire(mesh8):
    """ZeRO composes with the EF-compressed wire (grads identical across
    workers after decode) on the LM family."""
    mesh = worker_mesh(8)
    cfg = {"mesh": mesh, "size": 8, "rank": 0, "verbose": False,
           "zero_opt": True, "exch_strategy": "onebit",
           "batch_size": 8, "seq_len": 16, "vocab": 32, "d_model": 32,
           "n_head": 4, "n_layer": 2, "synthetic_train": 128,
           "compute_dtype": jnp.float32}
    model = TransformerLM(cfg)
    costs = _train(model, BSP_Exchanger(cfg), 6)
    assert np.isfinite(costs).all()
    assert np.mean(costs[-3:]) < np.mean(costs[:3])


def test_zero1_checkpoint_is_worker_count_portable(tmp_path, mesh4, mesh8):
    """Elastic resume for ZeRO chunks (round-4, matching fsdp): the boxed
    optimizer chunks re-partition onto a different worker count on load —
    the reassembled optimizer flat is identical, and training continues."""
    d = str(tmp_path / "ckpt")
    m4, _ = _make_tiny(True, mesh4, optimizer="adam")
    _train(m4, BSP_Exchanger(m4.config), 3)
    m4.save(d, epoch=0, count=3)
    ref_p = steps.unbox(jax.device_get(m4.step_state["params"]))
    ref_m = np.asarray(jax.device_get(
        m4.step_state["opt_state"]["opt"]["m"])).reshape(-1)[:m4.n_params]

    cfg8 = {"mesh": mesh8, "size": 8, "rank": 0, "verbose": False,
            "zero_opt": True, "optimizer": "adam"}
    m8 = TinyModel(cfg8)
    m8.compile_iter_fns(BSP_Exchanger(cfg8))
    assert m8.load(d) == 0
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
        ref_p, steps.unbox(jax.device_get(
            jax.tree.map(lambda x: x[:1], m8.step_state["params"]))))
    got_m = np.asarray(jax.device_get(
        m8.step_state["opt_state"]["opt"]["m"])).reshape(-1)[:m8.n_params]
    np.testing.assert_array_equal(ref_m, got_m)
    t8 = np.asarray(jax.device_get(m8.step_state["opt_state"]["opt"]["t"]))
    assert t8.shape == (8,) and (t8 == t8[0]).all() and t8[0] == 3
    m8.data.shuffle_data(0)
    m8.train_iter(3, None)               # and it keeps training


def test_zero1_ckpt_portable_under_tp(tmp_path, mesh8):
    """ZeRO chunk re-partition under tensor parallelism: dp=2×tp=2 saved,
    resumed on dp=4×tp=2 — each model rank's local flat reassembles
    identically across the two worker-chunkings."""
    base = _make_tp_lm(True, dp=2, tp=2, optimizer="adam")
    _train(base, BSP_Exchanger(base.config), 3)
    d = str(tmp_path / "ckpt")
    base.save(d, epoch=0, count=3)
    from theanompi_tpu.parallel import zero as zero_lib
    lay = base._zero_layout
    m_saved = np.asarray(jax.device_get(
        base.step_state["opt_state"]["opt"]["m"]))

    m2 = _make_tp_lm(True, dp=4, tp=2, optimizer="adam")
    m2.compile_iter_fns(BSP_Exchanger(m2.config))
    assert m2.load(d) == 0
    m_new = np.asarray(jax.device_get(
        m2.step_state["opt_state"]["opt"]["m"]))
    # reassembling per model rank must agree between the two layouts
    def per_rank(arr, n):
        c = arr.shape[1] // lay["shards"]
        return np.transpose(arr.reshape(n, lay["shards"], c),
                            (1, 0, 2)).reshape(lay["shards"],
                                               -1)[:, :lay["local_total"]]
    np.testing.assert_array_equal(per_rank(m_saved, 2), per_rank(m_new, 4))
    assert m_new.shape == (4, lay["shards"] * zero_lib.chunk_size(
        lay["local_total"], 4))
    m2.data.shuffle_data(0)
    m2.train_iter(3, None)


# -- round 4: composition with tensor parallelism ---------------------------

TP_LM = dict(verbose=False, batch_size=8, seq_len=16, vocab=32,
             synthetic_train=64, synthetic_val=32, d_model=32, n_head=4,
             n_layer=2, compute_dtype=jnp.float32)


def _make_tp_lm(zero, dp=2, tp=2, **kw):
    mesh = worker_mesh(dp, tp=tp)
    cfg = {**TP_LM, "mesh": mesh, "size": dp, "rank": 0, "tp": tp,
           "zero_opt": zero, **kw}
    return TransformerLM(cfg)


@pytest.mark.parametrize("optimizer", ["momentum", "adam"])
def test_zero1_bit_equal_under_tp(mesh8, optimizer):
    """dp=2 × tp=2: the ZeRO partition now chunks each device's LOCAL param
    shard — still bit-equal to the replicated optimizer on the same layout
    (round-3 verdict #6)."""
    base = _make_tp_lm(False, optimizer=optimizer)
    zero = _make_tp_lm(True, optimizer=optimizer)
    c0 = _train(base, BSP_Exchanger(base.config), 5)
    c1 = _train(zero, BSP_Exchanger(zero.config), 5)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    p0 = jax.device_get(steps.tree_to_host(base.step_state["params"]))
    p1 = jax.device_get(steps.tree_to_host(zero.step_state["params"]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), p0, p1)


def test_zero1_state_sharded_over_workers_and_model(mesh8):
    """The chunk state varies over BOTH axes: boxed [dp, tp·chunk] sharded
    P(workers, model) — per-device optimizer memory is local_P/dp."""
    from theanompi_tpu.parallel.mesh import MODEL_AXIS
    model = _make_tp_lm(True, optimizer="adam")
    model.compile_iter_fns(BSP_Exchanger(model.config))
    m = model.step_state["opt_state"]["opt"]["m"]
    local = steps.local_param_template(model.params, model.param_specs(),
                                       model.mesh)
    from theanompi_tpu.utils import helper_funcs
    chunk = -(-helper_funcs.tree_size(local) // 2)
    assert m.shape == (2, 2 * chunk), m.shape
    assert m.sharding.spec == (WORKER_AXIS, (MODEL_AXIS,)) or \
        m.sharding.spec == (WORKER_AXIS, MODEL_AXIS), m.sharding.spec
    # each device's addressable block is exactly one chunk
    assert m.addressable_shards[0].data.shape == (1, chunk)


@pytest.mark.slow
def test_zero1_bit_equal_under_pp(mesh8):
    """Pipeline composition: zero chunks each stage's local stack shard;
    bit-equal to the replicated optimizer on the same pp layout."""
    def make(zero):
        mesh = worker_mesh(2, pp=2)
        cfg = {**TP_LM, "mesh": mesh, "size": 2, "rank": 0, "tp": 1,
               "pp": 2, "zero_opt": zero, "optimizer": "adam"}
        return TransformerLM(cfg)
    base, zero = make(False), make(True)
    c0 = _train(base, BSP_Exchanger(base.config), 4)
    c1 = _train(zero, BSP_Exchanger(zero.config), 4)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    p0 = jax.device_get(steps.tree_to_host(base.step_state["params"]))
    p1 = jax.device_get(steps.tree_to_host(zero.step_state["params"]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), p0, p1)


@pytest.mark.slow
def test_zero1_bit_equal_under_3d_mesh(mesh8):
    """dp=2 × pp=2 × tp=2: leaves sharded over ONE model axis but replicated
    over the other must anchor per-axis (the all-or-nothing anchor failed
    compile here)."""
    def make(zero):
        mesh = worker_mesh(2, tp=2, pp=2)
        cfg = {**TP_LM, "mesh": mesh, "size": 2, "rank": 0, "tp": 2,
               "pp": 2, "pp_microbatches": 2, "zero_opt": zero,
               "optimizer": "adam"}
        return TransformerLM(cfg)
    base, zero = make(False), make(True)
    c0 = _train(base, BSP_Exchanger(base.config), 3)
    c1 = _train(zero, BSP_Exchanger(zero.config), 3)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
