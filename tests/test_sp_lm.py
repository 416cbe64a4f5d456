"""Sequence parallelism as a model mode (parallel/sp.py): TransformerLM with
sp=k shards the TIME dimension over a 'seq' axis and runs ring attention —
it must be the same model as the dense layout (same init, same losses),
which also pins the batch-spec plumbing (x/y sharded [workers, seq]).

The ring-attention op itself is oracle-pinned in test_ring_attention.py;
this file pins the MODEL integration.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.models.transformer_lm import TransformerLM
from theanompi_tpu.parallel.exchanger import BSP_Exchanger, get_exchanger
from theanompi_tpu.parallel.mesh import SEQ_AXIS, WORKER_AXIS, worker_mesh

LM_CFG = dict(verbose=False, batch_size=8, seq_len=32, vocab=32,
              synthetic_train=64, synthetic_val=32,
              d_model=32, n_head=4, n_layer=2, compute_dtype=jnp.float32)


def _make(dp, sp, **kw):
    mesh = worker_mesh(dp, sp=sp)
    cfg = {**LM_CFG, "mesh": mesh, "size": dp, "rank": 0, "sp": sp, **kw}
    return TransformerLM(cfg)


def _train_steps(model, exch, n_steps):
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    costs = []
    for i in range(n_steps):
        model.train_iter(i, None)
        costs.append(float(model.current_info["cost"]))
    return costs


def test_sp_mesh_and_batch_sharding(mesh8):
    model = _make(dp=2, sp=4)
    assert dict(model.mesh.shape) == {WORKER_AXIS: 2, SEQ_AXIS: 4}
    model.compile_iter_fns(BSP_Exchanger(model.config))
    from theanompi_tpu.parallel import steps
    model.data.shuffle_data(0)
    batch = model.data.next_train_batch(0)
    dev = steps.put_batch(model.mesh, batch, model.batch_spec())
    assert dev["x"].sharding.spec == (WORKER_AXIS, SEQ_AXIS)
    # one chip holds [rows/dp, T/sp]
    assert dev["x"].addressable_shards[0].data.shape == (8, 8)


def test_sp_init_identical_to_dense(mesh8):
    dense = _make(dp=2, sp=1)
    sp = _make(dp=2, sp=4)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), dense.params, sp.params)


@pytest.mark.slow
def test_sp_bsp_training_matches_dense(mesh8):
    dense = _make(dp=2, sp=1)
    sp = _make(dp=2, sp=4)
    c_dense = _train_steps(dense, BSP_Exchanger(dense.config), 6)
    c_sp = _train_steps(sp, BSP_Exchanger(sp.config), 6)
    np.testing.assert_allclose(c_sp, c_dense, rtol=2e-4, atol=2e-5)
    from theanompi_tpu.parallel import steps
    pd = steps.unbox(jax.device_get(steps.tree_to_host(
        dense.step_state["params"])))
    ps = steps.unbox(jax.device_get(steps.tree_to_host(
        sp.step_state["params"])))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-5), pd, ps)


def test_sp_val_matches_dense(mesh8):
    dense = _make(dp=2, sp=1)
    sp = _make(dp=2, sp=4)
    for m in (dense, sp):
        m.compile_iter_fns(BSP_Exchanger(m.config))
        m.data.shuffle_data(0)
        m.begin_val()
    recs = []
    from theanompi_tpu.parallel import steps
    for m in (dense, sp):
        batch = m.data.next_val_batch(0)
        dev = steps.put_batch(m.mesh, batch, m.batch_spec())
        cost, err, err5 = m.val_fn(m._val_params_boxed, m._val_bn_boxed, dev)
        recs.append((float(np.mean(np.asarray(cost))),
                     float(np.mean(np.asarray(err)))))
    (cd, ed), (cs, es) = recs
    assert cd == pytest.approx(cs, abs=1e-4)
    assert ed == pytest.approx(es, abs=1e-6)


def test_sp_with_compressed_wire(mesh8):
    """EF compression under sp: params (and so grads, after the automatic
    transpose-psum) are replicated over 'seq', so the EF state stays
    replicated too — the default spec path must handle a 'seq'-axis mesh."""
    model = _make(dp=2, sp=4, exch_strategy="onebit")
    costs = _train_steps(model, BSP_Exchanger(model.config), 6)
    assert np.isfinite(costs).all()
    assert np.mean(costs[-3:]) < np.mean(costs[:3])
    ef = model.step_state["extra"]["strat"]
    assert ef.sharding.spec == (WORKER_AXIS,), ef.sharding.spec


def test_attn_impl_plumbing(mesh8):
    """attn_impl threads from config to every attention layer; 'flash' is
    TPU-only so CPU tests check the wiring, not the kernel."""
    model = _make(dp=2, sp=1)
    assert all(b.attn.attn_impl == "reference" for b in model.blocks)
    mesh = worker_mesh(2)
    cfg = {**LM_CFG, "mesh": mesh, "size": 2, "rank": 0,
           "attn_impl": "flash", "seq_len": 128}
    m2 = TransformerLM(cfg)
    assert all(b.attn.attn_impl == "flash" for b in m2.blocks)
    # flash needs 128-aligned sequence blocks — rejected at build time
    with pytest.raises(AssertionError, match="128"):
        TransformerLM({**cfg, "seq_len": 96})
    with pytest.raises(AssertionError):
        from theanompi_tpu.models import layers as L
        L.MultiHeadAttention(32, 4, attn_impl="nope")


def test_sp_with_async_rule_smoke(mesh8):
    model = _make(dp=2, sp=4, sync_freq=2)
    exch = get_exchanger("easgd", model.config)
    costs = _train_steps(model, exch, 4)
    exch.exchange(None, exch.exchange_freq)
    assert np.isfinite(costs).all()
    model.begin_val()
    model.val_iter(0, None)
    model.end_val()


@pytest.mark.slow
def test_sp_composes_with_steps_per_call(mesh8):
    """round-4 (verdict #4): the multi-step dispatch stacks sequence-
    parallel batches P(None, workers, seq) and must trace the same params
    as single-step dispatch on the same sp layout."""
    one = _make(dp=2, sp=4)
    c1 = _train_steps(one, BSP_Exchanger(one.config), 4)
    spc = _make(dp=2, sp=4, steps_per_call=2)
    spc.compile_iter_fns(BSP_Exchanger(spc.config))
    spc.data.shuffle_data(0)
    for count in (1, 3):              # each call covers steps {c-1, c}
        spc.train_iter(count, None)
    from theanompi_tpu.parallel import steps
    p1 = steps.unbox(jax.device_get(steps.tree_to_host(
        one.step_state["params"])))
    p2 = steps.unbox(jax.device_get(steps.tree_to_host(
        spc.step_state["params"])))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7), p1, p2)


@pytest.mark.slow
def test_sp_composes_with_tp_3d_mesh(mesh8):
    """round-4: dp=2 × tp=2 × sp=2 — head-sharded ring attention,
    vocab-parallel CE + seq-mean loss — must match the dense model (same
    seed, same data) up to fp32 summation order."""
    from theanompi_tpu.parallel.mesh import MODEL_AXIS
    dense = TransformerLM({**LM_CFG, "mesh": worker_mesh(2), "size": 2,
                           "rank": 0})
    m3 = TransformerLM({**LM_CFG, "mesh": worker_mesh(2, tp=2, sp=2),
                        "size": 2, "rank": 0, "tp": 2, "sp": 2})
    assert dict(m3.mesh.shape) == {WORKER_AXIS: 2, MODEL_AXIS: 2,
                                   SEQ_AXIS: 2}
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), dense.params, m3.params)
    c_dense = _train_steps(dense, BSP_Exchanger(dense.config), 5)
    c_3d = _train_steps(m3, BSP_Exchanger(m3.config), 5)
    np.testing.assert_allclose(c_3d, c_dense, rtol=3e-4, atol=3e-5)
    from theanompi_tpu.parallel import steps
    pd = steps.unbox(jax.device_get(steps.tree_to_host(
        dense.step_state["params"])))
    p3 = steps.unbox(jax.device_get(steps.tree_to_host(
        m3.step_state["params"])))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-4), pd, p3)
    # val path composes too (vocab-parallel metrics + seq mean)
    m3.begin_val()
    m3.val_iter(0)
    m3.end_val()


@pytest.mark.slow
def test_sp_composes_with_pp(mesh8):
    """round-4: dp=2 × pp=2 × sp=2 — pipeline stages of ring-attention
    blocks over sequence-sharded microbatches — matches the dense model."""
    from theanompi_tpu.parallel.mesh import PIPE_AXIS
    dense = TransformerLM({**LM_CFG, "mesh": worker_mesh(2), "size": 2,
                           "rank": 0})
    m3 = TransformerLM({**LM_CFG, "mesh": worker_mesh(2, pp=2, sp=2),
                        "size": 2, "rank": 0, "pp": 2, "sp": 2,
                        "pp_microbatches": 2})
    assert dict(m3.mesh.shape) == {WORKER_AXIS: 2, PIPE_AXIS: 2,
                                   SEQ_AXIS: 2}
    c_dense = _train_steps(dense, BSP_Exchanger(dense.config), 4)
    c_3d = _train_steps(m3, BSP_Exchanger(m3.config), 4)
    np.testing.assert_allclose(c_3d, c_dense, rtol=3e-4, atol=3e-5)
    m3.begin_val()
    m3.val_iter(0)
    m3.end_val()


@pytest.mark.slow
def test_moe_sp_pp_trains(mesh8):
    """MoE under sp×pp (round-4): the homogeneous all-MoE pipeline with
    sequence-sharded microbatches trains finite/decreasing and validates
    (the microbatch aux re-anchors its seq invariance after the pipeline
    scan)."""
    from theanompi_tpu.models.transformer_lm import MoETransformerLM
    m = MoETransformerLM({**LM_CFG, "mesh": worker_mesh(2, pp=2, sp=2),
                          "size": 2, "rank": 0, "pp": 2, "sp": 2,
                          "pp_microbatches": 2, "moe_every": 1,
                          "moe_experts": 4})
    costs = _train_steps(m, BSP_Exchanger(m.config), 4)
    assert np.isfinite(costs).all()
    assert np.mean(costs[-2:]) < np.mean(costs[:2])
    m.begin_val()
    m.val_iter(0)
    m.end_val()
