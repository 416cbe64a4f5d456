"""Tensor parallelism (parallel/tp.py): the TP transformer must be the SAME
model as the dense one — identical init, equal losses/metrics/updates up to
fp32 summation-order noise — just laid out over a 2-D dp×model mesh.

The reference (Theano-MPI) has no model parallelism; this is a beyond-parity
capability, so the oracle is our own dense TransformerLM.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.models.transformer_lm import TransformerLM
from theanompi_tpu.parallel.exchanger import (BSP_Exchanger, EASGD_Exchanger,
                                              get_exchanger)
from theanompi_tpu.parallel.mesh import MODEL_AXIS, WORKER_AXIS, worker_mesh
from theanompi_tpu.jax_compat import shard_map

LM_CFG = dict(verbose=False, batch_size=8, seq_len=16, vocab=32,
              synthetic_train=64, synthetic_val=32,
              d_model=32, n_head=4, n_layer=2, compute_dtype=jnp.float32)


def _make(dp, tp, **kw):
    mesh = worker_mesh(dp, tp=tp)
    cfg = {**LM_CFG, "mesh": mesh, "size": dp, "rank": 0, "tp": tp, **kw}
    return TransformerLM(cfg), cfg


def _train_steps(model, exch, n_steps):
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    costs = []
    for i in range(n_steps):
        model.train_iter(i, None)
        costs.append(float(model.current_info["cost"]))
    return costs


def test_tp_mesh_shape_and_param_shardings(mesh8):
    model, _ = _make(dp=2, tp=4)
    assert dict(model.mesh.shape) == {WORKER_AXIS: 2, MODEL_AXIS: 4}
    model.compile_iter_fns(BSP_Exchanger(model.config))
    # column-parallel fc1 weight: boxed [2, d, 4d] split over model on dim 2
    w = model.step_state["params"]["block0"]["fc1"]["w"]
    spec = w.sharding.spec
    assert spec == (WORKER_AXIS, None, MODEL_AXIS), spec
    # one device holds a [1, d, 4d/4] local block
    local = w.addressable_shards[0].data.shape
    assert local == (1, 32, 32), local
    # replicated-over-model leaf: ln_f scale
    s = model.step_state["params"]["ln_f"]["scale"]
    assert s.sharding.spec == (WORKER_AXIS,), s.sharding.spec
    # optimizer state (adam m) mirrors the param layout
    m = model.step_state["opt_state"]["m"]["block0"]["fc1"]["w"]
    assert m.sharding.spec == (WORKER_AXIS, None, MODEL_AXIS)


def test_tp_init_identical_to_dense(mesh8):
    dense, _ = _make(dp=2, tp=1)
    tp, _ = _make(dp=2, tp=4)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), dense.params, tp.params)


def test_tp_bsp_training_matches_dense(mesh8):
    """tp=4 × dp=2 must trace the same loss curve as dense dp=2 (same seed,
    same data): the model is mathematically identical — only the layout and
    the psum summation order differ."""
    dense, _ = _make(dp=2, tp=1)
    tp, _ = _make(dp=2, tp=4)
    c_dense = _train_steps(dense, BSP_Exchanger(dense.config), 6)
    c_tp = _train_steps(tp, BSP_Exchanger(tp.config), 6)
    np.testing.assert_allclose(c_tp, c_dense, rtol=2e-4, atol=2e-5)
    # params agree leaf-by-leaf after 6 updates
    from theanompi_tpu.parallel import steps
    pd = jax.device_get(steps.unbox(steps.tree_to_host(
        dense.step_state["params"])))
    pt = jax.device_get(steps.unbox(steps.tree_to_host(
        tp.step_state["params"])))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-5), pd, pt)


def test_tp_val_matches_dense(mesh8):
    dense, _ = _make(dp=2, tp=1)
    tp, _ = _make(dp=2, tp=4)
    dense.compile_iter_fns(BSP_Exchanger(dense.config))
    tp.compile_iter_fns(BSP_Exchanger(tp.config))
    for m in (dense, tp):
        m.data.shuffle_data(0)
        m.begin_val()
    rec = []
    for m in (dense, tp):
        batch = m.data.next_val_batch(0)
        from theanompi_tpu.parallel import steps
        dev = steps.put_batch(m.mesh, batch)
        cost, err, err5 = m.val_fn(m._val_params_boxed, m._val_bn_boxed, dev)
        rec.append((float(np.mean(np.asarray(cost))),
                    float(np.mean(np.asarray(err))),
                    float(np.mean(np.asarray(err5)))))
    (cd, ed, e5d), (ct, et, e5t) = rec
    assert abs(cd - ct) < 1e-4
    assert ed == pytest.approx(et, abs=1e-6)      # discrete: must agree
    assert e5d == pytest.approx(e5t, abs=1e-6)


@pytest.mark.slow
def test_tp_easgd_and_gosgd_smoke(mesh8):
    """Async rules compose with tp: the extra state (EASGD center / GoSGD α)
    inherits the params' sharded layout and the exchange collective runs."""
    for rule, kw in (("easgd", {"sync_freq": 2}),
                     ("gosgd", {"exch_prob": 1.0})):
        model, cfg = _make(dp=2, tp=4, **kw)
        exch = get_exchanger(rule, model.config)
        costs = _train_steps(model, exch, 4)
        exch.exchange(None, exch.exchange_freq)
        assert np.isfinite(costs).all()
        # canonical params + val path on the tp layout
        model.begin_val()
        model.val_iter(0, None)
        model.end_val()


def test_tp_checkpoint_roundtrip(tmp_path, mesh8):
    """Mid-training save/load on the tp layout restores bit-identically."""
    from theanompi_tpu.parallel import steps
    model, cfg = _make(dp=2, tp=4)
    exch = BSP_Exchanger(model.config)
    _train_steps(model, exch, 3)
    model.save(str(tmp_path), epoch=0, count=3)
    before = jax.device_get(steps.tree_to_host(model.step_state["params"]))

    model2, _ = _make(dp=2, tp=4)
    exch2 = BSP_Exchanger(model2.config)
    model2.compile_iter_fns(exch2)
    assert model2.load(str(tmp_path)) == 0
    after = jax.device_get(steps.tree_to_host(model2.step_state["params"]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), before, after)
    # and training continues from the restored state
    model2.data.shuffle_data(0)
    model2.train_iter(3, None)
    assert np.isfinite(float(model2.current_info["cost"]))


@pytest.mark.slow
def test_tp_with_grad_accumulation_and_multi_step_dispatch(mesh8):
    """n_subb (microbatch scan) and steps_per_call (multi-step dispatch)
    compose with tp: the tp=4 run must trace dense dp=2 exactly as in the
    plain case."""
    dense, _ = _make(dp=2, tp=1, n_subb=2)
    tp, _ = _make(dp=2, tp=4, n_subb=2)
    c_dense = _train_steps(dense, BSP_Exchanger(dense.config), 4)
    c_tp = _train_steps(tp, BSP_Exchanger(tp.config), 4)
    np.testing.assert_allclose(c_tp, c_dense, rtol=2e-4, atol=2e-5)

    spc, _ = _make(dp=2, tp=4, steps_per_call=2)
    base, _ = _make(dp=2, tp=4)
    spc.compile_iter_fns(BSP_Exchanger(spc.config))
    base.compile_iter_fns(BSP_Exchanger(base.config))
    for m in (spc, base):
        m.data.shuffle_data(0)
    base.train_iter(0, None)
    base.train_iter(1, None)
    spc.train_iter(1, None)          # one dispatch covering steps 0..1
    from theanompi_tpu.parallel import steps as steps_lib
    pb = steps_lib.unbox(jax.device_get(steps_lib.tree_to_host(
        base.step_state["params"])))
    ps = steps_lib.unbox(jax.device_get(steps_lib.tree_to_host(
        spc.step_state["params"])))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5), pb, ps)


def test_tp_compressed_strategies_train(mesh8):
    """onebit/topk error-feedback compression composes with tp: each tp rank
    compresses its LOCAL grad shard (EF state [tp·local_flat] sharded over
    'model').  Loss must stay finite and trend down; EF state must be
    per-model-shard (non-identical across tp ranks after training)."""
    for strat in ("onebit", "topk"):
        model, cfg = _make(dp=2, tp=4, exch_strategy=strat)
        costs = _train_steps(model, BSP_Exchanger(model.config), 8)
        assert np.isfinite(costs).all(), (strat, costs)
        assert np.mean(costs[-3:]) < np.mean(costs[:3]), (strat, costs)
        ef = model.step_state["extra"]["strat"]
        from theanompi_tpu.parallel.mesh import MODEL_AXIS
        assert ef.sharding.spec == ("workers", MODEL_AXIS)
        # per-shard residuals: the four tp shards' EF blocks differ
        blocks = np.asarray(jax.device_get(ef))[0].reshape(4, -1)
        assert not np.allclose(blocks[0], blocks[1])


def test_tp_loss_head_matches_dense_oracle(mesh8):
    """The vocab-parallel CE / error heads alone, against the dense heads, on
    random logits sharded over a 1-D model mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from theanompi_tpu.models import layers as L
    from theanompi_tpu.parallel import tp as tplib

    devs = np.asarray(jax.devices()[:4])
    mesh = Mesh(devs, (MODEL_AXIS,))
    r = np.random.RandomState(0)
    logits = jnp.asarray(r.randn(16, 32).astype(np.float32) * 3)
    labels = jnp.asarray(r.randint(0, 32, 16).astype(np.int32))

    def f(lg, lb):
        return (tplib.tp_softmax_cross_entropy(lg, lb),
                tplib.tp_errors(lg, lb),
                tplib.tp_errors_top_x(lg, lb, 5))

    sm = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(None, MODEL_AXIS), P()),
        out_specs=(P(), P(), P())))
    cost, err, err5 = sm(
        jax.device_put(logits, NamedSharding(mesh, P(None, MODEL_AXIS))),
        jax.device_put(labels, NamedSharding(mesh, P())))
    assert float(cost) == pytest.approx(
        float(L.softmax_cross_entropy(logits, labels)), rel=1e-5)
    assert float(err) == pytest.approx(float(L.errors(logits, labels)))
    assert float(err5) == pytest.approx(
        float(L.errors_top_x(logits, labels, 5)))
    # gradient of the sharded CE matches the dense CE gradient
    g_tp = jax.jit(shard_map(
        jax.grad(lambda lg, lb: tplib.tp_softmax_cross_entropy(lg, lb)),
        mesh=mesh, in_specs=(P(None, MODEL_AXIS), P()),
        out_specs=P(None, MODEL_AXIS)))(
            jax.device_put(logits, NamedSharding(mesh, P(None, MODEL_AXIS))),
            jax.device_put(labels, NamedSharding(mesh, P())))
    g_dense = jax.grad(L.softmax_cross_entropy)(logits, labels)
    np.testing.assert_allclose(np.asarray(g_tp), np.asarray(g_dense),
                               rtol=1e-5, atol=1e-7)
