"""3-D composition: dp×pipe×model — GPipe stages of tensor-parallel blocks
in one SPMD program, pinned against the dense model.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.models.transformer_lm import TransformerLM
from theanompi_tpu.parallel.exchanger import BSP_Exchanger
from theanompi_tpu.parallel.mesh import (MODEL_AXIS, PIPE_AXIS, WORKER_AXIS,
                                         worker_mesh)

LM_CFG = dict(verbose=False, batch_size=8, seq_len=16, vocab=32,
              synthetic_train=64, synthetic_val=32,
              d_model=32, n_head=4, n_layer=4, compute_dtype=jnp.float32)


def _make(dp, tp, pp, **kw):
    mesh = worker_mesh(dp, tp=tp, pp=pp)
    cfg = {**LM_CFG, "mesh": mesh, "size": dp, "rank": 0, "tp": tp, "pp": pp,
           **kw}
    return TransformerLM(cfg)


def _train_steps(model, n_steps):
    exch = BSP_Exchanger(model.config)
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    costs = []
    for i in range(n_steps):
        model.train_iter(i, None)
        costs.append(float(model.current_info["cost"]))
    return costs


def test_3d_mesh_shape_and_shardings(mesh8):
    m = _make(dp=2, tp=2, pp=2)
    assert dict(m.mesh.shape) == {WORKER_AXIS: 2, PIPE_AXIS: 2,
                                  MODEL_AXIS: 2}
    m.compile_iter_fns(BSP_Exchanger(m.config))
    w = m.step_state["params"]["blocks"]["fc1"]["w"]
    # boxed [2 workers, 4 layers, d, 4d]: layers over pipe, 4d over model
    assert w.sharding.spec == (WORKER_AXIS, PIPE_AXIS, None, MODEL_AXIS), \
        w.sharding.spec
    assert w.addressable_shards[0].data.shape == (1, 2, 32, 64)
    # vocab-parallel embedding sharded over model, replicated over pipe
    e = m.step_state["params"]["embed"]["w"]
    assert e.sharding.spec == (WORKER_AXIS, MODEL_AXIS, None)


def test_3d_training_matches_dense(mesh8):
    dense = _make(dp=2, tp=1, pp=1)
    m3 = _make(dp=2, tp=2, pp=2)
    c_dense = _train_steps(dense, 5)
    c_3d = _train_steps(m3, 5)
    np.testing.assert_allclose(c_3d, c_dense, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_compressed_strategies_on_pipe_and_3d_meshes(mesh8):
    """EF compression and the explicit ring wire compose with pipeline (and
    pipe×model) sharding: per-stage EF shards, replicated leaves pmean'd
    back after the decode."""
    from theanompi_tpu.parallel.mesh import PIPE_AXIS

    def run(tp, pp, strat, n=5):
        mesh = worker_mesh(2, tp=tp, pp=pp)
        cfg = {**LM_CFG, "mesh": mesh, "size": 2, "rank": 0, "tp": tp,
               "pp": pp, "exch_strategy": strat}
        model = TransformerLM(cfg)
        return model, _train_steps(model, n)

    for tp, pp, strat in ((1, 4, "onebit"), (1, 4, "ring"),
                          (2, 2, "onebit"), (2, 2, "topk")):
        model, costs = run(tp, pp, strat)
        assert np.isfinite(costs).all(), (tp, pp, strat, costs)
        assert np.mean(costs[-2:]) < np.mean(costs[:2]), (tp, pp, strat)
        if strat in ("onebit", "topk"):
            ef = model.step_state["extra"]["strat"]
            want = (WORKER_AXIS, (PIPE_AXIS, MODEL_AXIS)) if tp > 1 \
                else (WORKER_AXIS, PIPE_AXIS)
            assert ef.sharding.spec == want, (strat, ef.sharding.spec)


def test_3d_val_and_checkpoint(tmp_path, mesh8):
    from theanompi_tpu.parallel import steps
    m3 = _make(dp=2, tp=2, pp=2)
    _train_steps(m3, 3)
    m3.begin_val()
    m3.val_iter(0, None)
    m3.end_val()
    m3.save(str(tmp_path), epoch=0, count=3)
    before = jax.device_get(steps.tree_to_host(m3.step_state["params"]))
    m3b = _make(dp=2, tp=2, pp=2)
    m3b.compile_iter_fns(BSP_Exchanger(m3b.config))
    assert m3b.load(str(tmp_path)) == 0
    after = jax.device_get(steps.tree_to_host(m3b.step_state["params"]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), before, after)
    m3b.data.shuffle_data(0)
    m3b.train_iter(3, None)
    assert np.isfinite(float(m3b.current_info["cost"]))


def test_worker_mesh_warns_on_idle_remainder(mesh8):
    """Review r3: flooring n_workers must not silently idle chips."""
    del mesh8
    with pytest.warns(UserWarning, match="left idle"):
        worker_mesh(None, tp=3, devices=jax.devices())   # 8 % 3 = 2 idle


@pytest.mark.slow
def test_4axis_tp_pp_sp_matches_dense(mesh8):
    """round-4: ALL model-parallel axes at once — pipeline stages of
    head-sharded ring-attention blocks over sequence-sharded microbatches
    (workers×pipe×model×seq = 1×2×2×2) — matches the dense model."""
    CFG = {**LM_CFG, "n_layer": 2}
    dense = TransformerLM({**CFG, "mesh": worker_mesh(1), "size": 1,
                           "rank": 0})
    m4 = TransformerLM({**CFG, "mesh": worker_mesh(1, tp=2, pp=2, sp=2),
                        "size": 1, "rank": 0, "tp": 2, "pp": 2, "sp": 2,
                        "pp_microbatches": 2})
    c_d = _train_steps(dense, 4)
    c_4 = _train_steps(m4, 4)
    np.testing.assert_allclose(c_4, c_d, rtol=5e-4, atol=5e-5)
    m4.begin_val()
    m4.val_iter(0)
    m4.end_val()
