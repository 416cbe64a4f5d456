"""Global-norm gradient clipping (config grad_clip) — exchanger-level, so
every rule gets it; pinned against a hand-computed clipped step."""

import numpy as np

import jax
import jax.numpy as jnp

from tests.conftest import TinyModel
from theanompi_tpu.parallel import steps
from theanompi_tpu.parallel.exchanger import BSP_Exchanger, get_exchanger


def _one_step(zero_clip, clip, mesh):
    cfg = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
           "optimizer": "sgd", "learning_rate": 1.0, "weight_decay": 0.0}
    if not zero_clip:
        cfg["grad_clip"] = clip
    m = TinyModel(cfg)
    m.compile_iter_fns(BSP_Exchanger(m.config))
    m.data.shuffle_data(0)
    p0 = steps.unbox(jax.device_get(m.step_state["params"]))
    m.train_iter(0, None)
    p1 = steps.unbox(jax.device_get(m.step_state["params"]))
    # with sgd lr=1 wd=0: update = -grad (possibly clipped)
    g = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b), p0, p1)
    return g


def test_grad_clip_matches_manual_scaling(mesh4):
    g_raw = _one_step(True, None, mesh4)
    norm = float(np.sqrt(sum(np.sum(np.square(l))
                             for l in jax.tree.leaves(g_raw))))
    clip = norm / 2.0                      # force clipping at half the norm
    g_clip = _one_step(False, clip, mesh4)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(b), np.asarray(a) * 0.5, rtol=1e-5, atol=1e-7),
        g_raw, g_clip)
    # a generous threshold leaves gradients untouched
    g_loose = _one_step(False, norm * 10, mesh4)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-8),
        g_raw, g_loose)


def test_grad_clip_on_async_rule(mesh4):
    cfg = {"mesh": mesh4, "size": 4, "rank": 0, "verbose": False,
           "grad_clip": 0.5, "sync_freq": 2}
    m = TinyModel(cfg)
    exch = get_exchanger("easgd", cfg)
    m.compile_iter_fns(exch)
    m.data.shuffle_data(0)
    for i in range(4):
        m.train_iter(i, None)
        exch.exchange(None, i)
    assert np.isfinite(float(m.current_info["cost"]))


def test_grad_clip_under_tensor_parallelism(mesh8):
    """The clip norm must be the GLOBAL norm under tp (sharded leaves
    psum'd, replicated leaves counted once): tp=4 with an aggressive clip
    must trace the dense run's loss curve."""
    import jax.numpy as jnp
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.parallel.mesh import worker_mesh

    def run(tp):
        mesh = worker_mesh(2, tp=tp)
        cfg = {"mesh": mesh, "size": 2, "rank": 0, "tp": tp,
               "verbose": False, "grad_clip": 0.05,   # bites every step
               "batch_size": 8, "seq_len": 16, "vocab": 32, "d_model": 32,
               "n_head": 4, "n_layer": 2, "synthetic_train": 64,
               "compute_dtype": jnp.float32}
        m = TransformerLM(cfg)
        m.compile_iter_fns(BSP_Exchanger(cfg))
        m.data.shuffle_data(0)
        costs = []
        for i in range(5):
            m.train_iter(i, None)
            costs.append(float(m.current_info["cost"]))
        return costs

    np.testing.assert_allclose(run(4), run(1), rtol=2e-4, atol=2e-5)
