"""GAN family tests (reference parity: ``wgan.py`` / ``lsgan.py``,
SURVEY.md §2.7): shapes, combined G/D step correctness, the n_critic
gradient gate, WGAN weight clipping, and multi-worker BSP compilation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.parallel import steps
from theanompi_tpu.parallel.exchanger import BSP_Exchanger, GOSGD_Exchanger
from theanompi_tpu.parallel.mesh import worker_mesh


def _build(cls_name, n=1, **cfg):
    from theanompi_tpu.models import gan
    mesh = worker_mesh(n)
    config = {"mesh": mesh, "size": n, "rank": 0, "verbose": False,
              "batch_size": 4, "compute_dtype": jnp.float32,
              "synthetic_train": 8 * n, "synthetic_val": 8 * n,
              "base_width": 8, "z_dim": 16, **cfg}
    return getattr(gan, cls_name)(config)


def test_reference_alias_paths_import():
    from theanompi_tpu.models.wgan import WGAN
    from theanompi_tpu.models.lsgan import LSGAN
    from theanompi_tpu.models import gan
    assert WGAN is gan.WGAN and LSGAN is gan.LSGAN


def test_generator_output_shape_and_range():
    m = _build("WGAN")
    z = jax.random.normal(jax.random.key(0), (3, m.z_dim))
    imgs, _ = m.generate(m.params, z)
    assert imgs.shape == (3, 32, 32, 3)
    assert bool((jnp.abs(imgs.astype(jnp.float32)) <= 1.0).all())  # tanh


@pytest.mark.parametrize("cls_name", ["WGAN", "LSGAN"])
def test_gan_train_step_finite(cls_name):
    m = _build(cls_name)
    m.compile_iter_fns(BSP_Exchanger(m.config))
    m.data.shuffle_data(0)
    for i in range(2):
        m.train_iter(i + 1, None)
    assert np.isfinite(float(np.asarray(m.current_info["cost"])))
    assert np.isfinite(float(np.asarray(m.current_info["error"])))


def test_wgan_n_critic_gate_and_clip():
    """G params move ONLY on count % n_critic == 0 steps; D weights stay
    inside the clip box every step."""
    m = _build("WGAN", n_critic=3, clip=0.005)
    m.compile_iter_fns(BSP_Exchanger(m.config))
    m.data.shuffle_data(0)

    def g_leaves():
        return [np.asarray(x) for x in jax.tree_util.tree_leaves(
            jax.device_get(steps.unbox(m.step_state["params"]))["G"])]

    g0 = g_leaves()
    m.train_iter(1, None)          # 1 % 3 != 0 → G frozen
    g1 = g_leaves()
    for a, b in zip(g0, g1):
        np.testing.assert_array_equal(a, b)
    m.train_iter(2, None)          # still frozen
    m.train_iter(3, None)          # 3 % 3 == 0 → G updates
    g3 = g_leaves()
    assert any((a != b).any() for a, b in zip(g1, g3))

    d = jax.device_get(steps.unbox(m.step_state["params"]))["D"]
    for leaf in jax.tree_util.tree_leaves(d):
        assert np.abs(np.asarray(leaf)).max() <= 0.005 + 1e-7


def test_n_critic_gate_holds_under_stateful_adam():
    """Regression: zeroed grads are NOT enough — adam's momentum would still
    move G on gated steps.  The update gate must keep G's params AND
    optimizer state bit-frozen."""
    m = _build("LSGAN", n_critic=4)
    assert m.optimizer == "adam"
    m.compile_iter_fns(BSP_Exchanger(m.config))
    m.data.shuffle_data(0)

    def g_side(tree):
        flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
        return [(str(p), np.asarray(v)) for p, v in flat if "'G'" in str(p)]

    m.train_iter(4, None)          # 4 % 4 == 0 → G updates, adam m/v warm
    p1 = g_side(steps.unbox(m.step_state["params"]))
    o1 = g_side(steps.unbox(m.step_state["opt_state"]))
    m.train_iter(5, None)          # gated → G params and G adam state frozen
    p2 = g_side(steps.unbox(m.step_state["params"]))
    o2 = g_side(steps.unbox(m.step_state["opt_state"]))
    for (_, a), (_, b) in zip(p1 + o1, p2 + o2):
        np.testing.assert_array_equal(a, b)
    m.train_iter(6, None)
    m.train_iter(7, None)
    m.train_iter(8, None)          # 8 % 4 == 0 → G moves again
    p3 = g_side(steps.unbox(m.step_state["params"]))
    assert any((a != b).any() for (_, a), (_, b) in zip(p2, p3))

    # Adam's bias-correction clock is per-leaf so the gate freezes it too:
    # after 5 steps (counts 4..8) G updated twice (4, 8) while D updated on
    # every step — "as if the G update function was never called" includes t.
    opt = jax.device_get(steps.unbox(m.step_state["opt_state"]))
    g_ts = {int(np.asarray(t)) for t in jax.tree.leaves(opt["t"]["G"])}
    d_ts = {int(np.asarray(t)) for t in jax.tree.leaves(opt["t"]["D"])}
    assert g_ts == {2}, g_ts
    assert d_ts == {5}, d_ts


def test_lsgan_loss_math():
    from theanompi_tpu.models.gan import LSGAN
    sr = jnp.asarray([1.0, 0.0])
    sf = jnp.asarray([0.5, 0.5])
    d = LSGAN.d_loss(None, sr, sf)
    g = LSGAN.g_loss(None, sf)
    np.testing.assert_allclose(float(d), 0.5 * (0.5 + 0.25), rtol=1e-6)
    np.testing.assert_allclose(float(g), 0.5 * 0.25, rtol=1e-6)


def test_gan_multiworker_bsp_and_gossip():
    """The combined G/D pytree rides the exchangers unchanged: 4-worker BSP
    keeps replicas identical; GoSGD conserves Σα."""
    m = _build("WGAN", n=4)
    m.compile_iter_fns(BSP_Exchanger(m.config))
    m.data.shuffle_data(0)
    m.train_iter(1, None)
    boxed = jax.device_get(m.step_state["params"])
    for leaf in jax.tree_util.tree_leaves(boxed):
        for r in range(1, 4):
            np.testing.assert_allclose(leaf[0], leaf[r], rtol=1e-5, atol=1e-6)

    m2 = _build("LSGAN", n=4, exch_prob=1.0)
    ex = GOSGD_Exchanger(m2.config)
    m2.compile_iter_fns(ex)
    m2.data.shuffle_data(0)
    m2.train_iter(1, None)
    ex.exchange(None, 1)
    alpha = np.asarray(jax.device_get(m2.step_state["extra"]["alpha"]))
    np.testing.assert_allclose(alpha.sum(), 4.0, rtol=1e-5)


def test_gan_rejects_zero_opt_but_composes_with_ema():
    """ZeRO flattens the optimizer state (no param paths), so the GANs'
    path-keyed n_critic gating cannot compose with it — rejected at build.
    EMA nests the state but keeps paths, so the gating (and the shadow)
    work through it."""
    with pytest.raises(AssertionError, match="param paths"):
        _build("WGAN", zero_opt=True)
    m = _build("WGAN", n=2, n_critic=2, ema_decay=0.9)
    m.compile_iter_fns(BSP_Exchanger(m.config))
    m.data.shuffle_data(0)
    p0 = steps.unbox(jax.device_get(m.step_state["params"]))
    m.train_iter(1, None)      # count=1: G is GATED on this step
    st = steps.unbox(jax.device_get(m.step_state["opt_state"]))
    assert "ema" in st
    # the gate reverts G's shadow to its INIT value — which must be G's
    # params (the init-time seed), NOT zeros: a zeroed shadow would make
    # validation/generate read a near-dead generator for ~1/(1-decay) steps
    def maxabs(t):
        return max(float(np.abs(np.asarray(l)).max())
                   for l in jax.tree.leaves(t))
    jax.tree.map(lambda e, p: np.testing.assert_allclose(
        np.asarray(e), np.asarray(p), rtol=1e-6, atol=1e-7),
        st["ema"]["G"], p0["G"])
    assert maxabs(st["ema"]["G"]) > 0.0
    m.train_iter(2, None)      # count=2: G updates; D's shadow keeps moving
    assert np.isfinite(float(np.asarray(m.current_info["cost"])))
    st2 = steps.unbox(jax.device_get(m.step_state["opt_state"]))
    moved = jax.tree.map(lambda e, p: float(np.abs(np.asarray(e)
                                                   - np.asarray(p)).max()),
                         st2["ema"]["D"], p0["D"])
    assert max(jax.tree.leaves(moved)) > 0.0
    # WGAN projects the shadow's critic into the clip box too — otherwise
    # validation would score a Lipschitz-violating critic for ~1/(1-decay)
    # steps (the EMA blend happens before the clip hook)
    clip = float(m.clip)
    for leaf in jax.tree.leaves(st2["ema"]["D"]):
        assert float(np.abs(np.asarray(leaf)).max()) <= clip + 1e-7


def test_wgan_rejects_ema_plus_zero_opt():
    """Review r3: zero_opt nests the EMA shadow as flat chunks the clip
    projection can't reach — the combination must fail loudly, not score an
    unclipped critic shadow silently."""
    with pytest.raises(AssertionError, match="EMA shadow"):
        _build("WGAN", ema_decay=0.99, zero_opt=True)

# excluded from the 870s-budgeted tier-1 gate; see pytest.ini (slow marker)
import pytest as _pytest
pytestmark = _pytest.mark.slow
