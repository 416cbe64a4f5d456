"""The defining BSP invariant (SURVEY.md §4 item b):

N-worker BSP training must equal 1-worker training on the concatenated
batch — gradients averaged across workers == gradient of the global batch.
The reference could only argue this; the simulated mesh proves it.
"""

import jax
import numpy as np
import pytest

from tests.conftest import TinyModel
from theanompi_tpu.parallel import steps
from theanompi_tpu.parallel.exchanger import BSP_Exchanger
from theanompi_tpu.parallel.mesh import worker_mesh


def _train(n_workers, per_worker_bs, n_iters=4, **cfg):
    mesh = worker_mesh(n_workers)
    config = {"mesh": mesh, "size": n_workers, "rank": 0, "verbose": False,
              "batch_size": per_worker_bs, **cfg}
    model = TinyModel(config)
    exch = BSP_Exchanger(config)
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    for i in range(n_iters):
        model.train_iter(i + 1, None)
        exch.exchange(None, i + 1)   # no-op in grads mode; averaging in params mode
    return jax.device_get(steps.unbox(model.step_state["params"]))


@pytest.mark.parametrize("strategy", ["allreduce", "ring"])
def test_8_workers_equal_1_worker(strategy):
    # global batch 64 either way; identical data order (common seed)
    p8 = _train(8, 8, exch_strategy=strategy)
    p1 = _train(1, 64, exch_strategy=strategy)
    flat8 = jax.tree_util.tree_leaves(p8)
    flat1 = jax.tree_util.tree_leaves(p1)
    for a, b in zip(flat8, flat1):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_bsp_replicas_stay_identical():
    mesh = worker_mesh(8)
    config = {"mesh": mesh, "size": 8, "rank": 0, "verbose": False,
              "batch_size": 8}
    model = TinyModel(config)
    model.compile_iter_fns(BSP_Exchanger(config))
    model.data.shuffle_data(0)
    for i in range(3):
        model.train_iter(i + 1, None)
    boxed = jax.device_get(model.step_state["params"])
    for leaf in jax.tree_util.tree_leaves(boxed):
        for w in range(1, 8):
            np.testing.assert_array_equal(leaf[w], leaf[0])


def test_initial_params_leave_the_device_once_placed():
    """``compile_iter_fns`` places the state from host copies, so the
    initial parameters move to the host there: a device copy of them kept
    through training is dead weight beside the step program (553 MB on
    chip 0 for VGG-16 — PERF.md §6, PR 26).  Same values, same tree, and
    a recompile starts from them again."""
    leaves = jax.tree_util.tree_leaves
    mesh = worker_mesh(2)
    config = {"mesh": mesh, "size": 2, "rank": 0, "verbose": False,
              "batch_size": 8}
    model = TinyModel(config)
    assert all(isinstance(p, jax.Array) for p in leaves(model.params))
    drawn = jax.device_get(model.params)
    for _ in range(2):
        model.compile_iter_fns(BSP_Exchanger(config))
        assert jax.tree.structure(model.params) == jax.tree.structure(drawn)
        assert all(isinstance(p, np.ndarray) for p in leaves(model.params))
        placed = jax.device_get(model.step_state["params"])
        for p0, p, boxed in zip(leaves(drawn), leaves(model.params),
                                leaves(placed)):
            np.testing.assert_array_equal(p, p0)
            for w in range(2):
                np.testing.assert_array_equal(boxed[w], p0)
        model.data.shuffle_data(0)
        model.train_iter(1, None)


def test_bsp_params_mode_exact_oracle():
    """Pin params-mode semantics exactly: each worker takes a LOCAL momentum
    step on its own shard's gradient, then parameters (not velocities) are
    averaged across workers.  The oracle recomputes both steps independently
    — per-worker grads via plain ``jax.grad`` (no mesh, no exchanger), the
    momentum algebra and the average in NumPy."""
    import jax.numpy as jnp
    from tests.conftest import SyntheticData
    from theanompi_tpu.models import layers as L

    n, bs = 2, 8
    mesh = worker_mesh(n)
    config = {"mesh": mesh, "size": n, "rank": 0, "verbose": False,
              "batch_size": bs, "exch_mode": "params"}
    model = TinyModel(config)
    exch = BSP_Exchanger(config)
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)

    params0 = jax.device_get(model.params)
    oracle = [jax.tree.map(np.array, params0) for _ in range(n)]
    vel = [jax.tree.map(np.zeros_like, params0) for _ in range(n)]
    data = SyntheticData({"size": n}, batch_size=bs)
    data.shuffle_data(0)
    lr, mu = model.current_lr, model.momentum
    assert model.weight_decay == 0.0  # keeps the oracle algebra minimal

    def loss_fn(p, x, y):
        logits, _ = model.seq.apply(p, x, train=True, state={})
        return L.softmax_cross_entropy(logits, y)

    for step in range(1, 3):
        batch = data.next_train_batch(step)
        model.train_iter(step, None)
        exch.exchange(None, step)
        for w in range(n):
            xw = jnp.asarray(batch["x"][w * bs:(w + 1) * bs])
            yw = jnp.asarray(batch["y"][w * bs:(w + 1) * bs])
            g = jax.device_get(jax.grad(loss_fn)(
                jax.tree.map(jnp.asarray, oracle[w]), xw, yw))
            vel[w] = jax.tree.map(lambda v, gg: mu * v - lr * gg, vel[w], g)
            oracle[w] = jax.tree.map(lambda p, v: p + v, oracle[w], vel[w])
        avg = jax.tree.map(lambda *xs: np.mean(np.stack(xs), axis=0), *oracle)
        oracle = [jax.tree.map(np.array, avg) for _ in range(n)]

    got = jax.device_get(steps.unbox(model.step_state["params"]))
    for a, b in zip(jax.tree_util.tree_leaves(avg),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7)


def test_bsp_params_mode_replicas_identical_after_exchange():
    """After the params-mode averaging collective, all replicas must agree —
    the invariant the reference's per-iteration allreduce maintained."""
    mesh = worker_mesh(4)
    config = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
              "batch_size": 8, "exch_mode": "params"}
    model = TinyModel(config)
    exch = BSP_Exchanger(config)
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    for i in range(2):
        model.train_iter(i + 1, None)
        exch.exchange(None, i + 1)
    boxed = jax.device_get(model.step_state["params"])
    for leaf in jax.tree_util.tree_leaves(boxed):
        for w in range(1, 4):
            np.testing.assert_array_equal(leaf[w], leaf[0])


def test_steps_per_call_matches_single_step_dispatch():
    """steps_per_call=k (k full steps scanned inside one dispatch, the
    host-overhead amortizer) must produce the same params as k single-step
    dispatches — same data order, same per-step RNG folding."""
    p1 = _train(4, 8, n_iters=4)

    mesh = worker_mesh(4)
    config = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
              "batch_size": 8, "steps_per_call": 2}
    model = TinyModel(config)
    model.compile_iter_fns(BSP_Exchanger(config))
    model.data.shuffle_data(0)
    for count in (2, 4):              # each call covers steps {c-1, c}
        model.train_iter(count, None)
    p2 = jax.device_get(steps.unbox(model.step_state["params"]))
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_steps_per_call_with_para_load_across_epochs():
    """Drop-last striding (n_batch_train // spc dispatches per epoch) with
    the prefetch loader: the per-epoch shuffle must cleanly restart the
    producer past the leftover batch — two full epochs stream with no
    deadlock and training state keeps advancing."""
    mesh = worker_mesh(4)
    config = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
              "batch_size": 8, "n_train": 4 * 8 * 5,   # 5 batches/epoch
              "para_load": True, "steps_per_call": 2}  # 2 dispatches + 1 left
    model = TinyModel(config)
    model.compile_iter_fns(BSP_Exchanger(config))
    count = 0
    for epoch in range(2):
        model.data.shuffle_data(epoch)
        for _ in range(model.data.n_batch_train // 2):
            count += 2
            model.train_iter(count, None)
    assert count == 8
    assert np.isfinite(float(np.asarray(model.current_info["cost"])))


def test_steps_per_call_accepts_every_rule():
    """Multi-step dispatch is no longer BSP-grads-only: rules with a
    post-step collective get their cadence fused INTO the scanned step
    (ISSUE 1 tentpole) — compile_iter_fns accepts them and flags the
    exchanger so the Python hook knows to stand down."""
    from theanompi_tpu.parallel.exchanger import (ASGD_Exchanger,
                                                  BSP_Exchanger,
                                                  EASGD_Exchanger,
                                                  GOSGD_Exchanger)
    mesh = worker_mesh(4)
    for cls, cfg in ((EASGD_Exchanger, {}), (ASGD_Exchanger, {}),
                     (GOSGD_Exchanger, {}),
                     (BSP_Exchanger, {"exch_mode": "params"})):
        config = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
                  "batch_size": 8, "steps_per_call": 2, **cfg}
        model = TinyModel(config)
        exch = cls(config)
        model.compile_iter_fns(exch)          # must not raise
        assert exch.fused, cls.__name__
    # BSP grads mode has no post-step hook — nothing to fuse, flag stays off
    config = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
              "batch_size": 8, "steps_per_call": 2}
    model = TinyModel(config)
    exch = BSP_Exchanger(config)
    model.compile_iter_fns(exch)
    assert not exch.fused


def test_training_reduces_loss():
    mesh = worker_mesh(8)
    config = {"mesh": mesh, "size": 8, "rank": 0, "verbose": False,
              "batch_size": 8, "sync_each_iter": True}
    model = TinyModel(config)
    model.compile_iter_fns(BSP_Exchanger(config))
    model.data.shuffle_data(0)
    costs = []
    for i in range(8):
        model.train_iter(i + 1, None)
        costs.append(float(model.current_info["cost"]))
    assert costs[-1] < costs[0], costs


def test_n_subb_grad_accumulation_equivalent():
    """n_subb microbatching (the reference's sub-batch machinery, §3.4) must
    not change the update for a mean-loss model."""
    p1 = _train(4, 8, n_subb=1)
    p2 = _train(4, 8, n_subb=2)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
