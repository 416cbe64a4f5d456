"""Model-zoo contract tests: every zoo model builds, runs a forward pass,
and (for the cheap ones) a full compiled train step (SURVEY.md §2.7 parity:
AlexNet / GoogLeNet / VGG-16 (+11) / ResNet-50 / CIFAR-10)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.parallel.exchanger import BSP_Exchanger
from theanompi_tpu.parallel.mesh import worker_mesh

ZOO = [
    ("theanompi_tpu.models.cifar10", "Cifar10_model", 10),
    ("theanompi_tpu.models.alex_net", "AlexNet", 16),
    pytest.param("theanompi_tpu.models.googlenet", "GoogLeNet", 16,
                 marks=pytest.mark.slow),
    ("theanompi_tpu.models.vggnet_16", "VGGNet_16", 16),
    pytest.param("theanompi_tpu.models.vggnet_16", "VGGNet_11_shallow", 16,
                 marks=pytest.mark.slow),
    pytest.param("theanompi_tpu.models.resnet50", "ResNet50", 16,
                 marks=pytest.mark.slow),
]


def _build(modelfile, modelclass, n_class, **cfg):
    import importlib
    cls = getattr(importlib.import_module(modelfile), modelclass)
    mesh = worker_mesh(1)
    config = {"mesh": mesh, "size": 1, "rank": 0, "verbose": False,
              "batch_size": 2, "n_class": n_class,
              "compute_dtype": jnp.float32, "synthetic_batches": 1,
              "synthetic_train": 64, "synthetic_val": 32, **cfg}
    return cls(config)


@pytest.mark.parametrize("modelfile,modelclass,n_class", ZOO)
def test_forward_shapes_and_finite(modelfile, modelclass, n_class):
    model = _build(modelfile, modelclass, n_class)
    batch = model.data.next_train_batch(0)
    x = jnp.asarray(batch["x"][:2])
    logits, _ = model.apply_model(model.params, x, train=False, rng=None,
                                  state=model.bn_state)
    assert logits.shape == (2, n_class)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("modelfile,modelclass,n_class", [
    ("theanompi_tpu.models.cifar10", "Cifar10_model", 10),
    pytest.param("theanompi_tpu.models.resnet50", "ResNet50", 8,
                 marks=pytest.mark.slow),
])
def test_full_train_step(modelfile, modelclass, n_class):
    """One compiled SPMD train step end-to-end (ResNet covers the BN-state
    threading path; CIFAR covers the plain path)."""
    model = _build(modelfile, modelclass, n_class)
    model.compile_iter_fns(BSP_Exchanger(model.config))
    model.data.shuffle_data(0)
    model.train_iter(1, None)
    cost = float(np.asarray(model.current_info["cost"]))
    assert np.isfinite(cost)
    if modelclass == "ResNet50":
        # BN running stats must have moved off their init
        bn = jax.device_get(model.step_state["bn_state"])
        means = [np.asarray(v) for k, v in
                 jax.tree_util.tree_flatten_with_path(bn)[0]
                 if "mean" in str(k[-1])]
        assert any((m != 0).any() for m in means)


@pytest.mark.slow
def test_train_decreases_loss_alexnet_tiny():
    """AlexNet trains on its synthetic data (labels are random, but the
    model can still fit them — loss must drop within a few steps)."""
    model = _build("theanompi_tpu.models.alex_net", "AlexNet", 8,
                   batch_size=4, learning_rate=0.02)
    model.compile_iter_fns(BSP_Exchanger(model.config))
    model.data.shuffle_data(0)
    costs = []
    for i in range(6):
        model.train_iter(i + 1, None)
        costs.append(float(np.asarray(model.current_info["cost"])))
    assert costs[-1] < costs[0], costs


@pytest.mark.slow
@pytest.mark.parametrize("n_workers", [1, 4])
def test_resnet_bn_composes_with_steps_per_call(n_workers):
    """Round-5 regression (found pre-hardware by the AOT compile of the
    staged resnet50-*-spc8 rows): sync_bn's pmean returns worker-INVARIANT
    BN stats, which mismatched the worker-varying scan carry under
    steps_per_call > 1 — BN models never met spc>1 anywhere else
    (AlexNet/GoogLeNet/VGG use LRN).  Must trace, run, and keep updating
    BN stats on both a single-worker mesh (the real-TPU-row shape) and a
    multi-worker mesh."""
    mesh = worker_mesh(n_workers)
    model = _build("theanompi_tpu.models.resnet50", "ResNet50", 8,
                   mesh=mesh, size=n_workers, batch_size=2,
                   steps_per_call=2, synthetic_batches=2)
    model.compile_iter_fns(BSP_Exchanger(model.config))
    model.data.shuffle_data(0)
    model.train_iter(1, None)                   # steps 0 and 1, one call
    assert np.isfinite(float(np.asarray(model.current_info["cost"])))
    bn = jax.device_get(model.step_state["bn_state"])
    means = [np.asarray(v) for k, v in
             jax.tree_util.tree_flatten_with_path(bn)[0]
             if "mean" in str(k[-1])]
    assert any((m != 0).any() for m in means)


@pytest.mark.slow
def test_resnet_bn_trains_under_async_rules():
    """Round-5 review regression: the async rules' sync_bn is the
    identity (replicas diverge on purpose), so their BN stats reach
    _revary_bn already worker-varying — the re-mark must be idempotent,
    not crash with pcast varying->varying.  (Rule tests elsewhere use the
    BN-free TinyModel, which is how this stayed latent.)"""
    from theanompi_tpu.parallel.exchanger import get_exchanger
    mesh = worker_mesh(4)
    model = _build("theanompi_tpu.models.resnet50", "ResNet50", 8,
                   mesh=mesh, size=4, batch_size=2)
    cfg = dict(model.config)
    model.compile_iter_fns(get_exchanger("gosgd", cfg))
    model.data.shuffle_data(0)
    model.train_iter(0, None)
    assert np.isfinite(float(np.asarray(model.current_info["cost"])))

# -- a max Pool directly after a ReLU layer runs before the ReLU --------------

@pytest.mark.parametrize("modelfile,modelclass,pairs", [
    ("theanompi_tpu.models.vggnet_16", "VGGNet_16", 5),
    ("theanompi_tpu.models.vggnet_16", "VGGNet_11_shallow", 5),
    ("theanompi_tpu.models.alex_net", "AlexNet", 1),       # conv5 -> pool5
    ("theanompi_tpu.models.cifar10", "Cifar10_model", 3),
    pytest.param("theanompi_tpu.models.googlenet", "GoogLeNet", 1,
                 marks=pytest.mark.slow),                  # conv1 -> pool1
    ("theanompi_tpu.models.resnet50", "ResNet50", 0),      # ConvBN: left
])
def test_pool_before_relu_count(modelfile, modelclass, pairs):
    from test_layers import pool_before_relu_count
    before = pool_before_relu_count()
    _build(modelfile, modelclass, 16)
    assert pool_before_relu_count() - before == pairs


def test_vgg16_no_max_pool_reads_a_relu_output():
    """The max-pool backward (select-and-scatter on the chip) takes its
    operand unfused; fed a ReLU's output it forces a second copy of the
    feature map beside the pre-activation (PERF.md §6, PR 26)."""
    from test_layers import pool_operand_makers
    model = _build("theanompi_tpu.models.vggnet_16", "VGGNet_16", 16)
    batch = {"x": jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32),
             "y": jax.ShapeDtypeStruct((2,), jnp.int32)}
    jaxpr = jax.make_jaxpr(
        lambda p, b: model.loss_and_metrics(p, {}, b, jax.random.key(0),
                                            True)[0])(model.params, batch)
    assert pool_operand_makers(jaxpr.jaxpr) == ["add"] * 5


@pytest.mark.slow
def test_vgg_train_step_cost_equals_the_plain_order():
    """Two train steps of VGG-11 at a fixed seed, as built and with the
    layer list's own order: the same costs."""
    costs = []
    for plain in (False, True):
        model = _build("theanompi_tpu.models.vggnet_16", "VGGNet_11_shallow",
                       8, seed=3, learning_rate=1e-4)
        assert len(model.seq._pool_first) == 5
        if plain:
            model.seq._pool_first = frozenset()
        model.compile_iter_fns(BSP_Exchanger(model.config))
        model.data.shuffle_data(0)
        got = []
        for i in range(2):
            model.train_iter(i + 1, None)
            got.append(np.asarray(model.current_info["cost"]))
        costs.append(np.stack(got))
    assert np.isfinite(costs[0]).all()
    np.testing.assert_allclose(costs[0], costs[1], rtol=1e-6)
