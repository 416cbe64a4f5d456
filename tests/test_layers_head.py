"""``layers.weighted_cross_entropy``: a whole-vocabulary head whose loss and
gradients come from one pass over the logits, held to plain reverse mode
over the unblocked float32 formulation; and what the two models that call
it trace: three ``[block, V]`` products a block, not four."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models import layers as L
from theanompi_tpu.models.looped_lm import LoopedLM
from theanompi_tpu.models.routed_lm import RoutedLM
from theanompi_tpu.utils import telemetry

M, D, V = 64, 32, 96


def problem(weights: str):
    r = np.random.RandomState(11)
    w = jnp.asarray(r.standard_normal((D, V)), jnp.float32) * 0.5
    h = jnp.asarray(r.standard_normal((M, D)), jnp.float32)
    y = jnp.asarray(r.randint(0, V, (M,)), jnp.int32)
    c = jnp.full((M,), 1.0 / M) if weights == "constant" else \
        jnp.asarray(r.uniform(0.0, 2.0 / M, (M,)), jnp.float32)
    return w, h, y, c


def plain(w, h, y, c):
    """The unblocked float32 formulation, for plain reverse mode."""
    logits = jnp.dot(h, w, precision="highest")
    ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, y[:, None], axis=-1)[:, 0]
    miss = (jnp.argmax(logits, axis=-1) != y).astype(jnp.float32)
    return jnp.sum(c * ce), (ce, miss)


def rel(got, want):
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want))


def products(jaxpr, shape, times=1):
    """``dot_general``s with an operand or a result of ``shape`` that one
    evaluation of ``jaxpr`` makes: a scan's body counts once an
    iteration."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                getattr(v.aval, "shape", None) == shape
                for v in list(eqn.invars) + list(eqn.outvars)):
            n += times
        inner = times * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += products(sub, shape, inner)
    return n


# -- the function against plain reverse mode -----------------------------------------

@pytest.mark.parametrize("weights", ["constant", "random"])
@pytest.mark.parametrize("blocks", [1, 4])
@pytest.mark.parametrize("cd, tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_value_and_gradients_are_plain_reverse_modes(cd, tol, blocks,
                                                     weights):
    """The loss is scaled by 3.7 outside, so the rule's cotangent is not
    1; bfloat16 at a tenth of the training check's limit on a gradient's
    norm (``benchmarks/reference/check.py`` ``GRAD_NORM_TOL``), which the
    routed model's bfloat16 test holds the whole model to."""
    w, h, y, c = problem(weights)

    def ours(w, h, c):
        s, ce, miss = L.weighted_cross_entropy(
            w, h, y, c, block=M // blocks, compute_dtype=cd)
        return 3.7 * s, (ce, miss)

    with jax.default_matmul_precision("highest"):
        (got, (ce, miss)), grads = jax.jit(jax.value_and_grad(
            ours, argnums=(0, 1, 2), has_aux=True))(w, h, c)
        (want, (ce0, miss0)), grads0 = jax.value_and_grad(
            lambda w, h, c: (3.7 * plain(w, h, y, c)[0],
                             plain(w, h, y, c)[1]),
            argnums=(0, 1, 2), has_aux=True)(w, h, c)
    assert abs(float(got) - float(want)) < tol * abs(float(want))
    assert rel(ce, ce0) < tol
    np.testing.assert_array_equal(miss, miss0)
    assert 0 < float(miss0.mean()) <= 1
    for name, g, g0 in zip("whc", grads, grads0):
        assert g.dtype == g0.dtype == jnp.float32, name
        assert rel(g, g0) < tol, name
    if cd == "bfloat16":            # and it is not float32 in disguise
        assert rel(grads[0], grads0[0]) > 1e-5


def test_ce_and_miss_carry_no_gradient():
    w, h, y, c = problem("random")

    def through_the_reports(w, h, c):
        _, ce, miss = L.weighted_cross_entropy(
            w, h, y, c, block=16, compute_dtype="float32")
        return jnp.sum(ce) + jnp.sum(miss)

    for g in jax.grad(through_the_reports, argnums=(0, 1, 2))(w, h, c):
        assert float(jnp.max(jnp.abs(g))) == 0.0


@pytest.mark.parametrize("blocks", [1, 4])
def test_not_differentiated_it_makes_the_logits_and_nothing_else(blocks):
    """Validation and the forward check: one product a block, no carry of
    the weight's gradient."""
    w, h, y, c = problem("constant")
    blk = M // blocks
    jaxpr = jax.make_jaxpr(lambda w, h, c: L.weighted_cross_entropy(
        w, h, y, c, block=blk, compute_dtype="bfloat16"))(w, h, c).jaxpr
    assert products(jaxpr, (blk, V)) == blocks

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scans(sub)

    (scan,) = scans(jaxpr)
    assert scan.params["num_carry"] == 0
    # differentiated, the one scan carries the weight's gradient in float32
    grad = jax.make_jaxpr(jax.grad(lambda w: L.weighted_cross_entropy(
        w, h, y, c, block=blk, compute_dtype="bfloat16")[0]))(w).jaxpr
    (scan,) = scans(grad)
    carried = [v.aval for v in scan.outvars[:scan.params["num_carry"]]]
    assert (w.shape, jnp.float32) in [(a.shape, a.dtype) for a in carried]
    assert products(grad, (blk, V)) == 3 * blocks \
        == L.head_logit_products(M, blk)


def test_a_constant_weight_inside_a_shard_map_gets_the_workers_axis():
    """The routed model hands ``1 / N`` as a constant: inside a step's
    ``shard_map`` its gradient is per worker like every other, or the
    rule's output types are refused."""
    from jax.sharding import Mesh, PartitionSpec as P

    from theanompi_tpu.jax_compat import shard_map
    w, h, y, _ = problem("constant")
    mesh = Mesh(np.array(jax.devices()[:1]), ("workers",))

    def per_worker(w, h, y):
        return jax.grad(lambda w: L.weighted_cross_entropy(
            w[0], h[0], y[0], jnp.full((M,), 1.0 / M), block=16,
            compute_dtype="float32")[0])(w)

    got = jax.jit(shard_map(per_worker, mesh=mesh, in_specs=P("workers"),
                            out_specs=P("workers")))(w[None], h[None],
                                                     y[None])
    want = jax.grad(lambda w: plain(w, h, y, jnp.full((M,), 1.0 / M))[0])(w)
    assert rel(got[0], want) < 1e-5


# -- what the two models trace ---------------------------------------------------------

def looped():
    m = LoopedLM(dict(vocab=128, d_model=64, n_head=2, n_layer=2, d_ff=96,
                      seq_len=16, loop_steps=3, n_workers=1, seed=3,
                      batch_size=2, synthetic_train=8, synthetic_val=4,
                      compute_dtype="float32", verbose=False))
    m.head_block = 16
    return m, 3 * 2 * 16, 16, (2, 16)


def routed():
    m = RoutedLM(dict(n_workers=1, seed=3, batch_size=2, synthetic_train=8,
                      synthetic_val=4, compute_dtype="float32",
                      verbose=False, head_block=16))
    return m, 2 * m.seq_len, 16, (2, m.seq_len)


@pytest.mark.parametrize("build", [looped, routed])
def test_a_models_training_step_makes_three_products_a_block(build):
    """The traced gradient of ``loss_and_metrics`` holds ``3 M / block``
    products of the ``[block, V]`` shape (reverse mode over a
    rematerialised block, as the parent had it, holds four), and the
    counter the model writes at that trace says the same."""
    model, tokens, blk, shape = build()
    assert not hasattr(model, "_head_losses")
    x = jnp.zeros(shape, jnp.int32)
    before = telemetry.totals().get("model.head_logit_products", (0, 0))[0]
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.loss_and_metrics(
        p, {}, {"x": x, "y": x}, None, True)[0]))(model.params).jaxpr
    want = 3 * tokens // blk
    assert products(jaxpr, (blk, model.vocab)) == want
    assert telemetry.totals()["model.head_logit_products"][0] - before \
        == want == L.head_logit_products(tokens, blk)
    # traced again, counted once; evaluation counts nothing
    jax.make_jaxpr(lambda p: model.loss_and_metrics(
        p, {}, {"x": x, "y": x}, None, True)[0])(model.params)
    model.val_metrics(model.params, {}, {"x": x, "y": x})
    assert telemetry.totals()["model.head_logit_products"][0] - before \
        == want
