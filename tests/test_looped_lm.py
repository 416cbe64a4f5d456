"""The looped language model at a small size on the CPU: held to the plain
reference at every loop step, to the plain stack at one loop step, and to
an unrolled model of tied copies at several."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import ouro  # noqa: E402
from theanompi_tpu.models import layers as L  # noqa: E402
from theanompi_tpu.models.looped_lm import (LoopedLM,  # noqa: E402
                                            exit_distribution)
from theanompi_tpu.utils import telemetry  # noqa: E402

SIZES = dict(vocab=128, d_model=64, n_head=2, n_layer=2, d_ff=96,
             seq_len=16, loop_steps=3)
REF = dict(n_head=2, loops=3, theta=1e6, eps=1e-6)


def build(**over):
    cfg = dict(SIZES, n_workers=1, seed=3, batch_size=2, synthetic_train=8,
               synthetic_val=4, compute_dtype="float32", verbose=False)
    cfg.update(over)
    model = LoopedLM(cfg)
    model.head_block = 16       # 32 tokens a step: the heads in two blocks
    return model


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def params(model):
    """Seeded weights, the norms' scales and the gate moved off their
    initial 1 and 0 so that each takes part."""
    r = np.random.RandomState(5)
    p = jax.device_get(model.params)
    return jax.tree.map(
        lambda a: a + 0.1 * r.standard_normal(a.shape).astype(np.float32)
        if a.ndim == 1 else a, p)


@pytest.fixture(scope="module")
def tokens():
    seq = np.random.RandomState(0).randint(0, 128, (2, 17)).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


def objective(model, p, x, y):
    return model.loss_and_metrics(p, {}, {"x": x, "y": y}, None, True)[0]


def test_every_loop_steps_logits_are_the_references(model, params, tokens):
    x, _ = tokens
    with jax.default_matmul_precision("highest"):
        hs = model.hidden_states(params, x, train=False)
        got = jnp.stack([model._logits(params, h) for h in hs])
        want = ouro.loop_logits(params, x, **REF)
        last, _ = model.apply_model(params, x, train=False, rng=None,
                                    state={})
    assert got.shape == want.shape == (3, 2, 16, 128)
    scale = float(jnp.max(jnp.abs(want)))
    for t in range(3):
        assert float(jnp.max(jnp.abs(got[t] - want[t]))) < 1e-5 * scale
    # the steps differ: the loop does something
    assert float(jnp.max(jnp.abs(want[2] - want[0]))) > 1e-2 * scale
    np.testing.assert_allclose(last, ouro.forward(params, x, **REF),
                               atol=1e-5 * scale)


def test_the_objective_and_its_gradients_are_the_references(model, params,
                                                            tokens):
    x, y = tokens
    with jax.default_matmul_precision("highest"):
        cost, grads = jax.value_and_grad(
            lambda p: objective(model, p, x, y))(params)
        want, ref_grads = jax.value_and_grad(
            lambda p: ouro.train_loss(p, x, y, **REF))(params)
    assert float(cost) == pytest.approx(float(want), rel=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        r = ref_grads
        for k in path:
            r = r[k.key]
        assert float(jnp.linalg.norm(r)) > 0, path      # every leaf is read
        assert float(jnp.linalg.norm(g - r)) \
            < 2e-5 * float(jnp.linalg.norm(r)), jax.tree_util.keystr(path)
    # the entropy term is in it: without beta the cost is another
    flat = build(exit_beta=0.0)
    with jax.default_matmul_precision("highest"):
        assert float(objective(flat, params, x, y)) > float(cost) + 0.05


def test_bfloat16_stays_near_the_reference(params, tokens):
    x, y = tokens
    with jax.default_matmul_precision("highest"):
        want = float(ouro.train_loss(params, x, y, **REF))
    got = float(objective(build(compute_dtype="bfloat16"), params, x, y))
    assert got == pytest.approx(want, abs=5e-3) and got != want


def test_one_loop_step_is_the_plain_stack(params, tokens):
    x, _ = tokens
    once = build(loop_steps=1)
    hs = once.hidden_states(params, x, train=False)
    h = params["embed"]["w"][x]
    for blk in once.blocks:
        h = blk.apply(params[blk.name], h)
    h = once.norm_f.apply(params["norm_f"], h)
    assert hs.shape == (1, 2, 16, 64)
    np.testing.assert_allclose(hs[0], h, rtol=1e-5, atol=1e-5)


def test_loop_steps_are_an_unrolled_model_of_tied_copies(model, params,
                                                         tokens):
    """R loop steps over N layers is an R x N-layer model whose copies hold
    the same weights, and a shared leaf's gradient is the sum over its
    copies' gradients."""
    x, y = tokens
    names = [b.name for b in model.blocks]

    def unrolled(copies, rest):
        h, hs = rest["embed"]["w"][x], []
        for copy in copies:                     # R copies of the N layers
            for blk in model.blocks:
                h = blk.apply(copy[blk.name], h, train=True)
            h = model.norm_f.apply(rest["norm_f"], h)
            hs.append(h)
        hs = jnp.stack(hs).reshape(len(copies), -1, 64)
        return model.exit_losses(rest, hs, y.reshape(-1))[0]

    rest = {k: v for k, v in params.items() if k not in names}
    copies = [{n: params[n] for n in names}] * 3
    with jax.default_matmul_precision("highest"):
        cost, grads = jax.value_and_grad(
            lambda p: objective(model, p, x, y))(params)
        tied, by_copy = jax.value_and_grad(unrolled)(copies, rest)
    assert float(cost) == pytest.approx(float(tied), rel=1e-6)
    summed = jax.tree.map(lambda *g: sum(g), *by_copy)
    for n in names:
        for (path, g), s in zip(
                jax.tree_util.tree_leaves_with_path(grads[n]),
                jax.tree.leaves(summed[n])):
            assert float(jnp.linalg.norm(g - s)) \
                < 2e-5 * float(jnp.linalg.norm(s)), (n, path)
    # and no single copy's gradient is the whole
    one = by_copy[0][names[0]]["mlp"]["wd"]
    whole = grads[names[0]]["mlp"]["wd"]
    assert float(jnp.linalg.norm(one - whole)) \
        > 0.1 * float(jnp.linalg.norm(whole))


@pytest.mark.parametrize("scale", [0.0, 1.0, 30.0, 200.0])
def test_the_exit_distribution_sums_to_one_per_token(scale):
    z = scale * jax.random.normal(jax.random.key(1), (4, 50))
    p, logp = exit_distribution(z)
    assert p.shape == (4, 50) and bool(jnp.all(p >= 0))
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-5)
    assert bool(jnp.all(jnp.isfinite(p * logp)))        # saturated gates too
    lam = jax.nn.sigmoid(z)
    want = ouro.exit_distribution(lam)
    np.testing.assert_allclose(p, want, atol=1e-6)
    np.testing.assert_allclose(jnp.sum(want, axis=0), 1.0, atol=1e-5)
    # by hand: leave at 1 with l1, at 2 with (1-l1) l2, ..., the rest at 4
    np.testing.assert_allclose(p[1], (1 - lam[0]) * lam[1], atol=1e-6)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), atol=1e-6)


def test_the_first_cost_starts_at_ln_vocabulary(tokens):
    """As initialised: four cross-entropies within hundredths of ln V, the
    gates at a half, so the cost is ln V less beta times 1.213."""
    x, y = tokens
    m = build()
    hs = m.hidden_states(m.params, x, train=True)
    cost, ces, _, p = m.exit_losses(m.params, hs.reshape(3, -1, 64),
                                    y.reshape(-1))
    assert np.abs(np.asarray(ces) - np.log(128)).max() < 0.05
    np.testing.assert_allclose(jnp.mean(p, axis=1), [0.5, 0.25, 0.25],
                               atol=0.06)
    assert abs(float(cost) - np.log(128)) < 0.25


def test_the_step_counts_its_loop_once_however_often_it_is_traced(tokens):
    x, y = tokens
    m = build()
    before = {k: telemetry.totals().get(k, (0, 0))[0]
              for k in ("model.loop_steps", "model.layer_applications",
                        "model.head_tokens")}
    for _ in range(2):
        jax.jit(lambda p: objective(m, p, x, y)).lower(m.params)
    after = {k: telemetry.totals()[k][0] - n for k, n in before.items()}
    assert after == {"model.loop_steps": 3, "model.layer_applications": 6,
                     "model.head_tokens": 3 * 2 * 16}
    m.val_metrics(m.params, {}, {"x": x, "y": y})       # counts nothing
    assert telemetry.totals()["model.loop_steps"][0] \
        == before["model.loop_steps"] + 3


def test_the_scopes_the_benchmark_reads_are_in_the_lowered_step(tokens):
    x, y = tokens
    m = build()
    text = jax.jit(jax.grad(lambda p: objective(m, p, x, y))).lower(
        m.params).as_text(debug_info=True)
    for scope in ("ut_loop", "block0/", "attn/attn_core", "mlp",
                  "exit_head", "norm_f"):
        assert scope in text, scope


def test_ids_are_uniform_from_the_seed_with_next_token_targets():
    a, b = build(seed=3).data, build(seed=4).data
    batch = a.next_train_batch(1)
    assert batch["x"].dtype == batch["y"].dtype == np.int32
    assert batch["x"].shape == batch["y"].shape == (2, 16)
    assert (a._train_seq[:, 1:] == a.y_train).all()
    assert (a._train_seq[:, :-1] == a.x_train).all()
    assert not (a._train_seq == b._train_seq).all()
    assert (build(seed=3).data._train_seq == a._train_seq).all()
    big = build(seed=2147489999, synthetic_train=512).data._train_seq
    counts = np.bincount(big.ravel(), minlength=128)
    assert counts.min() > 0.5 * counts.mean()           # the whole vocabulary


def test_it_trains_through_the_rules_own_path():
    from theanompi_tpu import BSP
    rule = BSP()
    rule.init(devices=1, modelfile="theanompi_tpu.models.looped_lm",
              modelclass="LoopedLM", epochs=1, synthetic_train=32,
              synthetic_val=4, printFreq=100, verbose=False, **SIZES)
    rec = rule.wait()
    assert np.isfinite(rec.epoch_records[-1]["val_cost"])
    assert set(rule.model.step_state["opt_state"]) == {"m", "v", "t"}
    attn = rule.model.blocks[0].attn
    assert isinstance(attn, L.RotaryAttention)
    assert (attn.attn_impl, attn.theta) == ("reference", 1e6)


# -- the lowering, pinned ---------------------------------------------------------------

@pytest.mark.parametrize("impl, t, want", [
    ("reference", 16,
     "04e3b3a7d4f5b376cdb0762f697bbed77653ab391265be66d90eedb05da3337a"),
    ("flash", 128,
     "4499a5849d3fb5c3a001dad4b6c768f7eee3982328dbd057afdc73312a549b17")])
def test_the_looped_steps_lowering_is_what_it_was(impl, t, want):
    """The looped model's loss and gradient lowered for a TPU at toy size:
    the StableHLO's hash since PR 38, whose heads take their loss and
    gradients from one pass (``layers.weighted_cross_entropy``; PR 37 had
    left the hashes of commit 6180482, PR 36, as they were).  A change to
    what the looped cell shares with another model (``attend``,
    ``flash_tiles``, ``splash_attention``, ``rotary``, ``RMSNorm``,
    ``GatedMLP``, ``weighted_cross_entropy``) that moves this has changed
    the looped cell's program: measure that cell, then pin the new hash.  The Pallas
    kernels' serialized bodies are cut out first: they hold the call
    stack's file paths and line numbers, which differ from one checkout
    to the next."""
    import hashlib
    import re

    model = LoopedLM(dict(
        vocab=128, d_model=256, n_head=2, n_layer=2, d_ff=96, seq_len=t,
        loop_steps=3, n_workers=1, seed=3, batch_size=2, synthetic_train=8,
        synthetic_val=4, verbose=False, attn_impl=impl))
    model.head_block = t
    x = jnp.zeros((2, t), jnp.int32)
    step = jax.jit(jax.value_and_grad(
        lambda p, x: model.loss_and_metrics(p, {}, {"x": x, "y": x}, None,
                                            True)[0]))
    text = step.trace(jax.device_get(model.params), x).lower(
        lowering_platforms=("tpu",)).as_text()
    text, kernels = re.subn(
        r'(\\22body\\22: \\22)[A-Za-z0-9+/=]*(\\22)', r"\1\2", text)
    assert kernels == (3 if impl == "flash" else 0)
    assert hashlib.sha256(text.encode()).hexdigest() == want
