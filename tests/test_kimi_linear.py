"""The routed model in Kimi Linear's form at a small size on the CPU: Kimi
Delta Attention layers (a chunked gated delta rule with a decay per
channel), a latent attention layer without rotary turns, a sigmoid router
over 16 experts with a shared expert.  The chunked recurrence is held to
the token-by-token one of the plain reference
(``benchmarks/reference/kimi_linear.py``), forward and backward, under a
decay so strong that no exponent of a cumulative decay alone would be
finite; the latent attention to a plain softmax; the whole model to the
reference on seeded weights (logits, loss, every leaf's gradient and the
training check's gaps over three Adam steps); the expert shares to the
uncut layer; the counters through the rule."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import check, kimi_linear  # noqa: E402
from benchmarks.reference.check import by_path  # noqa: E402
from benchmarks.reference.laguna import gated_mlp  # noqa: E402
from benchmarks.reference.lfm2 import routed_part  # noqa: E402
from theanompi_tpu.models import layers as L  # noqa: E402
from theanompi_tpu.models.routed_lm import RoutedLM  # noqa: E402
from theanompi_tpu.parallel.moe import HeldExperts  # noqa: E402
from theanompi_tpu.utils import telemetry  # noqa: E402

F32 = jnp.float32
# the toy model: a kda layer with the dense feed-forward, then the 3 : 1
# period's rest (kda, kda, mla, kda) with routed ones; 4 of 16 experts, 4 a
# token, a shared expert; four heads, so that the reference takes its
# operators a head at a time (HEAD_GROUPS)
TOY = dict(vocab=128, d_model=48, n_layer=5,
           layer_types=["kda", "kda", "kda", "mla", "kda"],
           mlp_layer_types=["dense"] + ["sparse"] * 4,
           n_head_per_layer=[4] * 5, kda_head_dim=12,
           kv_lora_rank=24, qk_nope_head_dim=8, qk_rope_head_dim=4,
           v_head_dim=12, d_ff=96, n_experts=16, experts_held=[0, 4], top_k=4,
           expert_width=16, shared_width=16, routed_scale=2.446,
           router_scoring="sigmoid", norm_eps=1e-5, seq_len=64)
REF = dict(head_dim=12, nope=8, rope=4, v_dim=12, top_k=4, scale=2.446,
           eps=1e-5, first_held=0)


def build(**over):
    cfg = dict(TOY, n_workers=1, seed=3, batch_size=2, synthetic_train=8,
               synthetic_val=4, compute_dtype="float32", verbose=False,
               head_block=32)
    cfg.update(over)
    return RoutedLM(cfg)


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def params(model):
    """Seeded weights, every vector moved off its initial value so that
    each takes part, the routers widened so that choices differ by more
    than rounding, the convolutions' taps and the low-rank products widened
    so that their gradients are not small beside the rest."""
    r = np.random.RandomState(5)
    p = jax.tree.map(
        lambda a: a + 0.1 * r.standard_normal(a.shape).astype(np.float32)
        if a.ndim == 1 else a, jax.device_get(model.params))
    for i in range(5):
        blk = p[f"block{i}"]
        if "moe" in blk:
            blk["moe"]["router"] = 10 * blk["moe"]["router"]
        if "kda" in blk:
            for k in ("q_conv", "k_conv", "v_conv", "w_fb", "w_gb"):
                blk["kda"][k] = 10 * blk["kda"][k]
    return p


@pytest.fixture(scope="module")
def tokens():
    seq = np.random.RandomState(0).randint(0, 128, (2, 65)).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


def objective(model, p, x, y):
    return model.loss_and_metrics(p, model.bn_state, {"x": x, "y": y}, None,
                                  True)[0]


# -- the chunked delta rule against the token recurrence -----------------------

def recurrence_inputs(seed, decay, t=96, h=3, d=16):
    """Unit keys, unit queries over sqrt(d), values, log decays ``-decay *
    e^N(0, 1)`` a token and channel, ``beta`` in (0, 1)."""
    r = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.randn(2, t, h, d)) / np.sqrt(d)
    k, v = unit(r.randn(2, t, h, d)), r.randn(2, t, h, d)
    a = -decay * np.exp(r.randn(2, t, h, d))
    beta = 1 / (1 + np.exp(-r.randn(2, t, h)))
    return tuple(jnp.asarray(x, F32) for x in (q, k, v, a, beta))


@pytest.mark.parametrize("decay, chunk, t", [
    (0.05, 32, 96), (0.05, 16, 96), (6.0, 32, 96), (20.0, 32, 96),
    (0.05, 64, 192), (0.05, 64, 64), (0.05, 2, 96)],
    ids=["mild", "mild-chunk16", "strong", "drastic", "three-chunks-of-64",
         "one-chunk", "three-groups"])
def test_the_chunked_delta_rule_is_the_token_recurrence(decay, chunk, t):
    """Float32: the output to 1e-5 of its largest entry and every input's
    gradient to 1e-4 of its norm, over three chunks and more, three chunks
    of the cells' 64, a single chunk (as the model's tests run it) and 48
    chunks of 2, three groups of ``KDA_GROUP`` with the state and its
    cotangent carried across.  Under the strong decays the cumulative log
    decay over a chunk falls below -100, where ``e^-G`` alone is not a
    float32 number: nothing the chunked form makes overflows."""
    q, k, v, a, beta = recurrence_inputs(int(decay * 10) + chunk, decay, t=t)
    if decay >= 6.0:
        falls = jnp.sum(a.reshape(2, -1, chunk, 3, 16), axis=2)
        assert float(jnp.max(falls)) < -100
    if chunk == 2:
        assert t // chunk == 3 * L.KDA_GROUP
    core = lambda *x: L.kda_core(*x, chunk=chunk, compute_dtype=F32)  # noqa
    with jax.default_matmul_precision("highest"):
        got, want = core(q, k, v, a, beta), kimi_linear.delta_rule(
            q, k, v, a, beta)
        assert bool(jnp.isfinite(got).all())
        assert float(jnp.max(jnp.abs(got - want))) \
            <= 1e-5 * float(jnp.max(jnp.abs(want)))
        w = jnp.asarray(np.random.RandomState(1).randn(*got.shape), F32)
        grads = [jax.grad(lambda *x: jnp.sum(f(*x) * w), argnums=range(5))(
            q, k, v, a, beta) for f in (core, kimi_linear.delta_rule)]
    for name, g, gw in zip("q k v a beta".split(), *grads):
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.linalg.norm(g - gw)) \
            <= 1e-4 * float(jnp.linalg.norm(gw)), name


def test_the_written_gradient_is_autodiffs_in_bfloat16(monkeypatch):
    """In bfloat16, as the cells run it: the written backward pass against
    ``jax.grad`` of the same chunked forward without its rule, over three
    groups of chunks of 16 (q, k and v in bfloat16, their cotangents too):
    the outputs equal, every input's gradient to 1e-2 of its norm (a few
    bfloat16 roundings) from autodiff's and from the float32 token
    recurrence's."""
    q, k, v, a, beta = recurrence_inputs(11, 0.5, t=768, h=2, d=32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    core = lambda *x: L.kda_core(*x, chunk=16,  # noqa: E731
                                 compute_dtype=jnp.bfloat16)
    w = jnp.asarray(np.random.RandomState(2).randn(2, 768, 2, 32), F32)

    def outputs_and_grads():
        return jax.jit(lambda *x: (core(*x), jax.grad(
            lambda *x: jnp.sum(core(*x) * w), argnums=range(5))(*x)))(
                q, k, v, a, beta)

    got, grads = outputs_and_grads()
    monkeypatch.setattr(L, "_kda", lambda cd, *x: L._kda_fwd(cd, *x)[0])
    want, auto = outputs_and_grads()
    assert bool(jnp.all(got == want))
    with jax.default_matmul_precision("highest"):
        ref = jax.grad(lambda *x: jnp.sum(kimi_linear.delta_rule(*x) * w),
                       argnums=range(5))(*(x.astype(F32) for x in (
                           q, k, v, a, beta)))
    for name, g, ga, gr in zip("q k v a beta".split(), grads, auto, ref):
        assert g.dtype == ga.dtype, name
        g, ga = g.astype(F32), ga.astype(F32)
        assert bool(jnp.isfinite(g).all()), name
        for other in (ga, gr):
            assert float(jnp.linalg.norm(g - other)) \
                <= 1e-2 * float(jnp.linalg.norm(other)), name


@pytest.mark.parametrize("c, decay, beta", [
    (2, 0.5, (0.0, 1.0)), (16, 0.5, (0.0, 1.0)), (64, 0.5, (0.0, 1.0)),
    (16, 20.0, (0.0, 1.0)), (64, 6.0, (0.0, 1.0)), (16, 0.5, (0.0, 1e-3)),
    (64, 0.5, (1 - 1e-3, 1.0))],
    ids=["chunk2", "chunk16", "chunk64", "strong-chunk16", "strong-chunk64",
         "beta-near-0", "beta-near-1"])
def test_the_written_chunk_pull_back_is_autodiffs(c, decay, beta):
    """The chunk work's pull-back written out (``T``'s gradient as ``-T^T
    dT T^T``, the pairs' transpose level by level, from ``T`` and ``a_kk``
    as the forward kept them) against ``jax.vjp`` of the chunk work, in
    float32 at the highest precision, random cotangents of all six
    outputs: every input's cotangent to 1e-5 of its norm, for chunks of
    2, 16 and 64, decays whose fall over a chunk is below -100, and
    ``beta`` near 0 and near 1."""
    r = np.random.RandomState(c + int(decay))
    q, k, v, a, _ = recurrence_inputs(c, decay, t=c, h=4, d=16)
    q, k, v, a = (x.swapaxes(1, 2) for x in (q, k, v, a))  # [B, H, C, d]
    b = jnp.asarray(r.uniform(*beta, (2, 4, c, 1)), F32)
    if decay > 1:
        assert float(jnp.max(jnp.sum(a, -2))) < -100
    with jax.default_matmul_precision("highest"):
        work, back = jax.vjp(lambda *x: L._kda_chunk_work(F32, *x)[0],
                             q, k, v, a, b)
        _, (t, a_kk) = L._kda_chunk_work(F32, q, k, v, a, b)
        cots = tuple(jnp.asarray(r.randn(*w.shape), F32) for w in work)
        want = back(cots)
        got = L._kda_chunk_back(F32, q, k, v, a, b, t, a_kk, cots)
    for name, g, w in zip("q k v a beta".split(), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert float(jnp.linalg.norm(g - w)) \
            <= 1e-5 * float(jnp.linalg.norm(w)), name


def test_the_unit_lower_inverse_is_by_substitution():
    """``(I + L)^-1`` against a float64 inverse: random entries, and all
    ones below the diagonal, whose inverse has ones and minus ones while
    the powers of ``L`` grow as binomial coefficients (a series in ``L``
    would lose every digit)."""
    r = np.random.RandomState(2)
    for lower in (np.tril(r.randn(3, 64, 64), -1),
                  np.tril(np.ones((1, 64, 64)), -1)):
        got = np.asarray(L._unit_lower_inverse(jnp.asarray(lower, F32)))
        want = np.linalg.inv(np.eye(64) + lower)
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * max(
            1.0, np.abs(want).max()))


def test_the_decayed_pairs_take_no_positive_exponent():
    """The pairs ``sum_c x_ic y_jc e^{g_ic - g_jc}``, ``j < i``, against
    the sum made pair by pair, where ``g`` falls by 50 a position: every
    ``e^{-g}`` alone overflows, and the result is finite and right to the
    rounding of ``g`` itself, a float32 of a thousand or so (a part in
    10^4 of each exponential)."""
    r = np.random.RandomState(3)
    x, y = r.randn(2, 16, 8), r.randn(2, 16, 8)
    g = -np.cumsum(np.full((2, 16, 8), 50.0) * r.uniform(0.5, 1.5,
                                                       (2, 16, 8)), axis=1)
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-g).astype(np.float32)).all()
    got = np.asarray(L._decayed_pairs(*(jnp.asarray(z, F32)
                                         for z in (x, y, g)), F32))
    want = np.zeros((2, 16, 16))
    for i in range(16):
        for j in range(i):
            want[:, i, j] = np.sum(x[:, i] * y[:, j] * np.exp(g[:, i]
                                                                - g[:, j]), -1)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-30)


# -- the latent attention --------------------------------------------------------

def test_latent_attention_is_a_plain_softmax():
    """Against the reference's blocked softmax and against one written out
    here: q and k of 16 + 8 with the 8 shared by the heads and not turned,
    v of 12, the latent normed."""
    mla = L.LatentAttention(20, 3, 10, 16, 8, 12, 1e-5,
                            w_init=("normal", 0.3), compute_dtype=F32)
    p = mla.init(jax.random.key(4))
    assert jax.tree.map(jnp.shape, p) == {
        "wq": (20, 72), "w_kva": (20, 18), "kv_norm": {"scale": (10,)},
        "w_kvb": (10, 84), "wo": (36, 20)}
    p["kv_norm"] = {"scale": jnp.asarray(
        1 + 0.3 * np.random.RandomState(6).randn(10), F32)}
    x = jax.random.normal(jax.random.key(5), (2, 12, 20), F32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(mla.apply(p, x), np.float64)
        ref = np.asarray(kimi_linear.mla(p, x, 16, 8, 12, 1e-5), np.float64)
    xn = np.asarray(x, np.float64)
    ckr = xn @ np.asarray(p["w_kva"], np.float64)
    c = ckr[..., :10]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + 1e-5) \
        * np.asarray(p["kv_norm"]["scale"], np.float64)
    kv = (c @ np.asarray(p["w_kvb"], np.float64)).reshape(2, 12, 3, 28)
    q = (xn @ np.asarray(p["wq"], np.float64)).reshape(2, 12, 3, 24)
    k = np.concatenate([kv[..., :16], np.broadcast_to(
        ckr[:, :, None, 10:], (2, 12, 3, 8))], -1)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(24)
    s = np.where(np.tril(np.ones((12, 12), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("bhqk,bkhd->bqhd", w / w.sum(-1, keepdims=True),
                  kv[..., 16:]).reshape(2, 12, 36)
    want = o @ np.asarray(p["wo"], np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ref, want, rtol=1e-4, atol=1e-5)


# -- the model against the reference -------------------------------------------------

def test_the_stack_is_the_pattern(model):
    got = [(type(b.attn).__name__, type(b.ff).__name__) for b in model.blocks]
    assert got == [("DeltaAttention", "GatedMLP")] + [
        ("DeltaAttention", "HeldExperts")] * 2 + [
        ("LatentAttention", "HeldExperts"), ("DeltaAttention", "HeldExperts")]
    shapes = {k: v.shape for k, v in by_path(model.params).items()}
    assert shapes["block0/kda/wq"] == (48, 48)
    assert shapes["block0/kda/q_conv"] == (4, 48)
    assert shapes["block0/kda/w_fa"] == (48, 12)
    assert shapes["block0/kda/w_fb"] == (12, 48)
    assert shapes["block0/kda/b_dt"] == (48,)
    assert shapes["block0/kda/A_log"] == (4,)
    assert shapes["block0/kda/w_beta"] == (48, 4)
    assert shapes["block0/kda/o_norm/scale"] == (12,)
    assert shapes["block3/mla/wq"] == (48, 4 * 12)
    assert shapes["block3/mla/w_kva"] == (48, 24 + 4)
    assert shapes["block3/mla/w_kvb"] == (24, 4 * 20)
    assert shapes["block1/moe/shared_expert/wg"] == (48, 16)
    assert shapes["head/w"] == (48, 128)
    # the decay rates' initialisation: exp(A_log) in [1, 16], softplus(b_dt)
    # in [1e-3, 1e-1]
    kda = model.params["block0"]["kda"]
    assert 1 <= float(jnp.exp(kda["A_log"]).min()) \
        <= float(jnp.exp(kda["A_log"]).max()) <= 16
    dt = jax.nn.softplus(kda["b_dt"])
    assert 1e-3 * 0.999 <= float(dt.min()) <= float(dt.max()) <= 0.1 * 1.001


def test_logits_and_loss_are_the_references(model, params, tokens):
    x, y = tokens
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: model.apply_model(
            p, x, train=False, rng=None, state=model.bn_state)[0])(params)
        want = jax.jit(lambda p: kimi_linear.forward(p, x, **REF))(params)
        loss = jax.jit(lambda p: objective(model, p, x, y))(params)
        ref_loss = jax.jit(lambda p: kimi_linear.train_loss(p, x, y, **REF))(
            params)
    assert got.shape == want.shape == (2, 64, 128)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale
    assert abs(float(loss) - float(ref_loss)) < 1e-5


def test_every_leafs_gradient_is_the_references(model, params, tokens):
    x, y = tokens
    with jax.default_matmul_precision("highest"):
        got = by_path(jax.jit(jax.grad(
            lambda p: objective(model, p, x, y)))(params))
        want = by_path(jax.jit(jax.grad(
            lambda p: kimi_linear.train_loss(p, x, y, **REF)))(params))
    assert set(got) == set(want) and len(got) == 109
    for k in want:
        norm = float(jnp.linalg.norm(want[k]))
        assert norm > 0, k
        assert float(jnp.linalg.norm(got[k] - want[k])) < 1e-4 * norm, k


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_three_adam_steps_are_inside_the_training_checks_limits(params,
                                                                dtype):
    """The program in ``dtype`` and its own Adam over three batches
    against the reference's three steps (``check.follow_steps``), compared
    as the cell's check compares them; float32 is the reference to
    rounding."""
    model = build(compute_dtype=dtype)
    r = np.random.RandomState(7)
    batches = []
    for _ in range(check.TRAIN_STEPS):
        seq = r.randint(0, 128, (2, 65)).astype(np.int32)
        batches.append((seq[:, :-1], seq[:, 1:]))
    hp = {"name": "adam", "learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
          "eps": 1e-8}
    step = jax.jit(lambda p, s, x, y: (lambda c, g: (c,) + model.opt.update(
        g, s, p, 3e-4))(*jax.value_and_grad(
            lambda p: objective(model, p, x, y))(p)))
    p, state, losses = params, model.opt.init(params), []
    for i, (x, y) in enumerate(batches):
        cost, p, state = step(p, state, x, y)
        losses.append(float(cost))
        if i == 0:
            first_moment = state["m"]
    named = ("block0/kda/A_log", "block3/mla/w_kvb")
    ref = check.follow_steps(
        lambda p, x, y: kimi_linear.train_loss(p, x, y, **REF), params,
        batches, 1, 3e-4, hp, named)
    got = check.compare_steps(
        ref, {"losses": losses, "first_moment": first_moment, "params": p},
        params, 0.9, named)
    assert got["ok"] and got["leaves_nought"] == [], got
    if dtype == "float32":
        assert got["grad_norm_gap"] < 1e-4 and got["change_norm_gap"] < 1e-3
    else:
        assert 0 < got["grad_norm_gap"] < check.GRAD_NORM_TOL
        assert 0 < got["change_norm_gap"] < check.CHANGE_NORM_TOL


def test_it_trains_through_the_rule_and_counts_once():
    from theanompi_tpu import BSP
    names = ("model.kda_layers", "model.mla_layers", "model.kda_chunks",
             "model.conv_layers", "model.full_layers", "model.window_layers",
             "model.sigmoid_routers", "model.attn_kernel_applications",
             "model.kda_state_bytes", "model.kda_chunk_kept_bytes")
    assert set(names) <= set(telemetry.COUNTS)
    before = {k: telemetry.totals().get(k, (0, 0))[0] for k in names}
    rule = BSP()
    rule.init(devices=2, modelfile="theanompi_tpu.models.routed_lm",
              modelclass="RoutedLM", epochs=1, synthetic_train=8,
              synthetic_val=4, batch_size=2, printFreq=2, verbose=False,
              scale_lr=False, **TOY)
    rec = rule.wait()
    assert math.isfinite(rec.epoch_records[-1]["val_cost"])
    after = {k: telemetry.totals()[k][0] - before[k] for k in names}
    # four kda layers of two 64-token sequences: one chunk each a layer and
    # sequence, whose starting state of 4 heads of 12 x 12 the backward
    # pass keeps in float32, and of whose work of 64 x 64 a head T with
    # a_kk (one float32 array) and a_qk (bfloat16, the default compute
    # dtype); one mla layer, on the reference path here
    assert after == {"model.kda_layers": 4, "model.mla_layers": 1,
                     "model.kda_chunks": 4 * 2 * 1, "model.conv_layers": 0,
                     "model.full_layers": 0, "model.window_layers": 0,
                     "model.sigmoid_routers": 4,
                     "model.attn_kernel_applications": 0,
                     "model.kda_state_bytes": 4 * 2 * 1 * 4 * 12 * 12 * 4,
                     "model.kda_chunk_kept_bytes":
                         4 * 2 * 1 * 4 * 64 * 64 * (4 + 2)}


def test_the_scopes_reach_the_lowering(model, params, tokens):
    """Every operator's scope, and the delta rule's three passes under
    ``kda_core``: the forward, the forward the layer's checkpoint makes
    again, and the written backward pass, which runs under ``transpose``
    as autodiff's did."""
    x, y = tokens
    text = jax.jit(jax.grad(lambda p: objective(model, p, x, y))).lower(
        params).as_text(debug_info=True)
    for scope in ("jvp(block0)/kda/", "kda/kda_core/",
                  "checkpoint/kda/kda_core/", "mla/kv_norm/",
                  "mla/attn_core/", "moe/shared_expert/"):
        assert scope in text, scope
    for way in ("jvp(block0)/kda/kda_core/",
                "transpose(jvp(block0))/jvp(block0)/checkpoint/"
                "rematted_computation/kda/kda_core/while",
                "transpose(jvp(block0))/jvp(block0)/checkpoint/kda/kda_core/"
                "while"):
        assert way in text, way


# -- the expert shares -----------------------------------------------------------------

D, E, K, W = 32, 64, 8, 16


@pytest.mark.parametrize("shares", [32, 8])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The deployment's 32 shares of 2 experts (and 8 of 8): their routed
    parts, with the shared expert counted once, add up to the uncut
    reference layer (a sigmoid router over 64 choosing 8, scaling 2.446,
    a shared expert) under a nonzero bias, and each share is the reference
    at that share."""
    whole = HeldExperts(D, E, (0, E), K, W, W, 2.446, "sigmoid",
                        compute_dtype=F32, w_init=("normal", 0.3))
    params = whole.init(jax.random.key(1))
    x = jax.random.normal(jax.random.key(7), (2, 24, D), F32)
    xf = x.reshape(-1, D)
    b = jnp.asarray(0.2 * np.random.RandomState(4).standard_normal(E), F32)
    size = E // shares
    with jax.default_matmul_precision("highest"):
        shared = gated_mlp(params["shared_expert"], xf)
        want = shared + routed_part(params, xf, b, K, 2.446, 0)
        total = shared
        for first in range(0, E, size):
            layer = HeldExperts(D, E, (first, first + size), K, W, W, 2.446,
                                "sigmoid", compute_dtype=F32)
            p = dict(params, experts=jax.tree.map(
                lambda a: a[first:first + size], params["experts"]))
            got = jax.jit(layer.apply)(p, x, state=b).reshape(-1, D)
            mine = routed_part(p, xf, b, K, 2.446, first)
            np.testing.assert_allclose(got, shared + mine, rtol=1e-4,
                                       atol=1e-5)
            total = total + got - shared
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 0.1
