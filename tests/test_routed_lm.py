"""The routed language model at a small size on the CPU: held to the plain
reference (logits, loss, every leaf's gradient); its attention against a
dense masked softmax; its expert layer's shares against the uncut layer,
and at both extremes of routing (nothing is dropped)."""

import functools
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

from benchmarks.reference import laguna  # noqa: E402
from benchmarks.reference.check import by_path  # noqa: E402
from theanompi_tpu.models import layers as L  # noqa: E402
from theanompi_tpu.models.routed_lm import (RoutedLM,  # noqa: E402
                                            rotary_frequencies)
from theanompi_tpu.parallel import moe  # noqa: E402
from theanompi_tpu.parallel.moe import HeldExperts  # noqa: E402
from theanompi_tpu.utils import telemetry  # noqa: E402

F32 = jnp.float32
# the toy sizes are the class's defaults; the reference is told the same
REF = dict(head_dim=16, window=8, top_k=4, scale=2.5, eps=1e-6, first_held=0,
           layer_types=RoutedLM.layer_types, rope=RoutedLM.rope)


def build(**over):
    cfg = dict(n_workers=1, seed=3, batch_size=2, synthetic_train=8,
               synthetic_val=4, compute_dtype="float32", verbose=False,
               head_block=16)
    cfg.update(over)
    return RoutedLM(cfg)


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def params(model):
    """Seeded weights, the norms' scales moved off 1 so that each takes
    part."""
    r = np.random.RandomState(5)
    return jax.tree.map(
        lambda a: a + 0.1 * r.standard_normal(a.shape).astype(np.float32)
        if a.ndim == 1 else a, jax.device_get(model.params))


@pytest.fixture(scope="module")
def tokens():
    seq = np.random.RandomState(0).randint(0, 128, (2, 33)).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


def objective(model, p, x, y):
    return model.loss_and_metrics(p, {}, {"x": x, "y": y}, None, True)[0]


# -- the model against the reference ------------------------------------------------

def test_the_stack_is_the_pattern(model):
    """Four layers from four rows: full+dense, window+sparse twice,
    full+sparse; query heads 2, 3, 3, 2 over one key/value head."""
    got = [(type(b.ff).__name__, b.attn.window, b.attn.n_q, b.attn.n_kv,
            len(b.attn.freq), b.attn.rope_factor) for b in model.blocks]
    assert got == [("GatedMLP", None, 2, 1, 4, 1.2),
                   ("HeldExperts", 8, 3, 1, 8, 1.0),
                   ("HeldExperts", 8, 3, 1, 8, 1.0),
                   ("HeldExperts", None, 2, 1, 4, 1.2)]
    assert [b.name for b in model.blocks] == [f"block{i}" for i in range(4)]
    assert sum(a.size for a in jax.tree.leaves(model.params)) == 166_080
    moe = model.blocks[1].ff
    assert (moe.n_experts, moe.first, moe.n_held, moe.top_k) == (16, 0, 4, 4)
    # a longer pattern is read as far as the depth held
    longer = build(n_layer=2, layer_types=["sliding_attention"] * 9,
                   mlp_layer_types=["sparse"] * 9,
                   n_head_per_layer=[3] * 9)
    assert [b.attn.window for b in longer.blocks] == [8, 8]
    with pytest.raises(AssertionError, match="pattern names"):
        build(n_layer=5)


def test_logits_and_loss_are_the_references(model, params, tokens):
    x, y = tokens
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: model.apply_model(
            p, x, train=False, rng=None, state={})[0])(params)
        want = jax.jit(lambda p: laguna.forward(p, x, **REF))(params)
        loss = jax.jit(lambda p: objective(model, p, x, y))(params)
        ref_loss = jax.jit(
            lambda p: laguna.train_loss(p, x, y, **REF))(params)
    assert got.shape == want.shape == (2, 32, 128)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    # the head in blocks of 16 tokens is the head at once
    whole = build(head_block=64)
    with jax.default_matmul_precision("highest"):
        again = jax.jit(lambda p: objective(whole, p, x, y))(params)
    assert abs(float(again) - float(loss)) < 1e-6


def test_every_leafs_gradient_is_the_references(model, params, tokens):
    x, y = tokens
    with jax.default_matmul_precision("highest"):
        got = by_path(jax.jit(jax.grad(
            lambda p: objective(model, p, x, y)))(params))
        want = by_path(jax.jit(jax.grad(
            lambda p: laguna.train_loss(p, x, y, **REF)))(params))
    assert set(got) == set(want) and len(got) == 55
    for k in want:
        norm = float(jnp.linalg.norm(want[k]))
        assert norm > 0, k
        assert float(jnp.linalg.norm(got[k] - want[k])) < 1e-4 * norm, k


def test_bfloat16_is_inside_the_training_checks_limits(params, tokens):
    """The first gradient's norms leaf by leaf, bfloat16 program against
    the float32 reference: the measure ``check.compare_steps`` takes."""
    from benchmarks.reference import check
    x, y = tokens
    model = build(compute_dtype="bfloat16")
    got = by_path(jax.jit(jax.grad(
        lambda p: objective(model, p, x, y)))(params))
    with jax.default_matmul_precision("highest"):
        want = by_path(jax.jit(jax.grad(
            lambda p: laguna.train_loss(p, x, y, **REF)))(params))
    gap, leaf = check._worst_gap(
        {k: float(jnp.linalg.norm(v)) for k, v in want.items()},
        {k: float(jnp.linalg.norm(v)) for k, v in got.items()})
    assert 0 < gap < check.GRAD_NORM_TOL, (gap, leaf)


def test_it_trains_through_the_rule_and_counts_once():
    from theanompi_tpu import BSP
    names = ("model.experts_held", "model.experts_routed",
             "model.routed_pairs", "model.routed_rows_at_once",
             "model.window_layers", "model.full_layers",
             "model.attn_kernel_applications")
    assert set(names) <= set(telemetry.COUNTS)
    before = {k: telemetry.totals().get(k, (0, 0))[0] for k in names}
    rule = BSP()
    rule.init(devices=2, modelfile="theanompi_tpu.models.routed_lm",
              modelclass="RoutedLM", epochs=1, synthetic_train=16,
              synthetic_val=4, batch_size=2, printFreq=2, verbose=False,
              scale_lr=False)
    rec = rule.wait()
    assert math.isfinite(rec.epoch_records[-1]["val_cost"])
    after = {k: telemetry.totals()[k][0] - before[k] for k in names}
    # three routed layers of 4 of 16 experts; 2 rows x 32 tokens x 4 a
    # token in each, of which a quarter is expected here: the 64 pairs a
    # layer overflow its first stretch of 16 rows, so the walks' loops run;
    # two window layers, two full ones; no kernel here
    assert after == {"model.experts_held": 12, "model.experts_routed": 48,
                     "model.routed_pairs": 3 * 2 * 32 * 4,
                     "model.routed_rows_at_once": 3 * (2 * 32 * 4 // 16),
                     "model.window_layers": 2, "model.full_layers": 2,
                     "model.attn_kernel_applications": 0}


def test_the_scopes_reach_the_lowering(model, params, tokens):
    x, y = tokens
    text = jax.jit(lambda p: jax.grad(
        lambda p: objective(model, p, x, y))(p)).lower(params).as_text(
            debug_info=True)
    for scope in ("jvp(block0)/mlp/", "jvp(block3)/moe/", "attn/attn_core/",
                  "moe/router/", "jvp(block3)/moe/experts/",
                  "jvp(block3)/moe/while/body/experts/",
                  "checkpoint/moe/experts/",
                  "checkpoint/moe/while/body/experts/",
                  "moe/shared_expert/", "jvp(head)/"):
        assert scope in text, scope


# -- the attention layer ---------------------------------------------------------------

def dense_attention(p, x, n_q, n_kv, hd, freq, factor, window):
    """Every head's [T, T] softmax written out, no grouping trick: query
    head i reads key/value head i // (n_q / n_kv)."""
    b, t, _ = x.shape
    heads = lambda w, n: (x @ w).reshape(b, t, n, hd).transpose(0, 2, 1, 3)  # noqa: E731,E501
    q = L.rotary_turn(heads(p["wq"], n_q), freq, factor)
    k = L.rotary_turn(heads(p["wk"], n_kv), freq, factor)
    v = heads(p["wv"], n_kv)
    gate = jax.nn.sigmoid(x @ p["wg"])                      # [B, T, n_q]
    back = np.arange(t)[:, None] - np.arange(t)[None]
    seen = (back >= 0) & (back < (window or t))
    out = []
    for i in range(n_q):
        j = i // (n_q // n_kv)
        s = jnp.where(seen, q[:, i] @ k[:, j].transpose(0, 2, 1)
                      / np.sqrt(hd), -jnp.inf)
        out.append(jax.nn.softmax(s, -1) @ v[:, j] * gate[:, :, i, None])
    return jnp.concatenate(out, axis=-1) @ p["wo"]


@pytest.mark.parametrize("window", [None, 4, 1])
@pytest.mark.parametrize("n_q, n_kv", [(6, 2), (3, 1), (2, 2)])
def test_grouped_query_attention_is_the_dense_masked_softmax(window, n_q,
                                                             n_kv):
    """Query heads != key/value heads, a causal window, rotary over half
    the head with a factor, the per-head gate."""
    freq = 50.0 ** (-np.arange(0, 4, 2) / 4)
    attn = L.GroupedQueryAttention(24, n_q, n_kv, 8, freq, rope_factor=1.3,
                                   window=window, compute_dtype=F32,
                                   w_init=("normal", 0.5), name="a")
    p = attn.init(jax.random.key(n_q + n_kv))
    assert {k: v.shape for k, v in p.items()} == {
        "wq": (24, 8 * n_q), "wk": (24, 8 * n_kv), "wv": (24, 8 * n_kv),
        "wg": (24, n_q), "wo": (8 * n_q, 24)}
    x = jax.random.normal(jax.random.key(1), (2, 12, 24), F32)
    with jax.default_matmul_precision("highest"):
        got = attn.apply(p, x)
        want = dense_attention(p, x, n_q, n_kv, 8, freq, 1.3, window)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_gate_scales_each_head_before_the_output_projection():
    freq = 100.0 ** (-np.arange(0, 8, 2) / 8)
    attn = L.GroupedQueryAttention(16, 2, 1, 8, freq, compute_dtype=F32,
                                   w_init=("normal", 0.5))
    p = attn.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 6, 16), F32)
    # a gate shut on head 1 (sigmoid(-inf)) leaves head 0's part alone
    shut = dict(p, wg=jnp.stack([jnp.zeros(16), jnp.full(16, -1e4)
                                 * jnp.sign(x[0, 0])], axis=1))
    only0 = dict(shut, wo=p["wo"].at[8:].set(0.0))
    with jax.default_matmul_precision("highest"):
        got = attn.apply(shut, x)[0, 0]
        want = attn.apply(only0, x)[0, 0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # and an open gate of sigmoid(0) = 1/2 halves the layer's output
    half = dict(p, wg=jnp.zeros((16, 2)))
    wide = dict(p, wg=jnp.stack([jnp.full(16, 1e4) * jnp.sign(x[0, 0])] * 2,
                                axis=1))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(2 * attn.apply(half, x)[0, 0],
                                   attn.apply(wide, x)[0, 0], rtol=1e-5,
                                   atol=1e-6)


def test_rotary_turn_turns_a_part_of_the_head_and_passes_the_rest():
    x = jax.random.normal(jax.random.key(0), (2, 5, 12), F32)
    whole = 30.0 ** (-np.arange(0, 12, 2) / 12)
    np.testing.assert_allclose(L.rotary_turn(x, whole), L.rotary(x, 30.0),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(L.rotary_turn(x, whole, scale=0.25),
                               L.rotary(x, 30.0, 0.25), rtol=1e-6,
                               atol=1e-6)
    part = L.rotary_turn(x, whole[:2], factor=1.5, scale=0.5)
    np.testing.assert_allclose(part[..., 4:], 0.5 * x[..., 4:], rtol=1e-6)
    np.testing.assert_allclose(
        part[..., :4], 0.5 * 1.5 * L.rotary_turn(x[..., :4], whole[:2]),
        rtol=1e-5, atol=1e-6)
    # position 0 is not turned
    np.testing.assert_allclose(part[:, 0, :4], 0.75 * x[:, 0, :4], rtol=1e-6)


@pytest.mark.parametrize("i, want", [
    # by hand at rotary size 64, theta 5e5, factor 128 over 8,192, beta 32
    # and 1: lo = floor(64 ln(8192 / (32 * 2 pi)) / (2 ln 5e5)) = 9,
    # hi = ceil(64 ln(8192 / (2 pi)) / (2 ln 5e5)) = 18
    (0, 1.0),                                   # kept whole: m = 1
    (12, 5e5 ** (-24 / 64) * (1 / 128 * (3 / 9) + (6 / 9))),   # blended
    (31, 5e5 ** (-62 / 64) / 128)])             # divided by the factor
def test_yarn_frequencies_by_hand(i, want):
    lo = 64 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(5e5))
    hi = 64 * math.log(8192 / (2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(lo), math.ceil(hi)) == (9, 18)
    published = laguna.PUBLISHED["rope"]["full_attention"]
    freq, factor = rotary_frequencies(published, 128)
    assert len(freq) == 32 and factor == 1.4852030263919618
    assert freq[i] == pytest.approx(want, rel=1e-6)
    ref, ref_factor = laguna.frequencies(published, 128)
    np.testing.assert_allclose(freq, ref, rtol=1e-6)
    assert ref_factor == factor
    # the window layers: theta 1e4 over the whole head, no factor
    freq, factor = rotary_frequencies(
        laguna.PUBLISHED["rope"]["sliding_attention"], 128)
    assert len(freq) == 64 and factor == 1.0
    assert freq[1] == pytest.approx(1e4 ** (-2 / 128))


# -- the expert layer: shares, and nothing dropped -----------------------------------------

D, E, K, W = 32, 12, 5, 16


def whole_layer(key=0):
    """The uncut layer: all 12 experts held, and a shared expert."""
    layer = HeldExperts(D, E, (0, E), K, W, W, 2.5, compute_dtype=F32,
                        w_init=("normal", 0.3))
    return layer, layer.init(jax.random.key(key))


def share_of(params, first, past, shared=True):
    layer = HeldExperts(D, E, (first, past), K, W, W if shared else 0, 2.5,
                        compute_dtype=F32)
    p = {"router": params["router"],
         "experts": jax.tree.map(lambda a: a[first:past],
                                 params["experts"])}
    if shared:
        p["shared_expert"] = params["shared_expert"]
    return layer, p


def reference_layer(p, x, first=0):
    return laguna.routed_mlp(p, x.reshape(-1, D), K, 2.5, first).reshape(
        x.shape)


@pytest.mark.parametrize("cuts", [(0, 12), (0, 4, 8, 12), (0, 1, 6, 7, 12),
                                  (0, 3, 6, 9, 12), (0, 2, 12), (0, 11, 12)])
def test_the_expert_shares_add_up_to_the_uncut_layer(cuts):
    """For every range of a partition of the experts: the routed parts of
    all shares and the shared expert counted once are the uncut
    reference's layer output; each share is the reference's at that
    share."""
    _, params = whole_layer()
    x = jax.random.normal(jax.random.key(7), (2, 24, D), F32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference_layer)(params, x)
        total = laguna.gated_mlp(params["shared_expert"],
                                 x.reshape(-1, D)).reshape(x.shape)
        for first, past in zip(cuts, cuts[1:]):
            layer, p = share_of(params, first, past, shared=False)
            total = total + jax.jit(layer.apply)(p, x)
            with_shared, ps = share_of(params, first, past)
            np.testing.assert_allclose(
                jax.jit(with_shared.apply)(ps, x),
                jax.jit(reference_layer, static_argnums=2)(ps, x, first),
                rtol=1e-4, atol=1e-5)
            # a share's gradients are the reference's at that share
            got = jax.jit(jax.grad(
                lambda p: jnp.sum(layer.apply(p, x) ** 2)))(p)
            ref = jax.jit(jax.grad(lambda p: jnp.sum(laguna.routed_part(
                p, x.reshape(-1, D), K, 2.5, first) ** 2)))(p)
            for k, g in by_path(ref).items():
                np.testing.assert_allclose(by_path(got)[k], g, rtol=2e-3,
                                           atol=1e-5, err_msg=k)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1


@pytest.mark.parametrize("n_q, n_kv, cuts", [(6, 2, (0, 1, 2)),
                                             (8, 4, (0, 1, 3, 4))])
def test_the_head_shares_add_up_to_the_uncut_attention(n_q, n_kv, cuts):
    """For every range of a partition of the key/value heads (each with its
    query heads): the shares' outputs add up to the whole layer's."""
    hd, d, group = 8, 24, n_q // n_kv
    freq = 50.0 ** (-np.arange(0, 4, 2) / 4)       # half the head turns
    make = lambda q, kv: L.GroupedQueryAttention(       # noqa: E731
        d, q, kv, hd, freq, window=5, compute_dtype=F32,
        w_init=("normal", 0.5))
    p = make(n_q, n_kv).init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (2, 10, d), F32)
    with jax.default_matmul_precision("highest"):
        want = make(n_q, n_kv).apply(p, x)
        ref = laguna.attention(p, x, "sliding_attention", hd, 5, {
            "sliding_attention": {"rope_type": "default", "rope_theta": 50.0,
                                  "partial_rotary_factor": 0.5}})
        total = 0.0
        for first, past in zip(cuts, cuts[1:]):
            q0, q1 = first * group * hd, past * group * hd
            share = {"wq": p["wq"][:, q0:q1], "wo": p["wo"][q0:q1],
                     "wg": p["wg"][:, first * group:past * group],
                     "wk": p["wk"][:, first * hd:past * hd],
                     "wv": p["wv"][:, first * hd:past * hd]}
            total = total + make((past - first) * group,
                                 past - first).apply(share, x)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(want, ref, rtol=1e-4, atol=1e-5)


def steer(params, x, experts, first=None, others=None):
    """Router weights under which every token of ``x`` chooses exactly
    ``experts`` (its ``K`` largest logits by a wide margin); with ``first``
    only the first so many tokens do, and the rest choose ``others``."""
    def bias(chosen):
        b = np.full((E,), -50.0, np.float32)
        b[list(chosen)] = 50.0 + np.arange(len(chosen))
        return b

    # logits = x W: W's last two rows read a feature that is one in the
    # first tokens and one that is one in the rest
    n = x.shape[0] * x.shape[1]
    here = (np.arange(n) < (n if first is None else first)).astype(
        np.float32).reshape(x.shape[:-1] + (1,))
    xs = jnp.concatenate([x[..., :-2], here, 1 - here], axis=-1)
    router = jnp.zeros((D, E)).at[-2].set(bias(experts)).at[-1].set(
        bias(experts if others is None else others)) \
        + 0.01 * params["router"].at[-2:].set(0.0)
    return dict(params, router=router), xs


NOT_HELD = (0, 1, 7, 8, 9)      # what a token steered away from (2, 7) chooses
# held, what the first tokens choose, how many they are (None: all 48), the
# pairs that gives; a stretch of (2, 7) is 48 * 5 // 16 = 15 rows
ROUTINGS = [
    pytest.param((2, 7), (2, 3, 4, 5, 6), None, 240, id="worst-case"),
    pytest.param((2, 7), NOT_HELD, None, 0, id="no-pair"),
    pytest.param((0, 3), (0, 1, 2, 10, 11), None, 144, id="two-absent"),
    pytest.param((2, 7), (2, 3, 4, 5, 6), 2, 10, id="part-of-a-stretch"),
    pytest.param((2, 7), (2, 3, 4, 5, 6), 3, 15, id="one-stretch-full"),
    pytest.param((2, 7), (2, 3, 4, 5, 6), 9, 45, id="three-stretches"),
    pytest.param((2, 7), (1, 2, 3, 8, 9), 20, 40, id="a-third-in-part")]


def steered_share(held, chosen, first, shared):
    """A share of the whole layer and tokens steered as a row of
    ``ROUTINGS`` says."""
    _, params = whole_layer(1)
    x = jax.random.normal(jax.random.key(9), (2, 24, D), F32)
    params, x = steer(params, x, chosen, first, NOT_HELD)
    layer, p = share_of(params, *held, shared=shared)
    return layer, p, x


@pytest.mark.parametrize("shared", [True, False],
                         ids=["shared-expert", "no-shared-expert"])
@pytest.mark.parametrize("held, chosen, first, pairs", ROUTINGS)
def test_nothing_is_dropped_at_either_extreme_of_routing(held, chosen, first,
                                                         pairs, shared):
    """With the router set so that every token chooses the held experts
    the buffer is full to its worst case, ``N * min(K, held)`` rows, and
    the layer is still the reference; a capacity-style drop would fail
    it.  With none chosen the routed part is nought (the walk's first
    stretch runs with every row cut off).  Between them: pairs that fill
    a part of the first stretch, all of it and no more (the walk's loop
    does not run), three stretches, and a third one in part.  Value and
    every gradient (the experts', the router's through the weights, the
    shared expert's through what the routed part is added into, the
    input's) are the reference's."""
    layer, p, x = steered_share(held, chosen, first, shared)
    stretch = layer.rows_at_once(48)
    assert 16 * stretch == 48 * min(K, held[1] - held[0]) >= pairs
    ref_p = p if shared else dict(p, shared_expert=jax.tree.map(
        jnp.zeros_like, whole_layer(1)[1]["shared_expert"]))
    with jax.default_matmul_precision("highest"):
        top, _ = layer.route(p, x.reshape(-1, D))
        local = np.asarray(top) - held[0]
        assert int(((local >= 0) & (local < layer.n_held)).sum()) == pairs
        (got, y), grads = jax.value_and_grad(
            lambda p, x: (lambda y: (jnp.sum(y ** 2), y))(layer.apply(p, x)),
            (0, 1), has_aux=True)(p, x)
        want, ref = jax.value_and_grad(
            lambda p, x: jnp.sum(reference_layer(p, x, held[0]) ** 2),
            (0, 1))(ref_p, x)
        base = laguna.gated_mlp(ref_p["shared_expert"],
                                x.reshape(-1, D)).reshape(x.shape)
    np.testing.assert_allclose(y, reference_layer(ref_p, x, held[0]),
                               rtol=1e-4, atol=1e-5)
    routed = float(jnp.max(jnp.abs(y - base)))
    assert (routed > 0.05) if pairs else (routed == 0.0)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    got_leaves, ref_leaves = by_path(grads), by_path(ref)
    for k in got_leaves:
        np.testing.assert_allclose(got_leaves[k], ref_leaves[k], rtol=2e-3,
                                   atol=1e-5, err_msg=str(k))
    if not pairs:
        assert not float(jnp.max(jnp.abs(grads[0]["experts"]["wd"])))


# The walk as it was until PR 40, kept here to hold the new one to it: one
# loop from stretch 0 into a zero array, the shared expert's result added
# behind it; the backward a loop into zero carries of every gradient's
# shape, each stretch's cotangents from ``jax.vjp`` of the forward's rows.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def parent_routed_part(rows, experts, xf, weights, order, ends):
    top_k = weights.shape[1]

    def some_rows(i, y):
        token, choice, sizes, live = moe._stretch(rows, order, ends, top_k, i)
        return y.at[token].add(moe._expert_rows(
            sizes, live, experts, xf[token], weights[token, choice]))

    return jax.lax.fori_loop(0, moe._stretches(rows, ends), some_rows,
                             jnp.zeros(xf.shape, F32))


def parent_fwd(rows, experts, xf, weights, order, ends):
    return parent_routed_part(rows, experts, xf, weights, order, ends), \
        (experts, xf, weights, order, ends)


def parent_bwd(rows, kept, dy):
    experts, xf, weights, order, ends = kept
    top_k = weights.shape[1]

    def some_rows(i, grads):
        token, choice, sizes, live = moe._stretch(rows, order, ends, top_k, i)
        _, back = jax.vjp(functools.partial(moe._expert_rows, sizes, live),
                          experts, xf[token], weights[token, choice])
        d_experts, d_xs, d_w = back(dy[token])
        g_experts, g_xf, g_weights = grads
        return (jax.tree.map(jnp.add, g_experts, d_experts),
                g_xf.at[token].add(d_xs.astype(F32)),
                g_weights.at[token, choice].add(d_w))

    zeros = lambda a: jnp.zeros(a.shape, F32)               # noqa: E731
    g_experts, g_xf, g_weights = jax.lax.fori_loop(
        0, moe._stretches(rows, ends), some_rows,
        (jax.tree.map(zeros, experts), zeros(xf), zeros(weights)))
    return g_experts, g_xf, g_weights, None, None


parent_routed_part.defvjp(parent_fwd, parent_bwd)


@pytest.mark.parametrize("shared", [True, False],
                         ids=["shared-expert", "no-shared-expert"])
@pytest.mark.parametrize("held, chosen, first, pairs", ROUTINGS)
def test_the_walk_is_the_parents_in_float32(held, chosen, first, pairs,
                                            shared):
    """Float32 operands, the routing given: the straight-line stretch, the
    loop behind it and the backward rule written out give the parent's
    value and its gradients to the experts, the tokens, the weights and
    what the routed part is added into, each to 1e-6 of its norm (a
    weight's gradient sums 16 products where the parent's summed 32,
    nothing else differs but the order of the sums)."""
    layer, p, x = steered_share(held, chosen, first, shared)
    xf = x.reshape(-1, D)
    chosen, weights = layer.route(p, xf)
    local = chosen.reshape(-1) - layer.first
    local = jnp.where((local >= 0) & (local < layer.n_held), local,
                      layer.n_held)
    order = jnp.argsort(local)
    ends = jnp.cumsum(jnp.sum(local[:, None] == jnp.arange(layer.n_held),
                              axis=0, dtype=jnp.int32))
    assert int(ends[-1]) == pairs
    base = layer.shared.apply(p["shared_expert"], xf) if shared \
        else jnp.zeros_like(xf)
    rows = layer.rows_at_once(48)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(moe._routed_part(
                rows, *a[:3], order, ends, a[3]) ** 2), (0, 1, 2, 3)))(
                    p["experts"], xf, weights, base)
        want = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum((parent_routed_part(
                rows, *a[:3], order, ends) + a[3]) ** 2), (0, 1, 2, 3)))(
                    p["experts"], xf, weights, base)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    got_leaves, want_leaves = by_path(got[1]), by_path(want[1])
    assert set(got_leaves) == set(want_leaves) and len(want_leaves) == 6
    for k, g in want_leaves.items():
        norm = float(jnp.linalg.norm(g))
        assert float(jnp.linalg.norm(got_leaves[k] - g)) <= 1e-6 * norm, k
        # with no pair only the cotangent handed through is not nought
        assert (norm > 0) == bool(pairs or (shared and k == "3")), k


def test_a_weight_not_held_stays_in_the_normalisation():
    """Two tokens' worth: the held experts' weights sum to the share of
    the chosen probability that is held, times the scale, not to the
    scale."""
    layer, params = whole_layer(2)
    x = jax.random.normal(jax.random.key(5), (1, 8, D), F32)
    with jax.default_matmul_precision("highest"):
        chosen, w = layer.route(params, x.reshape(-1, D))
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 2.5, rtol=1e-5)
    assert chosen.shape == w.shape == (8, K)
    held = (np.asarray(chosen) < 4)
    assert 0 < held.sum() < held.size       # some chosen are held, some not
    part = np.where(held, np.asarray(w), 0).sum(-1)
    assert (part < 2.5).any()


@pytest.mark.parametrize("first", [None, 2], ids=["the-loops-last-stretch",
                                                   "the-straight-line-one"])
def test_rows_of_no_group_are_cut_off_both_ways(monkeypatch, first):
    """On the chip a grouped product leaves in a row outside every group
    whatever it finds there, forward and transposed (the CPU's leaves
    nought, which hides it).  With products planted that leave NaN there
    (the forward's three, the backward rule's two made again and its three
    by a transposed matrix) and weight-gradient products planted that add
    in whatever both operands hold there, the layer and its gradients are
    still the reference's: in the last stretch of the loop, and in the
    straight-line stretch where the pairs fill a part of it."""
    from jax import lax

    rows_by_matrix, rows_by_rows = lax.ragged_dot, lax.ragged_dot_general

    def planted(lhs, rhs, group_sizes, **_):
        inside = jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(inside[:, None], rows_by_matrix(
            lhs, rhs, group_sizes, preferred_element_type=F32), jnp.nan)

    def planted_weight_gradient(lhs, rhs, group_sizes, **kw):
        outside = (jnp.arange(lhs.shape[0]) >= jnp.sum(group_sizes))[:, None]
        return rows_by_rows(lhs, rhs, group_sizes, **kw) + (
            jnp.where(outside, lhs, 0).T @ jnp.where(outside, rhs, 0))[None]

    monkeypatch.setattr(lax, "ragged_dot", planted)
    monkeypatch.setattr(lax, "ragged_dot_general", planted_weight_gradient)
    if first is None:       # the router's own choice: some eighty pairs
        layer, p = share_of(whole_layer(3)[1], 0, 4)
        x = jax.random.normal(jax.random.key(11), (2, 24, D), F32)
    else:                   # ten pairs in a stretch of fifteen rows
        layer, p, x = steered_share((2, 7), (2, 3, 4, 5, 6), first, True)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(
            lambda p, x: jnp.sum(layer.apply(p, x) ** 2), (0, 1))(p, x)
        monkeypatch.undo()
        want, ref = jax.value_and_grad(
            lambda p, x: jnp.sum(reference_layer(p, x, layer.first) ** 2),
            (0, 1))(p, x)
    assert float(want) > 1 and float(got) == pytest.approx(float(want),
                                                           rel=1e-4)
    for k, g in by_path(ref).items():
        np.testing.assert_allclose(by_path(grads)[k], g, rtol=2e-3,
                                   atol=1e-5, err_msg=str(k))
