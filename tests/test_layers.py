"""Layer library unit tests vs NumPy oracles (reference layers2.py parity:
Conv/Pool/LRN/FC/Dropout/Softmax/BatchNorm — SURVEY.md §2.7)."""

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models import layers as L

KEY = jax.random.key(0)
F32 = jnp.float32


def test_conv_shapes_and_groups():
    x = jnp.ones((2, 16, 16, 4))
    conv = L.Conv(4, 8, 3, padding="SAME", compute_dtype=F32, name="c")
    p = conv.init(KEY)
    assert p["w"].shape == (3, 3, 4, 8)
    y = conv.apply(p, x)
    assert y.shape == (2, 16, 16, 8)
    # AlexNet-style 2-group conv halves the per-group input channels
    gconv = L.Conv(4, 8, 3, groups=2, compute_dtype=F32, name="g")
    gp = gconv.init(KEY)
    assert gp["w"].shape == (3, 3, 2, 8)
    assert gconv.apply(gp, x).shape == (2, 16, 16, 8)


def test_conv_stride_valid():
    x = jnp.ones((1, 227, 227, 3))
    conv = L.Conv(3, 96, 11, stride=4, padding="VALID", compute_dtype=F32)
    y = conv.apply(conv.init(KEY), x)
    assert y.shape == (1, 55, 55, 96)   # AlexNet conv1 geometry


def test_conv_grad_works_in_bf16():
    """The default mixed-precision path must be differentiable (regression:
    conv transpose with preferred_element_type broke in jax 0.9)."""
    conv = L.Conv(3, 4, 3, compute_dtype=jnp.bfloat16, name="c")
    p = conv.init(KEY)
    x = jnp.ones((2, 8, 8, 3))

    def loss(p):
        return conv.apply(p, x).astype(jnp.float32).sum()

    g = jax.grad(loss)(p)
    assert g["w"].shape == p["w"].shape
    assert bool(jnp.isfinite(g["w"]).all())


def test_pool_max_oracle():
    x = jnp.asarray(np.arange(16, dtype=np.float32).reshape(1, 4, 4, 1))
    pool = L.Pool(2, 2, mode="max")
    y = np.asarray(pool.apply(None, x))[0, :, :, 0]
    np.testing.assert_array_equal(y, [[5, 7], [13, 15]])


def test_pool_avg_oracle():
    x = jnp.ones((1, 4, 4, 1))
    pool = L.Pool(2, 2, mode="avg")
    np.testing.assert_allclose(np.asarray(pool.apply(None, x)), 1.0)


def test_overlapping_pool():
    # the reference zoo's 3x3/stride-2 overlapping pooling
    x = jnp.ones((1, 15, 15, 2))
    pool = L.Pool(3, 2, mode="max")
    assert pool.apply(None, x).shape == (1, 7, 7, 2)


def test_lrn_oracle():
    rng = np.random.RandomState(0)
    x = rng.rand(2, 3, 3, 7).astype(np.float32)
    lrn = L.LRN(n=5, k=2.0, alpha=1e-4, beta=0.75)
    got = np.asarray(lrn.apply(None, jnp.asarray(x)))
    # numpy oracle: cross-channel windowed sum of squares
    sq = x ** 2
    pad = np.pad(sq, [(0, 0)] * 3 + [(2, 2)])
    ssum = sum(pad[..., i:i + 7] for i in range(5))
    expect = x / (2.0 + (1e-4 / 5) * ssum) ** 0.75
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def test_dropout_train_vs_eval():
    d = L.Dropout(0.5)
    x = jnp.ones((4, 100))
    # eval: identity
    np.testing.assert_array_equal(np.asarray(d.apply(None, x)), 1.0)
    # train: ~half dropped, survivors scaled 2x
    y = np.asarray(d.apply(None, x, train=True, rng=jax.random.key(1)))
    assert set(np.unique(y)) <= {0.0, 2.0}
    assert 0.3 < (y == 0).mean() < 0.7


def test_batchnorm_train_and_running_stats():
    bn = L.BatchNorm(4, momentum=0.5)
    p, s = bn.init(KEY), bn.init_state()
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(32, 5, 5, 4).astype(np.float32) * 3 + 1)
    y, s2 = bn.apply(p, x, train=True, state=s)
    ym = np.asarray(y).mean(axis=(0, 1, 2))
    ys = np.asarray(y).std(axis=(0, 1, 2))
    np.testing.assert_allclose(ym, 0.0, atol=1e-4)
    np.testing.assert_allclose(ys, 1.0, atol=1e-2)
    # running stats pulled halfway (momentum 0.5) toward batch stats
    assert (np.asarray(s2["mean"]) != 0).all()
    # eval mode uses running stats and returns no update
    y_eval, s3 = bn.apply(p, x, train=False, state=s2)
    assert s3 is None


def test_sequential_threads_bn_state():
    seq = L.Sequential([
        L.FC(8, 8, compute_dtype=F32, name="fc"),
        L.BatchNorm(8, name="bn"),
    ])
    p = seq.init(KEY)
    s = seq.init_state()
    x = jnp.ones((4, 8))
    y, s2 = seq.apply(p, x, train=True, state=s)
    assert y.shape == (4, 8)
    assert "bn" in s2 and (np.asarray(s2["bn"]["mean"]) !=
                           np.asarray(s["bn"]["mean"])).any()


def test_sequential_unique_names():
    seq = L.Sequential([L.Pool(2, name="p"), L.Pool(2, name="p")])
    assert seq._keys == ["p", "p_1"]


def test_softmax_cross_entropy_oracle():
    logits = jnp.asarray([[2.0, 0.0, -2.0], [0.0, 0.0, 0.0]])
    labels = jnp.asarray([0, 2])
    got = float(L.softmax_cross_entropy(logits, labels))
    p0 = np.exp(2) / (np.exp(2) + 1 + np.exp(-2))
    expect = (-np.log(p0) - np.log(1 / 3)) / 2
    np.testing.assert_allclose(got, expect, rtol=1e-6)


def test_errors_topk():
    logits = jnp.asarray([[5., 4., 3., 2., 1., 0.]] * 2)
    labels = jnp.asarray([0, 5])
    assert float(L.errors(logits, labels)) == 0.5
    assert float(L.errors_top_x(logits, labels, 5)) == 0.5
    assert float(L.errors_top_x(logits, labels, 6)) == 0.0


def test_init_schemes():
    # one fixed key on purpose: scheme shapes/scales are under test,
    # not stream independence (suppressions below)
    k = jax.random.key(2)
    w = L.init_weight(k, (1000,), ("normal", 0.01))
    assert 0.005 < float(jnp.std(w)) < 0.015
    c = L.init_weight(k, (10,), ("constant", 0.1))  # tpulint: disable=rng-discipline
    np.testing.assert_allclose(np.asarray(c), 0.1)
    he = L.init_weight(k, (100, 100), "he")  # tpulint: disable=rng-discipline
    assert 0.1 < float(jnp.std(he)) < 0.2    # sqrt(2/100) ≈ 0.141
    with pytest.raises(ValueError):
        L.init_weight(k, (3,), "bogus")  # tpulint: disable=rng-discipline


def test_batchnorm_bf16_norm_dtype_matches_fp32_path():
    """norm_dtype=bfloat16 (the perf lever) must keep stats fp32-exact and
    normalize within bf16 rounding of the fp32-exact path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from theanompi_tpu.models import layers as L

    r = np.random.RandomState(3)
    x = jnp.asarray(r.randn(4, 5, 5, 8).astype(np.float32) * 2 + 1,
                    dtype=jnp.bfloat16)
    bn32 = L.BatchNorm(8)
    bnbf = L.BatchNorm(8, norm_dtype=jnp.bfloat16)
    params = bn32.init(jax.random.key(0))
    params["scale"] = jnp.asarray(r.rand(8).astype(np.float32) + 0.5)
    params["bias"] = jnp.asarray(r.randn(8).astype(np.float32))
    state = bn32.init_state()

    y32, st32 = bn32.apply(params, x, train=True, state=state)
    ybf, stbf = bnbf.apply(params, x, train=True, state=state)
    # running stats are computed identically in fp32
    for k in st32:
        np.testing.assert_array_equal(np.asarray(st32[k]),
                                      np.asarray(stbf[k]))
    np.testing.assert_allclose(np.asarray(ybf, np.float32),
                               np.asarray(y32, np.float32),
                               rtol=0.05, atol=0.05)
    assert ybf.dtype == jnp.bfloat16

    # eval path too
    ye32, _ = bn32.apply(params, x, train=False, state=st32)
    yebf, _ = bnbf.apply(params, x, train=False, state=stbf)
    np.testing.assert_allclose(np.asarray(yebf, np.float32),
                               np.asarray(ye32, np.float32),
                               rtol=0.05, atol=0.05)


# -- Sequential: a max Pool directly after a ReLU layer runs before the ReLU --

def plain_apply(seq, params, x, *, train=False, rng=None):
    """``Sequential.apply`` in the layer list's own order (stateless layers)."""
    for k, layer in zip(seq._keys, seq.layers):
        sub = None
        if rng is not None:
            rng, sub = jax.random.split(rng)
        x = layer.apply(params.get(k), x, train=train, rng=sub)
    return x


def pool_operand_makers(jaxpr):
    """For every ``reduce_window_max`` of a jaxpr, calls inlined: the name of
    the primitive that made its operand (None: an input of the jaxpr)."""
    found = []
    key = lambda v: v if isinstance(v, jax.extend.core.Var) else None

    def walk(jaxpr, made):            # made: Var -> primitive that made it
        for eqn in jaxpr.eqns:
            subs = [getattr(v, "jaxpr", v) for v in eqn.params.values()
                    if hasattr(getattr(v, "jaxpr", v), "eqns")]
            if len(subs) == 1 and len(subs[0].invars) == len(eqn.invars):
                sub = subs[0]                   # pjit, custom_jvp_call, ...
                inner = walk(sub, {i: made.get(key(o))
                                   for i, o in zip(sub.invars, eqn.invars)})
                made.update({o: inner.get(key(s))
                             for o, s in zip(eqn.outvars, sub.outvars)})
                continue
            if eqn.primitive.name == "reduce_window_max":
                found.append(made.get(key(eqn.invars[0])))
            made.update({o: eqn.primitive.name for o in eqn.outvars})
        return made

    walk(jaxpr, {})
    return found


def pool_before_relu_count():
    from theanompi_tpu.utils import telemetry
    return telemetry.totals().get("pool_before_relu", (0, 0))[0]


def _tie_rich(shape, dtype, seed=0):
    """Half-integers in [-2, 2]: ties and exact zeros in most windows, and a
    corner where every window is all-negative."""
    r = np.random.RandomState(seed)
    x = np.round(r.randn(*shape) * 2) / 2
    x = np.clip(x, -2, 2)
    x[:, :5, :5, :] = -np.abs(x[:, :5, :5, :]) - 0.5
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("size,stride,padding", [
    (2, 2, "VALID"), (3, 2, "VALID"), (3, 2, "SAME"), (3, 1, "SAME")])
def test_pool_before_relu_is_relu_before_pool_bit_for_bit(
        size, stride, padding, dtype):
    """An identity 1x1 convolution hands the pool the tie-rich input itself
    as its pre-activation; forward and every gradient equal under ``==``."""
    c = 3
    seq = L.Sequential([
        L.Conv(c, c, 1, compute_dtype=dtype, name="conv"),
        L.Pool(size, stride, mode="max", padding=padding, name="pool")])
    assert seq._pool_first == {0}
    params = {"conv": {"w": jnp.eye(c, dtype=F32).reshape(1, 1, c, c),
                       "b": jnp.zeros((c,), F32)}}
    x = _tie_rich((2, 11, 11, c), dtype)
    np.testing.assert_array_equal(
        np.asarray(seq.layers[0].pre_activation(params["conv"], x), F32),
        np.asarray(x, F32))
    y_new = seq.apply(params, x)[0]
    y_old = plain_apply(seq, params, x)
    assert y_new.dtype == y_old.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y_new, F32),
                                  np.asarray(y_old, F32))
    assert (np.asarray(y_old, F32) == 0).any()       # all-negative windows

    w = jnp.asarray(np.random.RandomState(1).randn(*y_old.shape), dtype)
    new = jax.grad(lambda p, x: jnp.sum(
        (seq.apply(p, x)[0] * w).astype(F32)), (0, 1))(params, x)
    old = jax.grad(lambda p, x: jnp.sum(
        (plain_apply(seq, p, x) * w).astype(F32)), (0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, F32), np.asarray(b, F32))
    assert (np.asarray(new[1], F32) != 0).any()


def _conv(activation="relu"):
    return L.Conv(2, 4, 3, activation=activation, compute_dtype=F32,
                  name="conv")


@pytest.mark.parametrize("front,expect,pool_reads", [
    pytest.param([_conv(), L.Pool(3, 2, name="pool")], 1, "add",
                 id="relu_conv_max_pool"),
    pytest.param([_conv(), L.Pool(3, 2, mode="avg", name="pool")], 0, None,
                 id="avg_pool"),
    pytest.param([_conv("tanh"), L.Pool(3, 2, name="pool")], 0, "tanh",
                 id="tanh_conv"),
    pytest.param([_conv(), L.LRN(name="lrn"), L.Pool(3, 2, name="pool")],
                 0, "mul", id="lrn_between"),
    pytest.param([_conv(None), L.Activation("relu"),
                  L.Pool(3, 2, name="pool")], 0, "max",
                 id="relu_as_its_own_layer"),
])
def test_sequential_reorders_only_relu_then_max_pool(front, expect,
                                                     pool_reads):
    """The reorder is read off the layer list, counted once per pair where
    the stack is built, and changes neither the parameter tree, nor the rng
    each layer is handed (the dropout draw), nor any output."""
    before = pool_before_relu_count()
    seq = L.Sequential(front + [
        L.Flatten(), L.Dropout(0.5, name="drop"),
        L.FC(5 * 5 * 4, 5, activation=None, compute_dtype=F32, name="fc")])
    assert len(seq._pool_first) == expect
    assert pool_before_relu_count() - before == expect

    params = seq.init(KEY)
    assert {k: sorted(v) for k, v in params.items()} == {
        "conv": ["b", "w"], "fc": ["b", "w"]}
    x = _tie_rich((2, 11, 11, 2), F32, seed=2)
    rng = jax.random.key(7)
    for train in (False, True):
        got = seq.apply(params, x, train=train, rng=rng)[0]
        want = plain_apply(seq, params, x, train=train, rng=rng)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # what the max pool (and so its backward) takes as operand: the bias
    # add where the pair was reordered, the ReLU's `max` where a ReLU layer
    # of its own precedes it
    fed_by = pool_operand_makers(jax.make_jaxpr(
        lambda p, x: seq.apply(p, x)[0])(params, x).jaxpr)
    assert fed_by == ([pool_reads] if pool_reads else [])


# -- FC's weight gradient from gathered operands (PERF.md §6, PR 32) ---------

@pytest.mark.parametrize("cd,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
def test_dot_gathered_sums_the_weight_gradient_over_workers(cd, tol):
    """On a 4-way mesh the product's forward and ``dx`` are the local
    ones, ``dw`` is the full-batch ``x^T . dy`` in float32, the same bits
    on every worker."""
    from jax.sharding import PartitionSpec as P
    from theanompi_tpu.jax_compat import shard_map, vary
    from theanompi_tpu.parallel.mesh import worker_mesh
    n, rows, n_in, n_out = 4, 6, 16, 8
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(n * rows, n_in), jnp.float32)
    w = jnp.asarray(r.randn(n_in, n_out), jnp.float32)
    dy = jnp.asarray(r.randn(n * rows, n_out), jnp.float32)

    def local(x, w, dy):
        w = vary(w, "workers")
        y, vjp = jax.vjp(
            lambda x, w: L._dot_gathered(x.astype(cd), w, "workers"), x, w)
        dx, dw = vjp(dy.astype(cd))
        return y, dx, dw[None]

    y, dx, dw = jax.jit(shard_map(
        local, mesh=worker_mesh(n), in_specs=(P("workers"), P(), P("workers")),
        out_specs=(P("workers"), P("workers"), P("workers"))))(x, w, dy)
    assert dw.dtype == jnp.float32 and y.dtype == cd
    scale = lambda a: tol * float(np.max(np.abs(a)))
    np.testing.assert_allclose(np.asarray(y, np.float32), x @ w,
                               atol=scale(x @ w))
    np.testing.assert_allclose(np.asarray(dx, np.float32), dy @ w.T,
                               atol=scale(dy @ w.T))
    np.testing.assert_allclose(dw[0], x.T @ dy, atol=scale(x.T @ dy))
    for k in range(1, n):
        np.testing.assert_array_equal(dw[k], dw[0])


# -- the looped model's layer kinds, each against a three-line form ------------

def test_rmsnorm_is_x_over_root_mean_square_times_scale():
    x = jax.random.normal(KEY, (2, 5, 16)) * 3.0
    norm = L.RMSNorm(16, eps=1e-6, name="n")
    p = {"scale": jnp.linspace(0.5, 2.0, 16)}
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * p["scale"]
    np.testing.assert_allclose(norm.apply(p, x), want, rtol=1e-6)
    assert jax.tree.map(jnp.shape, norm.init(KEY)) == {"scale": (16,)}
    # statistics in float32 whatever comes in; the result in the input's type
    half = norm.apply(p, x.astype(jnp.bfloat16))
    assert half.dtype == jnp.bfloat16
    np.testing.assert_allclose(half.astype(F32), want, rtol=2e-2)


def test_rotary_turns_half_split_pairs_by_position():
    x = jax.random.normal(KEY, (2, 3, 6, 8))            # [B, H, T, hd]
    ang = jnp.arange(6.0)[:, None] * 100.0 ** (-jnp.arange(0, 8, 2) / 8)
    a, b = x[..., :4], x[..., 4:]
    want = jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)
    got = L.rotary(x, theta=100.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # a turn: lengths stay, position 0 stays, and q.k reads the distance
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_array_equal(got[:, :, 0], x[:, :, 0])
    same = jnp.broadcast_to(x[:, :, :1], x.shape)
    r = L.rotary(same, theta=100.0)
    np.testing.assert_allclose(jnp.sum(r[:, :, 1] * r[:, :, 3], -1),
                               jnp.sum(r[:, :, 2] * r[:, :, 4], -1),
                               rtol=1e-4)


def test_gated_mlp_is_down_of_silu_gate_times_up():
    mlp = L.GatedMLP(8, 24, compute_dtype=F32, name="m")
    p = mlp.init(KEY)
    assert jax.tree.map(jnp.shape, p) == {"wg": (8, 24), "wu": (8, 24),
                                          "wd": (24, 8)}
    x = jax.random.normal(KEY, (2, 5, 8))
    g = x @ p["wg"]
    want = (g * jax.nn.sigmoid(g) * (x @ p["wu"])) @ p["wd"]
    np.testing.assert_allclose(mlp.apply(p, x), want, rtol=1e-5, atol=1e-8)


def test_rotary_attention_is_attention_of_the_turned_q_and_k():
    attn = L.RotaryAttention(16, 2, theta=50.0, compute_dtype=F32, name="a")
    p = attn.init(KEY)
    x = jax.random.normal(KEY, (2, 6, 16))
    heads = lambda w: (x @ w).reshape(2, 6, 2, 8).transpose(0, 2, 1, 3)  # noqa: E731,E501
    q, k, v = L.rotary(heads(p["wq"]), 50.0), L.rotary(heads(p["wk"]), 50.0), \
        heads(p["wv"])
    s = jnp.where(jnp.tril(jnp.ones((6, 6), bool)),
                  q @ k.transpose(0, 1, 3, 2) / np.sqrt(8), -jnp.inf)
    want = (jax.nn.softmax(s, -1) @ v).transpose(0, 2, 1, 3).reshape(
        2, 6, 16) @ p["wo"]
    np.testing.assert_allclose(attn.apply(p, x), want, rtol=1e-4, atol=1e-6)
    # the scopes the benchmark's readers join on
    text = jax.jit(attn.apply).lower(p, x).as_text(debug_info=True)
    assert "a/attn_core" in text


# -- the Pallas kernel behind attn_impl='flash', and the shim to it ------------

def test_the_splash_shim_marks_the_steps_axis_alone_and_fails_by_name(
        monkeypatch):
    """The library module's out_shapes vary over the axis the caller names,
    where it is manual, and over no other; a library that no longer builds
    them through its global ``jax`` is refused when the shim is put in."""
    from jax.experimental.pallas.ops.tpu.splash_attention import \
        splash_attention_kernel as sk
    from jax.sharding import Mesh, PartitionSpec as P
    from theanompi_tpu import jax_compat
    view = jax_compat._VaryingOutShapes()
    view.axes.add("workers")
    assert view.numpy is jax.numpy                  # jax, for the rest
    assert not view.ShapeDtypeStruct((2,), F32).vma  # no manual axis here
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("workers", "model"))
    seen = []

    def local(x):
        seen.append(view.ShapeDtypeStruct((2,), F32).vma)
        return x

    jax.jit(jax_compat.shard_map(local, mesh=mesh, in_specs=P("workers",
                                                             "model"),
                                 out_specs=P("workers", "model"))
            ).lower(jnp.zeros((2, 2)))
    assert seen == [frozenset({"workers"})]
    monkeypatch.setattr(sk, "jax", object())        # a jax of another build
    q = jnp.zeros((1, 1, 128, 128), jnp.bfloat16)
    with pytest.raises(AssertionError, match="out_shapes"):
        jax_compat.splash_attention(q, q, q, axis_name="workers",
                                    mask="causal", **L.flash_tiles(128))


@pytest.mark.parametrize("t, causal", [(256, True), (256, False),
                                       (512, True), (512, False)])
def test_the_flash_branch_is_the_references_attention_and_gradients(
        t, causal):
    """``attn_impl='flash'`` in Pallas interpret mode, heads of 128 in
    bfloat16, against ``attention_reference`` in float32 on the same
    operands: the output and the gradients of q, k and v to bfloat16's
    rounding (0.2-0.7% of the largest entry, as on the chip: PERF.md
    section 6, PR 35)."""
    import jax.experimental.pallas.tpu as pltpu
    from theanompi_tpu.ops.ring_attention import attention_reference
    attn = L.MultiHeadAttention(256, 2, causal=causal, attn_impl="flash")
    ks = jax.random.split(jax.random.key(t + causal), 4)
    q, k, v = (jax.random.normal(kk, (2, 2, t, 128), F32).astype(
        jnp.bfloat16) for kk in ks[:3])
    w = jax.random.normal(ks[3], (2, 2, t, 128), F32)

    def flash(q, k, v):
        o = attn._attend(q, k, v)
        return jnp.sum(o.astype(F32) * w), o

    def plain(q, k, v):
        o = attention_reference(q.astype(F32), k.astype(F32), v.astype(F32),
                                causal=causal)
        return jnp.sum(o * w), o

    with pltpu.force_tpu_interpret_mode():
        (_, o), got = jax.value_and_grad(flash, (0, 1, 2), has_aux=True)(
            q, k, v)
    (_, o_ref), want = jax.value_and_grad(plain, (0, 1, 2), has_aux=True)(
        q, k, v)
    assert o.dtype == jnp.bfloat16 and o.shape == q.shape
    for name, a, b in zip("o q k v".split(), (o,) + got, (o_ref,) + want):
        b = b.astype(F32)
        gap = float(jnp.max(jnp.abs(a.astype(F32) - b)) / jnp.max(jnp.abs(b)))
        assert gap < 0.01, (name, gap)


@pytest.mark.parametrize("t, want", [
    (128, (128, 128, 128)), (4096, (512, 1024, 512)), (1536, (512, 768, 384)),
    (96, None), (4097, None)])
def test_flash_tiles_follow_the_sequence_length(t, want):
    """The sizes the zoo uses get the tiles chosen on the chip, cut to the
    sequence; one off 128 is refused by name."""
    if want is None:
        with pytest.raises(ValueError, match="multiple of 128"):
            L.flash_tiles(t)
        return
    tiles = L.flash_tiles(t)
    assert (tiles["block_q"], tiles["block_kv"],
            tiles["block_kv_compute"]) == want
    assert tiles["block_q_dkv"] == tiles["block_kv_dkv"] \
        == tiles["block_kv_dkv_compute"] == want[1]
    assert tiles["use_fused_bwd_kernel"] is True
    for name, b in tiles.items():
        assert name == "use_fused_bwd_kernel" or (t % b == 0 and b % 128 == 0)


@pytest.mark.parametrize("attn_impl, want", [("flash", 16), ("reference", 0)])
def test_the_looped_step_counts_the_attention_cores_on_the_kernel_path(
        attn_impl, want):
    """Four layers four times over: sixteen cores a step, all of them on
    the kernel path under ``flash`` and none under ``reference``.  Traced,
    not lowered: the kernel itself is the chip's."""
    from theanompi_tpu.models.looped_lm import LoopedLM
    from theanompi_tpu.utils import telemetry
    m = LoopedLM(dict(vocab=128, d_model=256, n_head=2, n_layer=4, d_ff=64,
                      seq_len=128, loop_steps=4, attn_impl=attn_impl,
                      n_workers=1, seed=3, batch_size=2, synthetic_train=8,
                      synthetic_val=4, verbose=False))
    names = ("model.attn_kernel_applications", "model.layer_applications")
    before = [telemetry.totals().get(k, (0, 0))[0] for k in names]
    batch = {"x": jnp.zeros((2, 128), jnp.int32),
             "y": jnp.zeros((2, 128), jnp.int32)}
    for _ in range(2):          # counted once however often it is traced
        jax.eval_shape(lambda p: m.loss_and_metrics(p, {}, batch, None, True),
                       m.params)
    after = [telemetry.totals()[k][0] for k in names]
    assert [a - b for a, b in zip(after, before)] == [want, 16]


@pytest.mark.parametrize("t, window, fwd, bwd", [
    (8192, 512, (512, 512, 512), (512, 1024, 1024)),
    (8192, 256, (512, 256, 256), (512, 1024, 1024)),
    (8192, 4096, (512, 1024, 512), (512, 1024, 1024)),
    (256, 64, (256, 128, 128), (256, 256, 256))])
def test_flash_tiles_under_a_window(t, window, fwd, bwd):
    """The forward's k/v tile is no longer than the window; without one
    the tiles are what they were."""
    tiles = L.flash_tiles(t, window)
    assert (tiles["block_q"], tiles["block_kv"],
            tiles["block_kv_compute"]) == fwd
    assert (tiles["block_q_dkv"], tiles["block_kv_dkv"],
            tiles["block_kv_dkv_compute"]) == bwd
    assert L.flash_tiles(t, None) == L.flash_tiles(t)
    assert L.flash_tiles(8192)["block_q_dkv"] == 1024
