"""Mixture-of-Experts / expert parallelism (parallel/moe.py).

Oracles are analytic (uniform-router aux = 1, tie-break routing to expert 0
scaled by the 1/E gate) or our own dense/ep=1 runs — the reference
(Theano-MPI) has no sparse models.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.models import layers as L
from theanompi_tpu.models.transformer_lm import MoETransformerLM
from theanompi_tpu.parallel.exchanger import BSP_Exchanger
from theanompi_tpu.parallel.mesh import MODEL_AXIS, worker_mesh
from theanompi_tpu.parallel.moe import MoE
from theanompi_tpu.jax_compat import shard_map

CFG = dict(verbose=False, batch_size=8, seq_len=16, vocab=32,
           synthetic_train=64, synthetic_val=32,
           d_model=32, n_head=4, n_layer=2, moe_experts=4, moe_every=2,
           compute_dtype=jnp.float32)


def _make(dp, tp, **kw):
    mesh = worker_mesh(dp, tp=tp)
    cfg = {**CFG, "mesh": mesh, "size": dp, "rank": 0, "tp": tp, **kw}
    return MoETransformerLM(cfg)


def _train_steps(model, n_steps):
    exch = BSP_Exchanger(model.config)
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    costs = []
    for i in range(n_steps):
        model.train_iter(i, None)
        costs.append(float(model.current_info["cost"]))
    return costs


def test_moe_uniform_router_matches_scaled_dense():
    """wg = 0 → uniform probs, argmax ties to expert 0, gate = 1/E: the MoE
    output must equal (1/E)·MLP_expert0(x) and aux must be exactly 1."""
    r = np.random.RandomState(0)
    d, E = 16, 4
    moe = MoE(d, E, mlp_ratio=2, ep=1, capacity_factor=float(E),
              compute_dtype=jnp.float32)
    params = moe.init(jax.random.key(0))
    params = dict(params, wg=jnp.zeros_like(params["wg"]))
    x = jnp.asarray(r.randn(12, d).astype(np.float32))
    y, aux = moe.apply(params, x)
    w1, b1 = params["w1"][0], params["b1"][0]
    w2, b2 = params["w2"][0], params["b2"][0]
    dense = jnp.dot(jax.nn.relu(jnp.dot(x, w1) + b1), w2) + b2
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense) / E,
                               rtol=1e-5, atol=1e-6)
    assert float(aux) == pytest.approx(1.0, abs=1e-6)


def test_moe_capacity_drops_overflow_tokens():
    """TRAINING: all tokens routed to expert 0 with capacity < N — rows
    past capacity come out ZERO (they ride the block's residual instead).
    INFERENCE is drop-free (capacity = N): every row gets its expert."""
    r = np.random.RandomState(1)
    d, E, n = 8, 2, 10
    moe = MoE(d, E, mlp_ratio=1, ep=1, capacity_factor=0.4,  # C = 2
              compute_dtype=jnp.float32)
    params = moe.init(jax.random.key(0))
    wg = np.zeros((d, E), np.float32)
    x = jnp.asarray(np.abs(r.randn(n, d)).astype(np.float32))  # positive
    wg[:, 0] = 1.0                                             # favor e0
    params = dict(params, wg=jnp.asarray(wg))
    y, _ = moe.apply(params, x, train=True)
    C = moe.capacity(n, train=True)
    assert C == 2
    np.testing.assert_array_equal(np.asarray(y[C:]), 0.0)
    assert np.abs(np.asarray(y[:C])).sum() > 0
    y_inf, _ = moe.apply(params, x, train=False)
    assert (np.abs(np.asarray(y_inf)).sum(axis=1) > 0).all()  # no zero rows


@pytest.mark.slow
def test_moe_ep4_matches_ep1(mesh8):
    """Expert-parallel ep=4 training must trace the dense-layout ep=1 loss
    curve (same seed/data): routing is replicated, only the expert placement
    and psum order differ."""
    m1 = _make(dp=2, tp=1)
    m4 = _make(dp=2, tp=4)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), m1.params, m4.params)
    c1 = _train_steps(m1, 5)
    c4 = _train_steps(m4, 5)
    np.testing.assert_allclose(c4, c1, rtol=2e-4, atol=2e-5)


def test_moe_converges_and_validates(mesh8):
    model = _make(dp=4, tp=2)
    costs = _train_steps(model, 8)
    assert np.isfinite(costs).all()
    assert costs[-1] < costs[0]          # learnable synthetic stream
    model.begin_val()
    model.val_iter(0, None)
    model.end_val()


@pytest.mark.slow
def test_moe_pp_matches_dense_layout(mesh8):
    """A homogeneous all-MoE stack (moe_every=1) pipelines over 'pipe': same
    init (stacked from the same keys) and — with drop-free capacity and the
    aux term off — the same loss curve as the pp=1 layout.  (With binding
    capacity the layouts legitimately differ: GPipe routes per MICROBATCH,
    so the capacity cutoff and the nonlinear aux statistic see B/M-row
    token sets — inherent pipeline-MoE semantics, not an implementation
    gap.)"""
    def make(pp):
        mesh = worker_mesh(2, pp=pp)
        cfg = {**CFG, "mesh": mesh, "size": 2, "rank": 0, "tp": 1, "pp": pp,
               "moe_every": 1, "n_layer": 4, "pp_microbatches": 4,
               "capacity_factor": 4.0, "moe_aux": 0.0}
        return MoETransformerLM(cfg)

    m1, m4 = make(1), make(4)
    stacked = m4.params["blocks"]
    for i, blk in enumerate(m1.blocks):
        jax.tree.map(lambda s, d: np.testing.assert_array_equal(
            np.asarray(s[i]), np.asarray(d)),
            stacked, m1.params[blk.name])
    c1 = _train_steps(m1, 5)
    c4 = _train_steps(m4, 5)
    np.testing.assert_allclose(c4, c1, rtol=2e-4, atol=2e-5)


def test_moe_pp_with_aux_converges(mesh8):
    """Default capacity/aux on the pipelined MoE stack: the aux rides the
    pipeline (bubble ticks masked) and training converges."""
    mesh = worker_mesh(2, pp=4)
    cfg = {**CFG, "mesh": mesh, "size": 2, "rank": 0, "tp": 1, "pp": 4,
           "moe_every": 1, "n_layer": 4, "pp_microbatches": 4}
    m = MoETransformerLM(cfg)
    costs = _train_steps(m, 8)
    assert np.isfinite(costs).all()
    assert np.mean(costs[-3:]) < np.mean(costs[:3])


def test_moe_mixed_stack_rejects_pp(mesh8):
    mesh = worker_mesh(2, pp=4)
    cfg = {**CFG, "mesh": mesh, "size": 2, "rank": 0, "tp": 1, "pp": 4,
           "moe_every": 2, "n_layer": 4}
    with pytest.raises(AssertionError, match="homogeneous"):
        MoETransformerLM(cfg)


def test_moe_checkpoint_roundtrip(tmp_path, mesh8):
    from theanompi_tpu.parallel import steps
    model = _make(dp=2, tp=4)
    _train_steps(model, 3)
    model.save(str(tmp_path), epoch=0, count=3)
    before = jax.device_get(steps.tree_to_host(model.step_state["params"]))
    model2 = _make(dp=2, tp=4)
    model2.compile_iter_fns(BSP_Exchanger(model2.config))
    assert model2.load(str(tmp_path)) == 0
    after = jax.device_get(steps.tree_to_host(model2.step_state["params"]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), before, after)


# -- round 4: sequence-sharded MoE (all-to-all dispatch) ---------------------

def _make_sp(dp, sp, tp=1, **kw):
    mesh = worker_mesh(dp, tp=tp, sp=sp)
    cfg = {**CFG, "mesh": mesh, "size": dp, "rank": 0, "tp": tp, "sp": sp,
           **kw}
    return MoETransformerLM(cfg)


def test_moe_sp_a2a_layer_exact_vs_dense(mesh8):
    """The all-to-all dispatch itself is EXACT: identical inputs route
    identically, travel to their seq-sharded expert and back, and
    reproduce the dense layer's output and aux to float noise."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    S, B, T, D, E = 4, 16, 16, 32, 4
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, S),
                ("workers", "seq"))
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(B, T, D).astype(np.float32))
    from theanompi_tpu.parallel.moe import MoE
    dense = MoE(D, E, ep=1, capacity_factor=100.0,
                compute_dtype=jnp.float32)
    params = dense.init(jax.random.key(1))
    y_d, _ = dense.apply(params, x, train=True)
    sp = MoE(D, E, ep=1, seq_shards=S, seq_axis="seq",
             capacity_factor=100.0, compute_dtype=jnp.float32)
    pspec = sp.specs()

    def body(p, xb):
        y, _aux = sp.apply(p, xb, train=True)
        return y

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(pspec, P("workers", "seq", None)),
        out_specs=P("workers", "seq", None)))
    pp = {k: jax.device_put(params[k], NamedSharding(mesh, pspec[k]))
          for k in params}
    y_s = f(pp, jax.device_put(
        x, NamedSharding(mesh, P("workers", "seq", None))))
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_s),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.slow
def test_moe_sp_model_close_to_dense_dropfree(mesh8):
    """Model-level: ring-vs-dense attention reorders fp32 sums by ~1e-6,
    and the ARGMAX router amplifies borderline flips into different expert
    assignments — so tight parity is ill-posed at the model level (the
    layer is exact above).  The loss curves must still agree loosely."""
    dense = _make(dp=2, tp=1, capacity_factor=100.0)
    sp = _make_sp(dp=2, sp=4, capacity_factor=100.0)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), dense.params, sp.params)
    c_dense = _train_steps(dense, 4)
    c_sp = _train_steps(sp, 4)
    np.testing.assert_allclose(c_sp, c_dense, rtol=2e-2)
    # expert tables really shard over 'seq'
    from theanompi_tpu.parallel.mesh import SEQ_AXIS, WORKER_AXIS
    w1 = sp.step_state["params"]["block1"]["moe"]["w1"]
    assert w1.sharding.spec == (WORKER_AXIS, SEQ_AXIS), w1.sharding.spec


def test_moe_sp_trains_with_default_capacity(mesh8):
    """Default capacity (tokens drop per source shard): trains finite and
    the loss decreases; Σ capacity budget matches the replicated path."""
    m = _make_sp(dp=2, sp=4)
    costs = _train_steps(m, 6)
    assert np.isfinite(costs).all()
    assert np.mean(costs[-3:]) < np.mean(costs[:3])
    m.begin_val()
    m.val_iter(0)
    m.end_val()


def test_moe_sp_tp_3d_smoke(mesh8):
    """sp×tp MoE: experts on 'model', tokens on 'seq' — one full train+val
    step on the 3-D mesh."""
    m = _make_sp(dp=2, sp=2, tp=2, moe_every=1)
    costs = _train_steps(m, 2)
    assert np.isfinite(costs).all()
    m.begin_val()
    m.val_iter(0)
    m.end_val()


@pytest.mark.slow
def test_moe_sp_uses_global_positions(mesh8):
    """Regression (round-4 review): MoE's _forward must offset position ids
    by the seq rank, like the base model.  With an amplified position table
    the local-positions bug would blow the costs apart; with global
    positions the sp model tracks the dense one."""
    dense = _make(dp=2, tp=1, capacity_factor=100.0)
    sp = _make_sp(dp=2, sp=4, capacity_factor=100.0)
    # make position embeddings LOUD and position-distinctive
    amp = np.outer(np.arange(CFG["seq_len"], dtype=np.float32) - 8.0,
                   np.ones(CFG["d_model"], np.float32))
    for m in (dense, sp):
        m.params = dict(m.params, pos={"w": jnp.asarray(amp)})
    c_d = _train_steps(dense, 1)[0]
    c_s = _train_steps(sp, 1)[0]
    assert abs(c_s - c_d) < 0.1 * abs(c_d), (c_d, c_s)


# -- round 4: top-k (GShard-style) routing -----------------------------------

def test_moe_top2_identical_experts_equals_dense():
    """With every expert's weights identical and drop-free capacity, the
    normalized top-2 gates sum to 1, so y = MLP(x) EXACTLY — whatever the
    router does."""
    r = np.random.RandomState(1)
    d, E = 16, 4
    moe = MoE(d, E, mlp_ratio=2, ep=1, top_k=2, capacity_factor=100.0,
              compute_dtype=jnp.float32)
    params = moe.init(jax.random.key(0))
    # copy expert 0 into every expert; router weights stay random
    for k in ("w1", "b1", "w2", "b2"):
        params[k] = jnp.broadcast_to(params[k][:1], params[k].shape)
    x = jnp.asarray(r.randn(24, d).astype(np.float32))
    y, aux = moe.apply(params, x, train=True)
    w1, b1 = params["w1"][0], params["b1"][0]
    w2, b2 = params["w2"][0], params["b2"][0]
    dense = jnp.dot(jax.nn.relu(jnp.dot(x, w1) + b1), w2) + b2
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)
    assert np.isfinite(float(aux))


def test_moe_top2_priority_capacity_drops_secondaries_first():
    """REAL rank contention (GShard priority ordering): group A routes
    (e0 primary, e1 secondary), group B the mirror.  With C=4 and 3+3
    tokens, every primary survives and each expert keeps exactly ONE
    secondary (the earliest), so precisely tokens 0 and 3 get their full
    top-2 output — with identical experts the per-token output SCALE
    reveals exactly which routes were kept.  Inverting rank priority or
    mis-accumulating the slot base changes the scales and fails."""
    d, E, n_g = 4, 2, 3
    moe = MoE(d, E, mlp_ratio=1, ep=1, top_k=2, capacity_factor=1.0,
              compute_dtype=jnp.float32)
    params = moe.init(jax.random.key(2))
    for k in ("w1", "b1", "w2", "b2"):     # identical experts: y = s·MLP(x)
        params[k] = jnp.broadcast_to(params[k][:1], params[k].shape)
    wg = np.zeros((d, E), np.float32)
    wg[0, 0] = 1.0
    wg[1, 1] = 1.0
    params = dict(params, wg=jnp.asarray(wg))
    a = np.array([2.0, 1.0, 0.0, 0.0], np.float32)   # prefers e0 then e1
    b = np.array([1.0, 2.0, 0.0, 0.0], np.float32)   # prefers e1 then e0
    x = jnp.asarray(np.stack([a, a, a, b, b, b]))    # rows 0-2 = A, 3-5 = B
    # capacity(6, train) = ceil(6*2/2 * 1.0) = 6 — too roomy; force C=4 via
    # eval-free capacity_factor choice: use cf = 4/6 exactly
    moe.capacity_factor = 4.0 / 6.0
    assert moe.capacity(6, True) == 4
    y, _ = moe.apply(params, x, train=True)
    w1, b1_, w2, b2_ = (params["w1"][0], params["b1"][0],
                        params["w2"][0], params["b2"][0])
    mlp = np.asarray(jnp.dot(jax.nn.relu(jnp.dot(x, w1) + b1_), w2) + b2_)
    scale = np.asarray(y)[:, 0] / mlp[:, 0]          # per-token kept gates
    # normalized top-2 gates of softmax([2,1]): g_hi ≈ 0.731, g_lo ≈ 0.269
    g_hi = float(np.exp(2) / (np.exp(2) + np.exp(1)))
    # rows 0 and 3: both routes kept (scale 1); the other four lose ONLY
    # their secondary (scale = primary gate) — primaries never drop
    np.testing.assert_allclose(scale[[0, 3]], 1.0, rtol=1e-5)
    np.testing.assert_allclose(scale[[1, 2, 4, 5]], g_hi, rtol=1e-5)


def test_moe_top2_lm_trains_and_composes_with_ep(mesh8):
    """moe_topk=2 through the model config: trains finite/decreasing dense
    AND with experts sharded over 'model' (ep=tp=2)."""
    for tp in (1, 2):
        m = _make(dp=2, tp=tp, moe_topk=2)
        costs = _train_steps(m, 5)
        assert np.isfinite(costs).all()
        assert np.mean(costs[-2:]) < np.mean(costs[:2])
