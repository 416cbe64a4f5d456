"""Pipeline parallelism (parallel/pipeline.py): GPipe microbatch pipelining
over a 'pipe' mesh axis must compute the SAME model as the dense layout —
same init (stacked from the same per-layer keys), same losses and updates up
to fp32 summation-order noise.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.models.transformer_lm import TransformerLM
from theanompi_tpu.parallel.exchanger import BSP_Exchanger
from theanompi_tpu.parallel.mesh import PIPE_AXIS, WORKER_AXIS, worker_mesh
from theanompi_tpu.jax_compat import shard_map
from theanompi_tpu.parallel.pipeline import (microbatch, pipeline_apply,
                                             unmicrobatch)

LM_CFG = dict(verbose=False, batch_size=8, seq_len=16, vocab=32,
              synthetic_train=64, synthetic_val=32,
              d_model=32, n_head=4, n_layer=4, compute_dtype=jnp.float32)


def _make(dp, pp, **kw):
    mesh = worker_mesh(dp, pp=pp)
    cfg = {**LM_CFG, "mesh": mesh, "size": dp, "rank": 0, "pp": pp, **kw}
    return TransformerLM(cfg)


def _train_steps(model, n_steps):
    exch = BSP_Exchanger(model.config)
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    costs = []
    for i in range(n_steps):
        model.train_iter(i, None)
        costs.append(float(model.current_info["cost"]))
    return costs


def test_pipeline_apply_matches_sequential():
    """The raw pipeline primitive on a pure 'pipe' mesh vs a sequential scan
    of the same stacked layers — forward AND gradient."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    pp, L, m, mb, d = 4, 4, 8, 2, 16
    mesh = Mesh(np.asarray(jax.devices()[:pp]), (PIPE_AXIS,))
    r = np.random.RandomState(0)
    stack = jnp.asarray(0.3 * r.randn(L, d, d).astype(np.float32))
    x = jnp.asarray(r.randn(m * mb, d).astype(np.float32))

    def layer(w, h):
        return jnp.tanh(h @ w)

    def stage_fn(st, h):
        def body(hh, w):
            return layer(w, hh), None
        hh, _ = lax.scan(body, h, st)
        return hh

    def pipe_loss(stack, x):
        y = pipeline_apply(stage_fn, stack, microbatch(x, m))
        return jnp.sum(unmicrobatch(y) ** 2)

    def seq_loss(stack, x):
        return jnp.sum(stage_fn(stack, x) ** 2)

    def f(stack, x):
        cost, g = jax.value_and_grad(pipe_loss)(stack, x)
        return cost, g

    sm = jax.jit(shard_map(f, mesh=mesh,
                               in_specs=(P(PIPE_AXIS), P()),
                               out_specs=(P(), P(PIPE_AXIS))))
    cost, grad = sm(jax.device_put(stack, NamedSharding(mesh, P(PIPE_AXIS))),
                    jax.device_put(x, NamedSharding(mesh, P())))
    cost_ref, grad_ref = jax.value_and_grad(seq_loss)(stack, x)
    assert float(cost) == pytest.approx(float(cost_ref), rel=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(grad_ref),
                               rtol=1e-4, atol=1e-6)


def test_pp_init_identical_to_dense(mesh8):
    dense = _make(dp=2, pp=1)
    pp = _make(dp=2, pp=4)
    stacked = pp.params["blocks"]
    for i, blk in enumerate(dense.blocks):
        jax.tree.map(lambda s, d: np.testing.assert_array_equal(
            np.asarray(s[i]), np.asarray(d)),
            stacked, dense.params[blk.name])


@pytest.mark.slow
def test_pp_bsp_training_matches_dense(mesh8):
    dense = _make(dp=2, pp=1)
    pp = _make(dp=2, pp=4)
    c_dense = _train_steps(dense, 6)
    c_pp = _train_steps(pp, 6)
    np.testing.assert_allclose(c_pp, c_dense, rtol=2e-4, atol=2e-5)


def test_pp_mesh_and_sharding(mesh8):
    pp = _make(dp=2, pp=4)
    assert dict(pp.mesh.shape) == {WORKER_AXIS: 2, PIPE_AXIS: 4}
    pp.compile_iter_fns(BSP_Exchanger(pp.config))
    w = pp.step_state["params"]["blocks"]["fc1"]["w"]
    assert w.sharding.spec == (WORKER_AXIS, PIPE_AXIS), w.sharding.spec
    # one device holds [1 worker, 1 layer, d, 4d]
    assert w.addressable_shards[0].data.shape == (1, 1, 32, 128)


def test_pp_val_and_checkpoint(tmp_path, mesh8):
    from theanompi_tpu.parallel import steps
    pp = _make(dp=2, pp=4)
    _train_steps(pp, 3)
    pp.begin_val()
    pp.val_iter(0, None)
    pp.end_val()
    pp.save(str(tmp_path), epoch=0, count=3)
    before = jax.device_get(steps.tree_to_host(pp.step_state["params"]))
    pp2 = _make(dp=2, pp=4)
    pp2.compile_iter_fns(BSP_Exchanger(pp2.config))
    assert pp2.load(str(tmp_path)) == 0
    after = jax.device_get(steps.tree_to_host(pp2.step_state["params"]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), before, after)


def test_pp_microbatch_divisibility_asserts(mesh8):
    with pytest.raises(AssertionError, match="divisible"):
        microbatch(jnp.zeros((10, 4)), 4)


# -- interleaved virtual stages (round 10, ISSUE 16) ------------------------

def test_pipeline_apply_interleaved_matches_v1():
    """The raw primitive at v=2 computes the same function as v=1: same
    forward cost, same gradients (modulo the stage-permuted parameter
    layout interleaving requires — rows map through stage_permutation)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from theanompi_tpu.parallel.pipeline import stage_permutation
    pp, L, m, mb, d, v = 4, 8, 8, 2, 16, 2
    mesh = Mesh(np.asarray(jax.devices()[:pp]), (PIPE_AXIS,))
    r = np.random.RandomState(0)
    stack = jnp.asarray(0.3 * r.randn(L, d, d).astype(np.float32))
    x = jnp.asarray(r.randn(m * mb, d).astype(np.float32))
    perm = stage_permutation(L, pp, v)

    def layer(w, h):
        return jnp.tanh(h @ w)

    def stage_fn(st, h):
        def body(hh, w):
            return layer(w, hh), None
        hh, _ = lax.scan(body, h, st)
        return hh

    def run(interleave):
        def pipe_loss(stack, x):
            y = pipeline_apply(stage_fn, stack, microbatch(x, m),
                               interleave=interleave)
            return jnp.sum(unmicrobatch(y) ** 2)

        def f(stack, x):
            return jax.value_and_grad(pipe_loss)(stack, x)

        sm = jax.jit(shard_map(f, mesh=mesh,
                               in_specs=(P(PIPE_AXIS), P()),
                               out_specs=(P(), P(PIPE_AXIS))))
        st = stack if interleave == 1 else stack[np.asarray(perm)]
        return sm(jax.device_put(st, NamedSharding(mesh, P(PIPE_AXIS))),
                  jax.device_put(x, NamedSharding(mesh, P())))

    cost1, grad1 = run(1)
    cost2, grad2 = run(v)
    assert float(cost2) == pytest.approx(float(cost1), rel=1e-6)
    # grad2 is w.r.t. the permuted stack; un-permute back to depth order
    np.testing.assert_allclose(
        np.asarray(grad2)[np.argsort(perm)], np.asarray(grad1),
        rtol=1e-5, atol=1e-7)


def test_pp_interleaved_init_identical_to_dense(mesh8):
    """Interleaved init stacks the same per-layer params, just in stage
    order — _gathered_dense_params round-trips them to depth order."""
    dense = _make(dp=2, pp=1, n_layer=8)
    ppm = _make(dp=2, pp=4, n_layer=8, pp_interleave=2)
    gathered = ppm._gathered_dense_params()
    for i, blk in enumerate(dense.blocks):
        jax.tree.map(lambda g, d: np.testing.assert_array_equal(
            np.asarray(g), np.asarray(d)),
            gathered[blk.name], dense.params[blk.name])


@pytest.mark.slow
def test_pp_interleaved_training_matches_v1_exact(mesh8):
    """v=2 walks each chunk's microbatches in the same order as v=1, so
    even the fp summation order matches — training costs are IDENTICAL,
    not merely close."""
    c1 = _train_steps(_make(dp=2, pp=4, n_layer=8), 5)
    c2 = _train_steps(_make(dp=2, pp=4, n_layer=8, pp_interleave=2), 5)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.slow
def test_pp_interleaved_v4_matches_v1_exact(mesh8):
    c1 = _train_steps(_make(dp=2, pp=4, n_layer=16), 4)
    c4 = _train_steps(_make(dp=2, pp=4, n_layer=16, pp_interleave=4), 4)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c4))


@pytest.mark.slow
def test_pp_interleaved_training_matches_dense(mesh8):
    """Same tolerance the v=1 pin uses (fp noise only)."""
    c_dense = _train_steps(_make(dp=2, pp=1, n_layer=8), 5)
    c_v2 = _train_steps(_make(dp=2, pp=4, n_layer=8, pp_interleave=2), 5)
    np.testing.assert_allclose(c_v2, c_dense, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_pp_interleaved_spc_fused_exact(mesh8):
    """The fused multi-step dispatch (steps_per_call) composes with the
    interleaved schedule: same costs as v=1 under the same cadence."""
    c1 = _train_steps(_make(dp=2, pp=4, n_layer=8, steps_per_call=2), 4)
    c2 = _train_steps(_make(dp=2, pp=4, n_layer=8, steps_per_call=2,
                            pp_interleave=2), 4)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.slow
def test_pp_interleaved_moe_aux_exact(mesh8):
    """with_aux masking stays exact over real ticks under interleaving:
    the MoE load-balance aux (psummed over the schedule) matches v=1
    bit-for-bit."""
    from theanompi_tpu.models.transformer_lm import MoETransformerLM

    def make(v):
        mesh = worker_mesh(2, pp=4)
        cfg = {**LM_CFG, "mesh": mesh, "size": 2, "rank": 0, "pp": 4,
               "n_layer": 8, "moe_experts": 4, "moe_every": 1,
               "pp_interleave": v}
        return MoETransformerLM(cfg)

    c1 = _train_steps(make(1), 4)
    c2 = _train_steps(make(2), 4)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_pp_interleave_validation_errors(mesh8):
    with pytest.raises(ValueError, match="pp_interleave"):
        _make(dp=2, pp=4, n_layer=8, pp_interleave=3)   # 8 % (4*3) != 0
    with pytest.raises(ValueError, match="pp_microbatches"):
        _make(dp=2, pp=4, n_layer=8, pp_interleave=2, pp_microbatches=6)
    with pytest.raises(ValueError, match="pp"):
        mesh = worker_mesh(2, pp=1)
        TransformerLM({**LM_CFG, "mesh": mesh, "size": 2, "rank": 0,
                       "pp": 1, "pp_interleave": 2})


def test_shim_sync_fallback_round_trip():
    """Without a real async surface the start permutes eagerly and the
    done unwraps — the one calling convention the pipeline's hop (and
    tpulint's pairing probe) sees, with a plain ppermute's result."""
    from jax.sharding import PartitionSpec as P
    from theanompi_tpu import jax_compat
    mesh = worker_mesh(4)
    perm = [(i, (i + 1) % 4) for i in range(4)]

    def f(x):
        t = jax_compat.ppermute_start(x, WORKER_AXIS, perm)
        return jax_compat.ppermute_done(t)

    g = jax.jit(shard_map(f, mesh=mesh, in_specs=P(WORKER_AXIS),
                          out_specs=P(WORKER_AXIS)))
    x = jnp.arange(8.0)
    np.testing.assert_array_equal(np.asarray(g(x)),
                                  np.roll(np.asarray(x).reshape(4, 2), 1,
                                          axis=0).reshape(-1))
