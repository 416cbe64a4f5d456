"""Ring attention vs the single-device oracle: exact (to accumulation
order) on an 8-way sequence-sharded mesh, fwd and grads, causal and not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from theanompi_tpu.ops.ring_attention import (attention_reference,
                                              ring_attention,
                                              ring_attention_sharded)
from theanompi_tpu.parallel.mesh import worker_mesh
from theanompi_tpu.jax_compat import shard_map

B, H, T, D = 2, 3, 64, 16        # T shards 8 ways × 8 tokens


def _qkv(seed=0):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(B, H, T, D).astype(np.float32))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full_attention(mesh8, causal):
    q, k, v = _qkv(1)
    out = ring_attention_sharded(q, k, v, mesh8, axis="workers",
                                 causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grads_match(mesh8, causal):
    """The whole point is TRAINING long sequences: gradients through the
    ring (scan + ppermute) must match full attention's."""
    q, k, v = _qkv(2)
    spec = P(None, None, "workers", None)

    def ring_loss(q, k, v):
        fn = shard_map(
            lambda a, b, c: ring_attention(a, b, c, axis="workers",
                                           causal=causal),
            mesh=mesh8, in_specs=(spec, spec, spec), out_specs=spec)
        return jnp.sum(fn(q, k, v) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    sh = NamedSharding(mesh8, spec)
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(qs, ks, vs)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_ring_attention_bf16_inputs(mesh8):
    """bf16 activations (the TPU training dtype) with fp32 accumulation."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(3))
    out = ring_attention_sharded(q, k, v, mesh8, axis="workers", causal=True)
    assert out.dtype == jnp.bfloat16
    ref = attention_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_2d_mesh_data_x_sequence_training_step():
    """Composition proof: a 2-D mesh (2 data-parallel workers × 4 sequence
    shards) trains a toy attention model — ring attention over the 'seq'
    axis inside the step, gradient psum over BOTH axes — and the loss
    decreases.  This is the long-context story on top of the same shard_map
    machinery the four exchangers use."""
    from jax import lax

    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("workers", "seq"))
    b, h, t, d, nclass = 4, 2, 32, 8, 2

    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(b, h, t, d).astype(np.float32))
    y = jnp.asarray((r.rand(b) > 0.5).astype(np.int32))
    params = {
        "wq": jnp.asarray(0.3 * r.randn(d, d).astype(np.float32)),
        "wk": jnp.asarray(0.3 * r.randn(d, d).astype(np.float32)),
        "wv": jnp.asarray(0.3 * r.randn(d, d).astype(np.float32)),
        "head": jnp.asarray(0.3 * r.randn(h * d, nclass).astype(np.float32)),
    }

    x_spec = P("workers", None, "seq", None)
    y_spec = P("workers")

    def loss_fn(params, x, y):
        q = jnp.einsum("bhtd,de->bhte", x, params["wq"])
        k = jnp.einsum("bhtd,de->bhte", x, params["wk"])
        v = jnp.einsum("bhtd,de->bhte", x, params["wv"])
        o = ring_attention(q, k, v, axis="seq", causal=True)
        # mean over the (sharded) sequence: local sum / global T
        pooled = lax.psum(o.sum(axis=2), "seq") / t        # [b_loc, h, d]
        logits = pooled.reshape(pooled.shape[0], -1) @ params["head"]
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - ll)

    def step(params, x, y, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        grads = jax.tree.map(
            lambda g: lax.pmean(lax.pmean(g, "workers"), "seq"), grads)
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new, lax.pmean(lax.pmean(loss, "workers"), "seq")

    sm = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=({k: P() for k in params}, x_spec, y_spec, P()),
        out_specs=({k: P() for k in params}, P())))

    xs = jax.device_put(x, NamedSharding(mesh, x_spec))
    ys = jax.device_put(y, NamedSharding(mesh, y_spec))
    losses = []
    for i in range(12):
        params, loss = sm(params, xs, ys, jnp.float32(0.5))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_ring_attention_jit_compiles_multichip():
    """Under jit on a fresh 8-way sequence mesh (the dryrun-style check)."""
    mesh = worker_mesh(8, axis_name="seq")
    q, k, v = _qkv(4)
    spec = P(None, None, "seq", None)
    fn = jax.jit(shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis="seq", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
    sh = NamedSharding(mesh, spec)
    out = fn(*(jax.device_put(x, sh) for x in (q, k, v)))
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
