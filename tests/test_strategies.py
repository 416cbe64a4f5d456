"""Exchanger-strategy numerical equivalence vs a NumPy oracle.

SURVEY.md §4 test matrix item (a): run each strategy over known per-worker
buffers on a real 8-way (simulated) mesh and check the reduced values — what
the reference could only do manually under ``mpirun -np 2..8``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from theanompi_tpu.ops import compress
from theanompi_tpu.parallel.mesh import WORKER_AXIS, worker_local_sharding
from theanompi_tpu.parallel import strategies
from theanompi_tpu.parallel.strategies import get_strategy
from theanompi_tpu.jax_compat import shard_map

N = 8


def _run_strategy(mesh, strat, per_worker_trees, state_boxed=None):
    """Drive a strategy inside shard_map exactly as the train step does."""
    from theanompi_tpu.parallel import steps

    def body(tree, state):
        tree = steps.unbox(tree)
        state = steps.unbox(state)
        out, new_state = strat(tree, state, axis=WORKER_AXIS, size=N)
        return steps.box(out), steps.box(new_state)

    sm = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(WORKER_AXIS), P(WORKER_AXIS)),
        out_specs=(P(WORKER_AXIS), P(WORKER_AXIS))))
    sh = worker_local_sharding(mesh)
    boxed = jax.tree.map(lambda x: jax.device_put(x, sh), per_worker_trees)
    if state_boxed is None:
        state_boxed = jax.tree.map(
            lambda x: jax.device_put(x, sh),
            jax.tree.map(lambda s: np.broadcast_to(
                np.asarray(s)[None], (N,) + np.asarray(s).shape).copy(),
                strat.init_state(steps.unbox(boxed))))
    return sm(boxed, state_boxed)


def _mk_tree(seed=0):
    """Per-worker pytree boxed as leaves [N, ...]."""
    r = np.random.RandomState(seed)
    return {
        "w": r.randn(N, 6, 10).astype(np.float32),
        "b": r.randn(N, 11).astype(np.float32),
    }


def _oracle_mean(tree):
    return jax.tree.map(lambda x: x.mean(axis=0), tree)


@pytest.mark.parametrize("name", ["allreduce", "ar", "nccl32", "asa32",
                                  "ring", "copper"])
def test_exact_strategies_match_oracle(mesh8, name):
    tree = _mk_tree(1)
    out, _ = _run_strategy(mesh8, get_strategy(name), tree)
    expect = _oracle_mean(tree)
    for k in tree:
        got = np.asarray(out[k])
        for w in range(N):
            np.testing.assert_allclose(got[w], expect[k], rtol=1e-5,
                                       atol=1e-6)


@pytest.mark.parametrize("name", ["nccl16", "asa16", "ring16", "copper16",
                                  "bf16"])
def test_bf16_wire_strategies_approximate_oracle(mesh8, name):
    tree = _mk_tree(2)
    out, _ = _run_strategy(mesh8, get_strategy(name), tree)
    expect = _oracle_mean(tree)
    for k in tree:
        got = np.asarray(out[k])
        # bf16 has ~3 decimal digits; ring accumulates over N-1 hops
        np.testing.assert_allclose(got[0], expect[k], rtol=0.05, atol=0.05)
        # all workers agree exactly
        for w in range(1, N):
            np.testing.assert_array_equal(got[w], got[0])


def test_ring_is_bit_consistent_across_workers(mesh8):
    tree = _mk_tree(3)
    out, _ = _run_strategy(mesh8, get_strategy("ring"), tree)
    for k in tree:
        got = np.asarray(out[k])
        for w in range(1, N):
            np.testing.assert_array_equal(got[w], got[0])


def test_onebit_identical_inputs_decode_exactly(mesh8):
    """With identical per-worker inputs, 1-bit EF decodes to scale·sign."""
    r = np.random.RandomState(4)
    base = r.randn(compress.PACK_ALIGN).astype(np.float32)
    tree = {"g": np.broadcast_to(base[None], (N,) + base.shape).copy()}
    strat = get_strategy("onebit")
    out, state = _run_strategy(mesh8, strat, tree)
    scale = np.abs(base).mean()
    expect = scale * np.where(base >= 0, 1.0, -1.0)
    np.testing.assert_allclose(np.asarray(out["g"])[0], expect, rtol=1e-4,
                               atol=1e-5)
    # error feedback holds the quantization residual
    ef = np.asarray(state)[0]
    np.testing.assert_allclose(ef, base - expect, rtol=1e-4, atol=1e-5)


def test_onebit_error_feedback_converges_on_average(mesh8):
    """EF property: the running sum of decoded outputs tracks the running
    sum of true means (residuals stay bounded)."""
    r = np.random.RandomState(5)
    tree = {"g": r.randn(N, compress.PACK_ALIGN).astype(np.float32)}
    strat = get_strategy("onebit")
    true_mean = np.asarray(_oracle_mean(tree)["g"])
    state = None
    total = np.zeros_like(true_mean)
    steps_n = 30
    for i in range(steps_n):
        out, state = _run_strategy(mesh8, strat, tree, state)
        total += np.asarray(out["g"])[0]
    avg = total / steps_n
    err = np.abs(avg - true_mean).mean() / (np.abs(true_mean).mean() + 1e-9)
    assert err < 0.25, f"EF average error too high: {err}"


def test_topk_full_k_is_bf16_wire_exact(mesh8):
    """With k = chunk (everything selected) chunked top-k degenerates to a
    bf16-wire allreduce: mean within bf16 rounding; the error buffer holds
    exactly the bf16 quantization residuals (≤ 2⁻⁸ relative)."""
    tree = _mk_tree(6)
    strat = get_strategy("topk", k=strategies.TopK.CHUNK)
    out, state = _run_strategy(mesh8, strat, tree)
    expect = _oracle_mean(tree)
    for k in tree:
        # same tolerance as the bf16-wire strategies: per-worker bf16
        # rounding before the sum, so abs error scales with |v_w|, not the
        # (possibly cancelled) mean
        np.testing.assert_allclose(np.asarray(out[k])[0], expect[k],
                                   rtol=0.05, atol=0.05)
    ef = np.asarray(state)[0]
    flat_w = np.concatenate(
        [np.asarray(tree[k])[0].reshape(-1) for k in tree])
    assert np.abs(ef).max() <= np.abs(flat_w).max() * 2**-8 + 1e-7


def test_topk_error_feedback_converges_on_average(mesh8):
    """EF property for chunked top-k: running sum of decoded outputs tracks
    the running sum of true means."""
    r = np.random.RandomState(6)
    tree = {"g": r.randn(N, 1024).astype(np.float32)}
    strat = get_strategy("topk", ratio=0.05)
    true_mean = np.asarray(_oracle_mean(tree)["g"])
    state = None
    total = np.zeros_like(true_mean)
    steps_n = 40
    for i in range(steps_n):
        out, state = _run_strategy(mesh8, strat, tree, state)
        total += np.asarray(out["g"])[0]
    avg = total / steps_n
    err = np.abs(avg - true_mean).mean() / (np.abs(true_mean).mean() + 1e-9)
    assert err < 0.3, f"EF average error too high: {err}"


def test_topk_selects_largest_per_chunk(mesh8):
    """One dominant entry per worker must survive a 1-per-chunk selection,
    arriving bf16-rounded at every worker."""
    x = np.zeros((N, 512), np.float32)
    for w in range(N):
        x[w, 7 * w] = 10.0 + w          # distinct spike per worker
    tree = {"g": x}
    strat = get_strategy("topk", k=1)
    out, _ = _run_strategy(mesh8, strat, tree)
    got = np.asarray(out["g"])[0]
    for w in range(N):
        np.testing.assert_allclose(got[7 * w], (10.0 + w) / N, rtol=1e-2)


def test_pack_unpack_roundtrip():
    r = np.random.RandomState(8)
    c = r.randn(4 * compress.PACK_ALIGN).astype(np.float32)
    packed = compress.pack_signs(jnp.asarray(c))
    assert packed.dtype == jnp.uint32
    # 32 sign bits per uint32 word, rows of 128 lanes
    assert packed.shape == (c.shape[0] // (32 * 128), 128)
    signs = np.asarray(compress.unpack_signs(packed))
    np.testing.assert_array_equal(signs, np.where(c >= 0, 1.0, -1.0))


def test_pack_pallas_matches_jnp_oracle():
    """The Pallas kernel pair (interpret mode here — compiled on TPU) and the
    jnp oracle must produce bit-identical wire buffers."""
    r = np.random.RandomState(13)
    c = jnp.asarray(r.randn(2 * compress.PACK_ALIGN).astype(np.float32))
    packed_pl = compress._pack_pallas(
        c.reshape(-1, compress.LANES), interpret=True)
    packed_jnp = compress.pack_signs_jnp(c)
    np.testing.assert_array_equal(np.asarray(packed_pl),
                                  np.asarray(packed_jnp))


def test_unpack_weighted_sum_pallas_matches_jnp_oracle():
    r = np.random.RandomState(14)
    c = r.randn(4, compress.PACK_ALIGN).astype(np.float32)
    scales = jnp.asarray(np.abs(r.randn(4)).astype(np.float32) + 0.1)
    packed = jnp.stack([compress.pack_signs_jnp(jnp.asarray(ci)) for ci in c])
    got = compress._unpack_wsum_pallas(packed, scales, interpret=True)
    expect = compress.unpack_signs_weighted_sum_jnp(packed, scales)
    np.testing.assert_allclose(np.asarray(got).reshape(-1),
                               np.asarray(expect), rtol=1e-6, atol=1e-6)


def test_encode_pallas_matches_jnp_oracle():
    """Fused onebit encode: one error-fed read → (packed signs, |c|),
    bit-identical to the oracle on both outputs."""
    r = np.random.RandomState(15)
    flat = jnp.asarray(r.randn(2 * compress.PACK_ALIGN).astype(np.float32))
    state = jnp.asarray(r.randn(2 * compress.PACK_ALIGN).astype(np.float32))
    packed_pl, abs_pl = compress._encode_pallas(
        flat.reshape(-1, compress.LANES),
        state.reshape(-1, compress.LANES), interpret=True)
    packed_jnp, abs_jnp = compress.pack_signs_encode_jnp(flat, state)
    np.testing.assert_array_equal(np.asarray(packed_pl),
                                  np.asarray(packed_jnp))
    np.testing.assert_array_equal(np.asarray(abs_pl).reshape(-1),
                                  np.asarray(abs_jnp))


def test_residual_pallas_matches_jnp_oracle():
    """Fused onebit residual: ``where(bit, |c|−scale, scale−|c|)`` from the
    packed bits, bit-identical to the oracle (which is itself bit-exact vs
    the unfused ``c − scale·sign`` — pinned in test_compress_fusion.py)."""
    r = np.random.RandomState(16)
    c = r.randn(2 * compress.PACK_ALIGN).astype(np.float32)
    c[::97] = 0.0                    # exercise the c == 0 bit-1 convention
    c = jnp.asarray(c)
    packed = compress.pack_signs_jnp(c)
    absc = jnp.abs(c)
    scale = jnp.float32(0.37)
    got = compress._residual_pallas(
        absc.reshape(-1, compress.LANES), packed, scale, interpret=True)
    expect = compress.signed_residual_jnp(absc, packed, scale)
    np.testing.assert_array_equal(np.asarray(got).reshape(-1),
                                  np.asarray(expect))


@pytest.mark.parametrize("rows,cols", [(10, 64), (300, 2500)])
def test_matmul_pack_pallas_matches_jnp_oracle(rows, cols):
    """Fused PowerSGD factor matmul + staging pack: the MXU tile must equal
    ``m @ q`` with the pad rows exactly zero (the stacked-psum identity in
    parallel/strategies.py PowerSGD rests on those zeros).  The second
    shape spans two K blocks with a masked tail and a partial row block."""
    from theanompi_tpu.ops import factor_pack
    r = np.random.RandomState(19)
    m = jnp.asarray(r.randn(rows, cols).astype(np.float32))
    q = jnp.asarray(r.randn(cols, 2).astype(np.float32))
    rows_pad = factor_pack.pad_rows(rows)
    got = factor_pack._matmul_pack_pallas(m, q, rows_pad, interpret=True)
    expect = factor_pack.matmul_pack_jnp(m, q, rows_pad)
    assert got.shape == (rows_pad, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got)[rows:], 0.0)


def test_unpack_weighted_sum_oracle():
    r = np.random.RandomState(9)
    c = r.randn(3, compress.PACK_ALIGN).astype(np.float32)
    scales = np.abs(r.randn(3)).astype(np.float32)
    packed = jnp.stack([compress.pack_signs(jnp.asarray(ci)) for ci in c])
    got = np.asarray(compress.unpack_signs_weighted_sum(packed,
                                                        jnp.asarray(scales)))
    expect = (np.where(c >= 0, 1.0, -1.0) * scales[:, None]).sum(axis=0)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-5)


def test_unknown_strategy_raises():
    with pytest.raises(ValueError, match="unknown exchange strategy"):
        get_strategy("definitely-not-a-strategy")


# -- a wide FC's gradient as gathered operands: the rule (PERF.md §6, PR 32) --

@pytest.mark.parametrize("who,n,rows,n_in,n_out,engages,reduced,gathered", [
    # VGG-16 at b384 a chip in bf16, four chips: the benchmark's cell
    ("vgg16-fc6", 4, 384, 25088, 4096, True, 616_562_688, 67_239_936),
    ("vgg16-fc7", 4, 384, 4096, 4096, True, 100_663_296, 18_874_368),
    ("vgg16-fc8", 4, 384, 4096, 1000, False, 24_576_000, 11_741_184),
    # one chip moves nothing; thirty-two gather too many rows
    ("vgg16-fc6-n1", 1, 384, 25088, 4096, False, 0, 0),
    ("vgg16-fc6-n32", 32, 384, 25088, 4096, False, 796_393_472,
     694_812_672),
    # AlexNet at b1024 on four: 82 MB against 226; ResNet-50's head is
    # under the floor whatever its rows
    ("alexnet-fc6", 4, 1024, 9216, 4096, False, 226_492_416, 81_788_928),
    ("resnet50-head", 4, 8, 2048, 1000, False, 12_288_000, 146_304),
])
def test_gather_rule_table(who, n, rows, n_in, n_out, engages, reduced,
                           gathered):
    assert strategies.fc_wire_bytes(n, rows, 1, n_in, n_out, 2) \
        == (reduced, gathered)
    assert strategies.gather_engages(n, rows, 1, n_in, n_out, 2) is engages


def test_gather_rule_counts_microbatches_and_the_floor():
    # fc7 engages at one microbatch (18.9 MB against 100.7) and not at two
    assert strategies.gather_engages(4, 384, 1, 4096, 4096, 2)
    assert not strategies.gather_engages(4, 384, 2, 4096, 4096, 2)
    # a 2 MiB leaf is an all-reduce of latency, whatever its rows
    assert not strategies.gather_engages(4, 1, 1, 1024, 512, 2)
    assert strategies.gather_engages(4, 1, 1, 1024, 512, 2, min_bytes=0)


def test_allreduce_scales_summed_leaves_without_a_collective(mesh8):
    tree = _mk_tree(3)
    out, _ = _run_strategy(mesh8, get_strategy("allreduce"), tree)
    held = functools.partial(get_strategy("allreduce"),
                             summed={"['w']": ()})
    out_held, _ = _run_strategy(
        mesh8, held, tree,
        state_boxed=jax.device_put(np.zeros((N, 0), np.float32),
                                   worker_local_sharding(mesh8)))
    # the other leaf is the mean still; the held one is its own 1/N
    np.testing.assert_array_equal(np.asarray(out_held["b"]),
                                  np.asarray(out["b"]))
    np.testing.assert_allclose(np.asarray(out_held["w"]), tree["w"] / N,
                               rtol=1e-6)


def _n_compressible(params):
    psgd = get_strategy("powersgd")
    return sum(psgd._compressible(np.shape(l))
               for l in jax.tree.leaves(params))


# each BSP wire's collectives a dispatch, a device: (op class, count from
# TinyModel's parameters and the mesh's size n)
_WIRE_COLLECTIVES = {
    "allreduce": ("all-reduce", lambda p, n: len(jax.tree.leaves(p))),
    "nccl16": ("all-reduce", lambda p, n: len(jax.tree.leaves(p))),
    # the scale and the packed signs / the values and the offsets
    "onebit": ("all-gather", lambda p, n: 2),
    "topk": ("all-gather", lambda p, n: 2),
    # reduce-scatter, then all-gather: one hop each
    "ring": ("collective-permute", lambda p, n: 2 * (n - 1)),
    # the stacked P and Q factor sums, then one sum a dense leaf
    "powersgd": ("all-reduce", lambda p, n: 2 + len(jax.tree.leaves(p))
                 - _n_compressible(p)),
}


@pytest.mark.parametrize("wire", sorted(_WIRE_COLLECTIVES))
def test_bsp_window_collective_count(wire):
    """A devprof capture of k BSP dispatches on the four-device CPU mesh
    holds each wire's collectives k times on each device, and every
    async start its done.  train_fn is driven directly: train_iter's
    cost-mean helper dispatches a collective of its own."""
    from tests.conftest import TinyModel
    from theanompi_tpu.parallel import steps
    from theanompi_tpu.parallel.exchanger import BSP_Exchanger
    from theanompi_tpu.parallel.mesh import worker_mesh
    from theanompi_tpu.utils import devprof
    k, n = 3, 4
    mesh = worker_mesh(n)
    cfg = {"mesh": mesh, "size": n, "rank": 0, "verbose": False,
           "batch_size": 8, "exch_strategy": wire}
    model = TinyModel(cfg)
    model.compile_iter_fns(BSP_Exchanger(cfg))
    model.data.shuffle_data(0)
    batch = steps.put_batch(mesh, model.data.next_train_batch(0),
                            model.batch_spec())
    lr, rng = jnp.float32(0.05), jax.random.key(0)
    st, _, _ = model.train_fn(model.step_state, batch, lr, rng,
                              jnp.int32(1))
    jax.block_until_ready(st["params"])         # compile outside the window
    with devprof.capture() as cap:
        for count in range(2, 2 + k):
            st, _, _ = model.train_fn(st, batch, lr, rng, jnp.int32(count))
        jax.block_until_ready(st["params"])
    assert cap.profile is not None
    ops = {o["op"]: o["count"] for o in cap.profile["top_ops"]}
    for op, c in ops.items():
        if op.endswith("-start"):
            assert ops.get(op[:-len("-start")] + "-done") == c, ops
    op_class, per_device = _WIRE_COLLECTIVES[wire]
    got = sum(c for op, c in ops.items() if op.startswith(op_class))
    assert got == per_device(model.params, n) * k * n, (got, ops)
