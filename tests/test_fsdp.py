"""FSDP / ZeRO-3 (parallel/fsdp.py): parameters sharded over the workers
axis as flat chunks, gathered transiently per step, gradients reduce-
scattered by the all_gather's AD transpose — bit-equal to plain BSP."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import TinyModel
from theanompi_tpu.models.transformer_lm import TransformerLM
from theanompi_tpu.parallel import steps
from theanompi_tpu.parallel.exchanger import BSP_Exchanger, get_exchanger
from theanompi_tpu.parallel.mesh import WORKER_AXIS, worker_mesh


def _train(model, exch, n_steps):
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    costs = []
    for i in range(n_steps):
        model.train_iter(i, None)
        costs.append(float(model.current_info["cost"]))
    return costs


def _make_tiny(fsdp, mesh, **kw):
    cfg = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
           "fsdp": fsdp, **kw}
    return TinyModel(cfg), cfg


def _host_params(model):
    if model._fsdp is not None:
        return model.canonical_host_params()
    return steps.unbox(jax.device_get(model.step_state["params"]))


@pytest.mark.parametrize("optimizer", ["momentum", "adam"])
def test_fsdp_bit_equal_to_bsp(mesh4, optimizer):
    """Same data, same seed: the gather/transpose-scatter step must trace
    plain BSP's trajectory EXACTLY (psum and psum_scatter reduce in the
    same order on the simulated mesh; elementwise update on chunks)."""
    base, _ = _make_tiny(False, mesh4, optimizer=optimizer)
    shard, _ = _make_tiny(True, mesh4, optimizer=optimizer)
    c0 = _train(base, BSP_Exchanger(base.config), 6)
    c1 = _train(shard, BSP_Exchanger(shard.config), 6)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
        _host_params(base), _host_params(shard))


def test_fsdp_state_is_the_partition(mesh4):
    """Persistent memory: params AND optimizer state live as one
    ceil(P/N) chunk per worker — the boxed [n, chunk] layout IS the
    partition, and chunks genuinely differ across workers."""
    model, _ = _make_tiny(True, mesh4, optimizer="adam")
    model.compile_iter_fns(BSP_Exchanger(model.config))
    chunk = -(-model.n_params // 4)
    p = model.step_state["params"]
    assert p.shape == (4, chunk)
    assert p.sharding.spec == (WORKER_AXIS,)
    m = model.step_state["opt_state"]["m"]
    assert m.shape == (4, chunk)
    pp = np.asarray(jax.device_get(p))
    assert not np.array_equal(pp[0], pp[1])
    # the gathered full tree still matches the init params before training
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=0, atol=0),
        model.canonical_host_params(), jax.device_get(model.params))


def test_fsdp_composes_with_n_subb(mesh4):
    """Microbatch accumulation re-gathers per microbatch inside the scan
    and accumulates the CHUNK-sized gradient (scatter-then-sum — the
    accumulator is 1/N the size of BSP's full-tree sum-then-reduce).  The
    reduction order therefore differs by one level of fp32 associativity:
    trajectories track to float tolerance, not bit-exactly (the n_subb=1
    case IS bit-exact — test_fsdp_bit_equal_to_bsp)."""
    base, _ = _make_tiny(False, mesh4, n_subb=2, batch_size=16)
    shard, _ = _make_tiny(True, mesh4, n_subb=2, batch_size=16)
    c0 = _train(base, BSP_Exchanger(base.config), 4)
    c1 = _train(shard, BSP_Exchanger(shard.config), 4)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1),
                               rtol=1e-6, atol=1e-7)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-7),
        _host_params(base), _host_params(shard))


def test_fsdp_composes_with_steps_per_call(mesh4):
    """k full FSDP steps per dispatch (the scan carries the chunk state)
    must land bit-equal to k single-step dispatches."""
    one, _ = _make_tiny(True, mesh4)
    spc, _ = _make_tiny(True, mesh4, steps_per_call=2)
    _train(one, BSP_Exchanger(one.config), 4)
    m = spc
    m.compile_iter_fns(BSP_Exchanger(m.config))
    m.data.shuffle_data(0)
    for last in (1, 3):
        m.train_iter(last, None)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), _host_params(one), _host_params(m))


def test_fsdp_ema_matches_dense_ema(mesh4):
    """The EMA shadow tracks the CHUNK under fsdp; the assembled shadow
    must equal the dense EMA shadow, and validation reads it."""
    base, _ = _make_tiny(False, mesh4, ema_decay=0.9)
    shard, _ = _make_tiny(True, mesh4, ema_decay=0.9)
    _train(base, BSP_Exchanger(base.config), 5)
    _train(shard, BSP_Exchanger(shard.config), 5)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
        base._ema_host_params(), shard._ema_host_params())
    # begin_val assembles the shadow on device — same tree
    shard.begin_val()
    boxed = jax.device_get(shard._val_params_boxed)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)[0]),
        shard._ema_host_params(), boxed)
    shard.end_val()


def test_fsdp_grad_clip_close_to_bsp(mesh4):
    """Global-norm clipping: the chunked norm (one vector psum) equals the
    leaf-wise norm up to fp32 summation order — trajectories track to
    float tolerance with a clip LOW enough to actually engage."""
    base, _ = _make_tiny(False, mesh4, grad_clip=0.05)
    shard, _ = _make_tiny(True, mesh4, grad_clip=0.05)
    c0 = _train(base, BSP_Exchanger(base.config), 5)
    c1 = _train(shard, BSP_Exchanger(shard.config), 5)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1),
                               rtol=1e-5, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
        _host_params(base), _host_params(shard))


def test_fsdp_val_matches_bsp(mesh4):
    """Validation gathers the full tree on device; metrics must equal the
    dense model's on the same replicas."""
    base, _ = _make_tiny(False, mesh4)
    shard, _ = _make_tiny(True, mesh4)
    _train(base, BSP_Exchanger(base.config), 4)
    _train(shard, BSP_Exchanger(shard.config), 4)
    for m in (base, shard):
        m.begin_val()
    b0 = base.data.next_val_batch(0)
    dev = steps.put_batch(base.mesh, b0, None)
    r0 = [np.asarray(x) for x in base.val_fn(
        base._val_params_boxed, base._val_bn_boxed, dev)]
    r1 = [np.asarray(x) for x in shard.val_fn(
        shard._val_params_boxed, shard._val_bn_boxed, dev)]
    for a, b in zip(r0, r1):
        np.testing.assert_array_equal(a, b)


def test_fsdp_checkpoint_exact_resume(tmp_path, mesh4):
    """Save mid-run, rebuild from disk, continue: bit-equal to the
    uninterrupted run.  Chunks are genuinely per-worker state — the
    checkpoint stores params AND opt_state boxed (no dedup)."""
    solo, _ = _make_tiny(True, mesh4)
    c_solo = _train(solo, BSP_Exchanger(solo.config), 6)

    a, _ = _make_tiny(True, mesh4)
    _train(a, BSP_Exchanger(a.config), 3)
    a.save(str(tmp_path), epoch=0, count=3)
    import json
    import os
    with open(os.path.join(str(tmp_path), "ckpt_epoch0.json")) as f:
        meta = json.load(f)
    assert set(meta["boxed_parts"]) >= {"params", "opt_state"}, meta
    # the .npy snapshot holds the FULL canonical tree, not chunks
    snap = os.path.join(str(tmp_path), "params_epoch0")
    full_shapes = sorted(np.shape(l) for l in jax.tree.leaves(a.params))
    snap_shapes = sorted(np.load(os.path.join(snap, f)).shape
                         for f in os.listdir(snap))
    assert snap_shapes == full_shapes

    b, _ = _make_tiny(True, mesh4)
    b.compile_iter_fns(BSP_Exchanger(b.config))
    assert b.load(str(tmp_path)) == 0    # also restores the data cursor —
    costs = []                           # no shuffle_data() after load
    for i in range(3, 6):
        b.train_iter(i, None)
        costs.append(float(b.current_info["cost"]))
    np.testing.assert_array_equal(np.asarray(c_solo[3:]), np.asarray(costs))
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), _host_params(solo), _host_params(b))


def test_fsdp_checkpoint_is_worker_count_portable(tmp_path, mesh4, mesh8):
    """Elastic resume for chunked state: chunking is a pure partition of
    the padded flat vector, so a 4-worker fsdp checkpoint re-slices onto
    8 workers (and back) — assembled params and optimizer flat identical,
    and training continues."""
    d = str(tmp_path / "ckpt")
    m4, _ = _make_tiny(True, mesh4, optimizer="adam")
    _train(m4, BSP_Exchanger(m4.config), 3)
    m4.save(d, epoch=0, count=3)
    ref = m4.canonical_host_params()
    ref_m = np.asarray(jax.device_get(
        m4.step_state["opt_state"]["m"])).reshape(-1)[:m4.n_params]

    cfg8 = {"mesh": mesh8, "size": 8, "rank": 0, "verbose": False,
            "fsdp": True, "optimizer": "adam"}
    m8 = TinyModel(cfg8)
    m8.compile_iter_fns(BSP_Exchanger(cfg8))
    assert m8.load(d) == 0
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), ref, m8.canonical_host_params())
    got_m = np.asarray(jax.device_get(
        m8.step_state["opt_state"]["m"])).reshape(-1)[:m8.n_params]
    np.testing.assert_array_equal(ref_m, got_m)
    t8 = np.asarray(jax.device_get(m8.step_state["opt_state"]["t"]))
    assert t8.shape == (8,) and (t8 == t8[0]).all() and t8[0] == 3
    m8.train_iter(3, None)               # and it keeps training

    # different model config must fail LOUDLY, not silently re-slice
    cfg_bad = dict(cfg8, n_train=256)
    bad = TinyModel({**cfg_bad, "batch_size": 8})
    bad.params = jax.tree.map(
        lambda x: np.zeros(np.shape(x)[:-1] + (np.shape(x)[-1] + 1,),
                           np.float32), bad.params)
    from theanompi_tpu.parallel.fsdp import FsdpLayout
    bad._fsdp = FsdpLayout(bad.params, 8)
    bad.compile_iter_fns(BSP_Exchanger(bad.config))
    with pytest.raises(AssertionError, match="different model config"):
        bad.load(d)


def test_rechunk_roundtrips_across_worker_counts():
    """Pure-layout property: re-partitioning a flat vector through ANY
    sequence of worker counts is the identity on the data (pad is sliced
    off and re-derived each hop) — for both fsdp's flat layout and zero's
    rank-major model-sharded layout."""
    from theanompi_tpu.parallel import zero as zero_lib
    from theanompi_tpu.parallel.fsdp import FsdpLayout
    rng = np.random.RandomState(0)
    params = {"a": rng.randn(13, 7).astype(np.float32),
              "b": rng.randn(29).astype(np.float32)}
    flat = np.concatenate([params["a"].reshape(-1), params["b"]])
    for ns in ([4, 8, 3, 4], [1, 5, 1]):
        lay = {n: FsdpLayout(params, n) for n in ns}
        boxed = lay[ns[0]].chunk_host(params)
        for n in ns[1:]:
            boxed = lay[n].rechunk(boxed)
        np.testing.assert_array_equal(
            boxed.reshape(-1)[:flat.size], flat)
    # zero's rank-major layout: shards=3 model ranks, each local_total=40
    local_total, shards = 40, 3
    per_rank = rng.randn(shards, local_total).astype(np.float32)

    def to_boxed(n):
        c = zero_lib.chunk_size(local_total, n)
        padded = np.pad(per_rank, ((0, 0), (0, c * n - local_total)))
        return np.transpose(padded.reshape(shards, n, c),
                            (1, 0, 2)).reshape(n, shards * c)

    boxed = to_boxed(5)
    for n in (2, 7, 5):
        boxed = zero_lib.rechunk_boxed(boxed, n, shards, local_total)
    np.testing.assert_array_equal(boxed, to_boxed(5))


def test_fsdp_rejects_incompatible_configs(mesh4, mesh8):
    """fsdp is BSP-grads + exact allreduce only; zero_opt is subsumed;
    model-parallel layouts shard params their own way."""
    m, cfg = _make_tiny(True, mesh4, sync_freq=2)
    with pytest.raises(AssertionError, match="allreduce"):
        m.compile_iter_fns(get_exchanger("gosgd", cfg))
    for bad in ({"exch_strategy": "topk"}, {"exch_mode": "params"},
                {"exch_strategy": "none"}):
        m, cfg = _make_tiny(True, mesh4, **bad)
        with pytest.raises(AssertionError, match="allreduce"):
            m.compile_iter_fns(BSP_Exchanger(cfg))
    with pytest.raises(AssertionError, match="subsumes"):
        _make_tiny(True, mesh4, zero_opt=True)
    mesh = worker_mesh(2, tp=2)
    with pytest.raises(AssertionError, match="tensor/pipeline|data-parallel"):
        TransformerLM({"mesh": mesh, "size": 2, "rank": 0, "tp": 2,
                       "verbose": False, "fsdp": True, "batch_size": 8,
                       "seq_len": 16, "vocab": 32, "d_model": 32,
                       "n_head": 4, "n_layer": 2,
                       "compute_dtype": jnp.float32})


def test_fsdp_transformer_trains(mesh8):
    """The LM family rides fsdp unchanged (pure-DP layout): loss falls and
    the persistent state is chunked."""
    mesh = worker_mesh(8)
    cfg = {"mesh": mesh, "size": 8, "rank": 0, "verbose": False,
           "fsdp": True, "batch_size": 8, "seq_len": 16, "vocab": 32,
           "d_model": 32, "n_head": 4, "n_layer": 2,
           "synthetic_train": 128, "compute_dtype": jnp.float32}
    model = TransformerLM(cfg)
    costs = _train(model, BSP_Exchanger(cfg), 6)
    assert np.isfinite(costs).all()
    assert np.mean(costs[-3:]) < np.mean(costs[:3])
    chunk = -(-model.n_params // 8)
    assert model.step_state["params"].shape == (8, chunk)
    # generation reads the canonical (assembled) params — works on chunks
    out = np.asarray(model.generate(np.array([[1, 2, 3]]),
                                    max_new_tokens=4))
    assert out.shape == (1, 4) and (out >= 0).all() and (out < 32).all()


def test_per_worker_strategy_state_rejects_worker_count_change(
        tmp_path, mesh4, mesh8):
    """Round-4 review: exchange-strategy error-feedback state (onebit/
    topk/powersgd) is boxed per-worker with NO refit path — resuming on a
    different worker count must fail with the targeted message naming the
    limitation, not a raw leaf-shape mismatch."""
    d = str(tmp_path / "ckpt")
    m4, cfg4 = _make_tiny(False, mesh4, exch_strategy="topk")
    _train(m4, get_exchanger("bsp", cfg4), 3)
    m4.save(d, epoch=0, count=3)

    cfg8 = {"mesh": mesh8, "size": 8, "rank": 0, "verbose": False,
            "exch_strategy": "topk"}
    m8 = TinyModel(cfg8)
    m8.compile_iter_fns(get_exchanger("bsp", cfg8))
    with pytest.raises(ValueError, match="no.*worker-count refit"):
        m8.load(d)

    # same worker count stays fully resumable (the supported path)
    m4b, cfg4b = _make_tiny(False, mesh4, exch_strategy="topk")
    m4b.compile_iter_fns(get_exchanger("bsp", cfg4b))
    assert m4b.load(d) == 0
