"""Bucketed overlap-scheduled collectives (ISSUE 13 tentpole):
``parallel/buckets.py`` planner determinism and boundary cases, the
per-rule bucketed ≡ monolithic bit-identity contract, the
collectives-per-window count on a CPU devprof capture, and AOT cache key
sensitivity to ``bucket_bytes``.

The correctness contract this file pins (the way test_fused_exchange.py
pinned the PR 1 fusion): at fixed membership, the bucketed wire is a
SCHEDULE change only — every rule's exchange produces bit-identical
state whether the payload crosses as one monolith or as ~bucket_bytes
async start/done slices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import TinyModel
from theanompi_tpu import jax_compat
from theanompi_tpu.parallel import buckets
from theanompi_tpu.parallel.exchanger import (ASGD_Exchanger, BSP_Exchanger,
                                              EASGD_Exchanger,
                                              GOSGD_Exchanger)
from theanompi_tpu.parallel.mesh import worker_mesh
from theanompi_tpu.utils import devprof


# -- the planner ------------------------------------------------------------

def _tree(**leaves):
    return dict(leaves)


def test_plan_deterministic_and_pure():
    """Same tree-def + shapes/dtypes → the same plan, values ignored."""
    t1 = _tree(a=jnp.zeros(100), b=jnp.ones(200), c=jnp.zeros(50))
    t2 = _tree(a=jnp.full(100, 7.0), b=jnp.zeros(200), c=jnp.ones(50))
    p1 = buckets.plan_buckets(t1, 512)
    p2 = buckets.plan_buckets(t2, 512)
    assert p1 == p2
    assert buckets.plan_signature(p1) == buckets.plan_signature(p2)
    # abstract avals plan identically (the AOT prewarm venue traces
    # shapes, never values)
    p3 = buckets.plan_buckets(jax.eval_shape(lambda: t1), 512)
    assert p3 == p1
    # every non-empty leaf lands in exactly one bucket, in tree order
    covered = [i for b in p1.buckets for i in b.leaf_ids]
    assert sorted(covered) == covered
    assert set(covered) | set(p1.empty_leaf_ids) == set(range(p1.n_leaves))


def test_plan_oversized_leaf_is_single_leaf_bucket():
    """A leaf ≥ bucket_bytes becomes its OWN bucket — never split
    mid-leaf, never merged with neighbors."""
    t = _tree(small=jnp.zeros(8), big=jnp.zeros(4096), tail=jnp.zeros(8))
    p = buckets.plan_buckets(t, 1024)          # big leaf = 16 KiB > 1 KiB
    big_buckets = [b for b in p.buckets if 4096 in b.sizes]
    assert len(big_buckets) == 1
    assert big_buckets[0].sizes == (4096,)     # alone in its bucket
    assert len(big_buckets[0].leaf_ids) == 1


def test_plan_mixed_dtypes_never_share_a_bucket():
    t = _tree(a=jnp.zeros(10, jnp.float32), b=jnp.zeros(10, jnp.bfloat16),
              c=jnp.zeros(10, jnp.float32), d=jnp.zeros(10, jnp.float32))
    p = buckets.plan_buckets(t, 1 << 20)
    for b in p.buckets:
        leaf_dts = {np.dtype(jnp.zeros(1, jnp.bfloat16).dtype).name
                    if i == 1 else "float32" for i in b.leaf_ids}
        assert len(leaf_dts) == 1 and b.dtype in leaf_dts
    # d cannot rejoin c's float32 bucket across the bfloat16 boundary
    # (tree order is preserved), so at least 3 buckets exist
    assert p.n_buckets >= 3


def test_plan_empty_and_scalar_leaves():
    t = _tree(a=jnp.zeros(()), b=jnp.zeros((0,)), c=jnp.zeros((4, 0)),
              d=jnp.zeros(3))
    p = buckets.plan_buckets(t, 1 << 20)
    assert p.empty_leaf_ids == (1, 2)          # zero-size: nothing to wire
    assert sum(b.size for b in p.buckets) == 4  # scalar counts as 1
    # pack/unpack round-trips the empty leaves verbatim
    vecs = buckets.pack(t, p)
    out = buckets.unpack(vecs, t, p)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_pack_unpack_bit_exact_round_trip():
    rng = np.random.RandomState(0)
    t = _tree(a=jnp.asarray(rng.randn(7, 3), jnp.float32),
              b=jnp.asarray(rng.randn(11), jnp.float32),
              c=jnp.asarray(rng.randn(2, 2, 2), jnp.float32))
    p = buckets.plan_buckets(t, 64)
    out = buckets.unpack(buckets.pack(t, p), t, p)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_stable_under_membership_masking():
    """set_active_ranks scales VALUES, not shapes — the plan (and so the
    compiled collective schedule) is identical before and after a
    demotion, which is what keeps the masked-membership algebra exact
    per bucket."""
    mesh = worker_mesh(4)
    cfg = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
           "batch_size": 8, "bucket_bytes": 256, "sync_freq": 1}
    model = TinyModel(cfg)
    exch = EASGD_Exchanger(cfg)
    model.compile_iter_fns(exch)
    sig_full = buckets.plan_signature(
        buckets.plan_buckets(model.params, exch.bucket_bytes))
    n_full = exch.n_buckets()
    exch.set_active_ranks((0, 2))
    sig_masked = buckets.plan_signature(
        buckets.plan_buckets(model.params, exch.bucket_bytes))
    assert sig_full == sig_masked and exch.n_buckets() == n_full


# -- the jax_compat shim ----------------------------------------------------

def test_shim_sync_fallback_round_trip():
    """Without a real async surface the start eagerly reduces and the
    done unwraps — the pair is still the one calling convention the
    bucketed wire (and tpulint's pairing probe) sees."""
    mesh = worker_mesh(4)
    from jax.sharding import PartitionSpec as P

    def f(x):
        t = jax_compat.psum_start(x, "workers")
        return jax_compat.psum_done(t)

    g = jax.jit(jax_compat.shard_map(f, mesh=mesh, in_specs=P("workers"),
                                     out_specs=P()))
    x = jnp.arange(8.0)
    np.testing.assert_allclose(np.asarray(g(x))[0], x.reshape(4, 2).sum(0)[0])


# -- per-rule bit-identity --------------------------------------------------

def _run(exch_cls, n_steps=4, spc=1, active=None, **kw):
    mesh = worker_mesh(4)
    cfg = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
           "batch_size": 8, "steps_per_call": spc, **kw}
    model = TinyModel(cfg)
    exch = exch_cls(cfg)
    if active is not None:
        # demote BEFORE compile so both dispatch shapes trace the mask
        exch.mesh, exch.model = mesh, model
        exch.size = 4
        exch.set_active_ranks(active)
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    for count in range(spc, n_steps + 1, spc):
        model.train_iter(count, None)
        exch.exchange(None, count)
    return jax.device_get(model.step_state)


def _assert_bit_identical(a, b):
    for part in ("params", "opt_state", "extra"):
        for x, y in zip(jax.tree_util.tree_leaves(a[part]),
                        jax.tree_util.tree_leaves(b[part])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=part)


@pytest.mark.parametrize("exch_cls,cfg", [
    (BSP_Exchanger, {}),                                   # fused psum wire
    (BSP_Exchanger, {"exch_strategy": "nccl16"}),          # bf16 wire cast
    (BSP_Exchanger, {"exch_mode": "params"}),              # post-step wire
    (BSP_Exchanger, {"exch_strategy": "onebit"}),          # packed signs
    (BSP_Exchanger, {"exch_strategy": "topk"}),            # sparse rows
    (BSP_Exchanger, {"exch_strategy": "powersgd"}),        # dense remainder
    (EASGD_Exchanger, {"sync_freq": 2}),
    (ASGD_Exchanger, {"sync_freq": 1}),
    (GOSGD_Exchanger, {"exch_prob": 0.9}),
    (GOSGD_Exchanger, {"exch_prob": 0.9, "gosgd_peers": "iid"}),
    (GOSGD_Exchanger, {"exch_prob": 0.9, "gosgd_peers": "shift"}),
], ids=["bsp-allreduce", "bsp-nccl16", "bsp-params", "bsp-onebit",
        "bsp-topk", "bsp-powersgd", "easgd", "asgd", "gosgd-perm",
        "gosgd-iid", "gosgd-shift"])
def test_bucketed_equals_monolithic(exch_cls, cfg):
    """THE acceptance contract: tiny buckets (many slices on this model)
    vs the monolithic wire, bit-for-bit across params, optimizer and
    rule state after several exchanges."""
    mono = _run(exch_cls, **cfg)
    buck = _run(exch_cls, bucket_bytes=256, **cfg)
    _assert_bit_identical(mono, buck)


def test_bucketed_equals_monolithic_fused_cadence():
    """The in-scan fused cadence (steps_per_call > 1) traces the same
    exchange_body — bucketing must survive the lax.cond/scan wrapping."""
    mono = _run(EASGD_Exchanger, spc=4, sync_freq=2)
    buck = _run(EASGD_Exchanger, spc=4, sync_freq=2, bucket_bytes=256)
    _assert_bit_identical(mono, buck)


def test_bucketed_masked_membership_bit_identity():
    """Demoted-rank algebra per bucket: with ranks (0, 2) active, the
    bucketed and monolithic EASGD exchanges still agree bit-for-bit —
    the mask scales values upstream of the pack."""
    mono = _run(EASGD_Exchanger, sync_freq=1, active=(0, 2))
    buck = _run(EASGD_Exchanger, sync_freq=1, active=(0, 2),
                bucket_bytes=256)
    _assert_bit_identical(mono, buck)


# -- collectives-per-window (devprof CPU capture) ---------------------------

def _window_allreduce_count(bucket_bytes, k=3, n=4):
    """all-reduce executions over a k-dispatch BSP window, driving
    train_fn directly (train_iter's cost-mean helper dispatches its own
    tiny all-reduce that would pollute the count)."""
    mesh = worker_mesh(n)
    cfg = {"mesh": mesh, "size": n, "rank": 0, "verbose": False,
           "batch_size": 8, "bucket_bytes": bucket_bytes}
    model = TinyModel(cfg)
    exch = BSP_Exchanger(cfg)
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    from theanompi_tpu.parallel import steps
    batch = steps.put_batch(mesh, model.data.next_train_batch(0),
                            model.batch_spec())
    lr = jnp.float32(0.05)
    rng = jax.random.key(0)
    st, _, _ = model.train_fn(model.step_state, batch, lr, rng,
                              jnp.int32(1))
    jax.block_until_ready(st["params"])         # compile outside window
    with devprof.capture() as cap:
        for count in range(2, 2 + k):
            st, _, _ = model.train_fn(st, batch, lr, rng, jnp.int32(count))
        jax.block_until_ready(st["params"])
    assert cap.profile is not None
    ops = {o["op"]: o["count"] for o in cap.profile["top_ops"]}
    # balanced start/done pairs: every async start class has an
    # equal-count done twin (vacuous on a sync-lowering backend)
    for op, c in ops.items():
        if op.endswith("-start"):
            assert ops.get(op[:-len("-start")] + "-done") == c, ops
    return (sum(c for op, c in ops.items()
                if op.startswith("all-reduce")), exch.n_buckets())


def test_bucketed_bsp_window_collective_count():
    """Structure verified without hardware: a devprof capture of a
    bucketed BSP window shows exactly n_buckets all-reduce executions
    per dispatch per device — the planner's count, not the leaf count
    the monolithic wire issues."""
    k, n = 3, 4
    n_ar, n_buckets = _window_allreduce_count(1024, k=k, n=n)
    assert n_buckets and n_buckets > 1, "buckets must slice TinyModel"
    assert n_ar == n_buckets * k * n, (n_ar, n_buckets)
    # the monolithic control: leaf-wise psums (one per param leaf — more
    # collectives than the planner's packed buckets on this model)
    n_ar_mono, nb_mono = _window_allreduce_count(0, k=k, n=n)
    assert nb_mono is None
    n_leaves = len(jax.tree.leaves(TinyModel(
        {"mesh": worker_mesh(n), "size": n, "rank": 0, "verbose": False,
         "batch_size": 8}).params))
    assert n_ar_mono == n_leaves * k * n
    assert n_ar != n_ar_mono                    # the schedule moved


# -- AOT cache key sensitivity ----------------------------------------------



