"""Leaf-wise update-plane sharding (parallel/update_sharding.py,
docs/design.md §23): the per-leaf schema stamps correctly, the
shard/rebuild round trip is the identity bit for bit, and training with
the sharded update plane is assert_array_equal-identical to the
replicated path — for BSP moments, the EASGD/ASGD centers, and a
compressed rule with error feedback — including under steps_per_call
fused dispatch.  Fast suite: tier-1 runs this file (unlike
tests/test_zero.py, which stays slow-marked)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tests.conftest import TinyModel
from theanompi_tpu.parallel import steps
from theanompi_tpu.parallel import update_sharding as us
from theanompi_tpu.parallel.exchanger import BSP_Exchanger, get_exchanger
from theanompi_tpu.parallel.mesh import WORKER_AXIS, worker_mesh
from theanompi_tpu.utils import devprof


def _train(model, exch, n_steps):
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    costs = []
    for i in range(n_steps):
        model.train_iter(i, None)
        costs.append(float(model.current_info["cost"]))
    return costs


def _make_tiny(ushard, mesh, **kw):
    cfg = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
           "update_sharding": ushard, "ushard_min_bytes": 0, **kw}
    return TinyModel(cfg), cfg


def _assert_trees_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


# ---------------------------------------------------------------------------
# the schema itself
# ---------------------------------------------------------------------------

def test_plan_stamps_per_leaf_schema():
    """Ragged P=10, N=4: chunk ceil(10/4)=3, pad 2, spec P(workers); a
    3-element leaf (< N) and a scalar stay replicated with P()."""
    tree = {"w": np.zeros(10, np.float32), "b": np.zeros(3, np.float32),
            "s": np.float32(0.0)}
    plan = us.plan_tree(tree, 4, min_bytes=0)
    by_path = {lp.path: lp for lp in plan.leaves}
    w = by_path["['w']"]
    assert (w.sharded, w.chunk, w.pad, w.spec) == (True, 3, 2,
                                                   P(WORKER_AXIS))
    assert not by_path["['b']"].sharded and by_path["['b']"].spec == P()
    assert not by_path["['s']"].sharded
    assert plan.any_sharded
    specs = plan.specs(tree)
    assert specs["w"] == P(WORKER_AXIS) and specs["b"] == P()
    # the byte threshold moves leaves back to replicated wholesale
    assert not us.plan_tree(tree, 4, min_bytes=1 << 20).any_sharded
    # one worker: nothing to partition
    assert not us.plan_tree(tree, 1, min_bytes=0).any_sharded


def test_host_boxed_roundtrip_identity():
    """shard_host_boxed → unshard_boxed is the identity (ragged leaf:
    the [N, chunk] rows carry the pad, the rebuild trims it)."""
    rng = np.random.RandomState(0)
    tree = {"w": rng.randn(10).astype(np.float32),
            "b": rng.randn(3).astype(np.float32)}
    plan = us.plan_tree(tree, 4, min_bytes=0)
    boxed = us.shard_host_boxed(tree, plan)
    assert boxed["w"].shape == (4, 3)        # rows ARE the partition
    assert boxed["b"].shape == (4, 3)        # replicated rows
    _assert_trees_equal(us.unshard_boxed(boxed, plan), tree)


def test_traced_roundtrip_identity():
    """shard_tree → unshard_tree under shard_map rebuilds every leaf bit
    for bit (the fused per-dtype allgather is value-exact)."""
    from theanompi_tpu.jax_compat import shard_map
    mesh = worker_mesh(4)
    rng = np.random.RandomState(1)
    tree = {"w": rng.randn(4, 5).astype(np.float32),
            "m": rng.randn(8).astype(np.float32),
            "c": rng.randn(6).astype(np.int32)}
    plan = us.plan_tree(tree, 4, min_bytes=0)

    def body(t):
        rank = jax.lax.axis_index(WORKER_AXIS)
        full = us.unshard_tree(us.shard_tree(t, plan, rank), plan,
                               WORKER_AXIS)
        return jax.tree.map(lambda x: x[None], full)   # boxed per worker

    out = shard_map(body, mesh=mesh, in_specs=(P(),),
                    out_specs=P(WORKER_AXIS))(tree)
    for row in range(4):                     # every worker rebuilt it all
        _assert_trees_equal(jax.tree.map(lambda x: x[row], out), tree)
    assert out["c"].dtype == np.int32        # dtypes preserved per lane


def test_ushard_row_columns_schema():
    """The report vocabulary is pinned in the jax-free schema home and
    stays disjoint from the other column families."""
    cols = set(devprof.USHARD_ROW_COLUMNS)
    assert cols == {"update_state_bytes_per_chip",
                    "update_state_bytes_replicated", "update_state_shrink"}
    assert not cols & set(devprof.PIPELINE_ROW_COLUMNS)


# ---------------------------------------------------------------------------
# bit-identity vs the replicated update plane, per rule
# ---------------------------------------------------------------------------

def test_bsp_bit_equal_under_fused_dispatch(mesh4):
    """BSP momentum with the sharded optimizer, under steps_per_call=2
    fused dispatch: cost trace and final params EXACTLY equal the
    replicated run (elementwise math on disjoint chunks + value-exact
    gather; no reduction-order change)."""
    base, _ = _make_tiny(False, mesh4, optimizer="momentum",
                         steps_per_call=2)
    shard, _ = _make_tiny(True, mesh4, optimizer="momentum",
                          steps_per_call=2)
    assert shard._ushard_plan is not None
    c0 = _train(base, BSP_Exchanger(base.config), 6)
    c1 = _train(shard, BSP_Exchanger(shard.config), 6)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    _assert_trees_equal(
        steps.unbox(jax.device_get(base.step_state["params"])),
        steps.unbox(jax.device_get(shard.step_state["params"])))


@pytest.mark.parametrize("rule", ["easgd", "asgd"])
def test_center_rules_bit_equal(mesh4, rule):
    """EASGD/ASGD with the center sharded into per-worker chunks: cost
    trace, final params, and the canonical CENTER itself all exactly
    equal the replicated run."""
    kw = {"rule": rule, "sync_freq": 2}
    base, bcfg = _make_tiny(False, mesh4, **kw)
    shard, scfg = _make_tiny(True, mesh4, **kw)
    c0 = _train(base, get_exchanger(rule, bcfg), 6)
    c1 = _train(shard, get_exchanger(rule, scfg), 6)
    assert shard.exchanger.update_plan() is not None
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    _assert_trees_equal(
        steps.unbox(jax.device_get(base.step_state["params"])),
        steps.unbox(jax.device_get(shard.step_state["params"])))
    _assert_trees_equal(
        jax.device_get(base.exchanger.canonical_params(base.step_state)),
        jax.device_get(shard.exchanger.canonical_params(shard.step_state)))


def test_powersgd_ef_bit_equal(mesh4):
    """BSP + powersgd compressed wire: the moments shard, the per-worker
    error-feedback buffers stay LOCAL (never planned — they diverge per
    worker by construction), and training is bit-equal."""
    kw = {"optimizer": "momentum", "exch_strategy": "powersgd"}
    base, _ = _make_tiny(False, mesh4, **kw)
    shard, _ = _make_tiny(True, mesh4, **kw)
    assert shard._ushard_plan is not None
    c0 = _train(base, BSP_Exchanger(base.config), 6)
    c1 = _train(shard, BSP_Exchanger(shard.config), 6)
    # the EF buffers are not in any plan: BSP declares nothing shardable
    assert shard.exchanger.update_plan() is None
    assert shard.exchanger.shardable_extra() == ()
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    _assert_trees_equal(
        steps.unbox(jax.device_get(base.step_state["params"])),
        steps.unbox(jax.device_get(shard.step_state["params"])))


# ---------------------------------------------------------------------------
# memory: the headline ~N× shrink, measured
# ---------------------------------------------------------------------------

def test_update_state_memory_shrinks(mesh4):
    model, _ = _make_tiny(True, mesh4, optimizer="momentum")
    model.compile_iter_fns(BSP_Exchanger(model.config))
    # the boxed [N, chunk] layout IS the partition, sharded on the data
    # axis — per-chip bytes are boxed/N (momentum state: a velocity tree)
    vel = model.step_state["opt_state"]["opt"]
    chunks = [l for l in jax.tree.leaves(vel)
              if l.sharding.spec == (WORKER_AXIS,)]
    assert chunks and all(l.ndim == 2 for l in chunks)
    report = devprof.update_state_report(model)
    assert set(report) == set(devprof.USHARD_ROW_COLUMNS)
    # TinyModel at N=4: every leaf but the 2-element bias shards → ~3.9×
    assert report["update_state_shrink"] >= 3.0, report
    # control: the replicated run reports ~1×
    base, _ = _make_tiny(False, mesh4, optimizer="momentum")
    base.compile_iter_fns(BSP_Exchanger(base.config))
    flat = devprof.update_state_report(base)
    assert flat["update_state_shrink"] <= 1.01, flat


# ---------------------------------------------------------------------------
# cache keys and config guards
# ---------------------------------------------------------------------------



def test_rejects_zero_opt_composition(mesh4):
    """zero_opt and update_sharding are two layouts of the SAME memory —
    enabling both is a config error, loudly."""
    with pytest.raises(AssertionError, match="zero_opt"):
        _make_tiny(True, mesh4, zero_opt=True)


def test_min_bytes_threshold_disables(mesh4):
    """A threshold above every leaf leaves the plan inactive: identical
    programs, no `ushard` reshaping, nothing sharded."""
    model, _ = _make_tiny(True, mesh4, optimizer="momentum",
                          ushard_min_bytes=1 << 30)
    assert model._ushard_plan is None
