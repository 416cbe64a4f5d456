"""A wide FC layer's gradient travels as its all-gathered operands
(``strategies.gather_engages``, ``layers.GatheredGrads``,
``BSP_Exchanger.gathered_grads``; PERF.md §6, PR 32).

Held here, on the CPU mesh: the gathered path trains as the all-reduce
does and keeps replicas bit-identical; who may take it and who never
does; what the lowered step holds; what the counters say.  The floor
(8 MiB a leaf) is lowered on the exchanger instance: no key sets it.
"""

import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tests.conftest import SyntheticData, TinyModel
from theanompi_tpu.models import layers as L
from theanompi_tpu.parallel import exchanger as X
from theanompi_tpu.parallel.mesh import worker_mesh
from theanompi_tpu.utils import telemetry

GATHERED = ("['fc1']['w']", "['fc2']['w']")


class ConvFC(TinyModel):
    """Conv, then two FCs wide against their 4 rows a chip (they engage
    once the floor is out of the way) and a 256x2 head that never does."""

    batch_size = 4

    def build_model(self):
        cd = self.config.get("compute_dtype", jnp.float32)
        self.seq = L.Sequential([
            L.Reshape((4, 4, 1)),
            L.Conv(1, 8, 3, w_init="he", compute_dtype=cd, name="conv"),
            L.Flatten(),
            L.FC(128, 512, w_init="he", compute_dtype=cd, name="fc1"),
            L.Dropout(0.5, name="drop"),
            L.FC(512, 256, w_init="he", compute_dtype=cd, name="fc2"),
            L.FC(256, 2, w_init=("normal", 0.01), activation=None,
                 compute_dtype=cd, name="out"),
        ])
        self.data = SyntheticData(self.config, self.batch_size, n_train=256)


def _build(n, floor=0, exch_cls=X.BSP_Exchanger, absent=False, **cfg):
    mesh = worker_mesh(n)
    config = {"mesh": mesh, "size": n, "rank": 0, "verbose": False, **cfg}
    model = ConvFC(config)
    exch = exch_cls(config)
    if floor is not None:
        exch.gather_min_bytes = floor
    if absent:
        exch.gathered_grads = lambda: None
    model.compile_iter_fns(exch)
    model.data.shuffle_data(0)
    return model, exch


def _train(n, steps=3, **kw):
    model, _ = _build(n, **kw)
    costs = []
    for count in range(1, steps + 1):
        model.train_iter(count, None)
        costs.append(float(model.current_info["cost"]))
    return jax.device_get(model.step_state), costs


def _lowered(model):
    return model.train_fn.lower(*model._train_input_avals(1)).as_text()


def _ops(text, op):
    return len(re.findall(rf"stablehlo\.{op}\b", text))


def _reduced_shapes(text):
    """Operand shapes of the text's all-reduces (a region op: its type
    follows the region)."""
    return re.findall(
        r"stablehlo\.all_reduce.*?\}\) : \(tensor<([^>]*)>", text, re.S)


def _comm_counts():
    t = telemetry.totals()
    return {k: t.get(k, (0, 0))[0] for k in (
        "comm.gathered_leaves", "comm.gathered_bytes",
        "comm.allreduce_bytes")}


# -- (a) the gathered path is the all-reduce path ---------------------------

@pytest.mark.parametrize("n,cfg", [
    (4, {}), (8, {}), (4, {"n_subb": 2}), (8, {"n_subb": 2}),
], ids=["4dev", "8dev", "4dev-subb2", "8dev-subb2"])
def test_gathered_steps_equal_allreduce_steps(n, cfg):
    plain, plain_costs = _train(n, floor=None, **cfg)   # floor 8 MiB: off
    got, costs = _train(n, floor=0, **cfg)
    np.testing.assert_allclose(costs, plain_costs, rtol=1e-5)
    for part in ("params", "opt_state"):
        for a, b in zip(jax.tree.leaves(got[part]),
                        jax.tree.leaves(plain[part])):
            np.testing.assert_allclose(
                a, b, rtol=1e-5, atol=1e-5 * np.max(np.abs(b)))
            for w in range(1, n):
                np.testing.assert_array_equal(a[w], a[0])


# -- (b) who takes it -------------------------------------------------------

def _prepared(exch_cls, n=4, model=None, **cfg):
    mesh = cfg.pop("mesh", None) or worker_mesh(n)
    exch = exch_cls({"mesh": mesh, "size": n, **cfg})
    exch.gather_min_bytes = 0
    if model is None:
        model = types.SimpleNamespace(param_specs=lambda: None, params={})
    exch.mesh, exch.model, exch.size = mesh, model, n
    return exch


@pytest.mark.parametrize("exch_cls,cfg", [
    (X.EASGD_Exchanger, {}), (X.ASGD_Exchanger, {}), (X.GOSGD_Exchanger, {}),
    (X.BSP_Exchanger, {"exch_mode": "params"}),
    (X.BSP_Exchanger, {"exch_strategy": "nccl16"}),
    (X.BSP_Exchanger, {"exch_strategy": "ring"}),
    (X.BSP_Exchanger, {"exch_strategy": "onebit"}),
    (X.BSP_Exchanger, {"exch_strategy": "none"}),
    (X.BSP_Exchanger, {"fsdp": True}),
    (X.BSP_Exchanger, {"zero_opt": True}),
    (X.BSP_Exchanger, {"update_sharding": True}),
    (X.BSP_Exchanger, {"n": 1}),
], ids=["easgd", "asgd", "gosgd", "bsp-params", "allreduce16", "ring",
        "onebit", "none", "fsdp", "zero_opt", "update_sharding", "n1"])
def test_never_gathered(exch_cls, cfg):
    assert _prepared(exch_cls, **cfg).gathered_grads() is None


def test_never_gathered_for_a_model_parallel_model():
    tp_model = types.SimpleNamespace(
        param_specs=lambda: {"w": P("model")}, params={})
    assert _prepared(X.BSP_Exchanger, model=tp_model).gathered_grads() \
        is None
    assert _prepared(X.BSP_Exchanger, n=2,
                     mesh=worker_mesh(2, tp=2)).gathered_grads() is None


def test_gathered_under_plain_bsp_and_its_aliases():
    for name in ("allreduce", "ar", "nccl32"):
        g = _prepared(X.BSP_Exchanger, exch_strategy=name).gathered_grads()
        assert isinstance(g, L.GatheredGrads) and g.axis == "workers"


def test_only_the_loss_tree_own_rank2_float32_leaves_are_taken():
    g = L.GatheredGrads("workers", lambda *a: True)
    w = jnp.zeros((8, 4))
    params = {"fc": {"w": w, "b": jnp.zeros(4)},
              "half": {"w": jnp.zeros((8, 4), jnp.bfloat16)},
              "conv": {"w": jnp.zeros((1, 1, 8, 4))}}
    seen = {}

    def loss(p):
        seen["own"] = g.take(p["fc"]["w"], 2, 4)
        seen["copy"] = g.take(p["fc"]["w"] + 0, 2, 4)
        seen["half"] = g.take(p["half"]["w"], 2, 2)
        seen["conv"] = g.take(p["conv"]["w"], 2, 4)
        return 0.0

    g.loss(loss)(params)
    assert seen == {"own": True, "copy": False, "half": False,
                    "conv": False}
    assert g.taken == {"['fc']['w']": (2, 8, 4, 4)}
    assert not g.take(w, 2, 4)        # closed: nothing is watched
    assert L._gathered is None


def test_fc_with_rank3_input_and_row_fc_keep_their_product():
    from theanompi_tpu.parallel import tp
    g = L.GatheredGrads("workers", lambda *a: True)
    fc = L.FC(8, 4, compute_dtype=jnp.float32)
    row = tp.RowFC(8, 4, compute_dtype=jnp.float32, axis="workers")
    params = {"fc": fc.init(jax.random.key(0))}

    def loss(p):
        fc.pre_activation(p["fc"], jnp.ones((2, 3, 8)))
        assert not g.taken
        fc.pre_activation(p["fc"], jnp.ones((2, 8)))
        return 0.0

    g.loss(loss)(params)
    assert list(g.taken) == ["['fc']['w']"]
    assert row.pre_activation.__func__ is not L.FC.pre_activation


# -- (c) what the lowered step holds ----------------------------------------

@pytest.mark.parametrize("cfg", [{}, {"n_subb": 2}], ids=["subb1", "subb2"])
def test_lowered_step_gathers_twice_a_leaf_and_reduces_the_rest(cfg):
    plain = _lowered(_build(4, floor=None, **cfg)[0])
    got = _lowered(_build(4, floor=0, **cfg)[0])
    n_leaves = 8                     # conv, fc1, fc2, out: w and b each
    assert _ops(plain, "all_gather") == 0
    assert _ops(plain, "all_reduce") == n_leaves
    assert _ops(got, "all_gather") == 2 * len(GATHERED)
    assert _ops(got, "all_reduce") == n_leaves - len(GATHERED)
    # no all-reduce of a taken leaf's shape is left
    for shape in ("128x512xf32", "512x256xf32"):
        assert shape in _reduced_shapes(plain)
        assert shape not in _reduced_shapes(got)


def test_one_worker_lowering_is_the_lowering_without_the_mechanism():
    with_it = _lowered(_build(1, floor=0)[0])
    without = _lowered(_build(1, floor=0, absent=True)[0])
    assert with_it == without
    assert _ops(with_it, "all_gather") == 0
    # and on four the mechanism is what makes the difference
    assert _lowered(_build(4, floor=0)[0]) \
        != _lowered(_build(4, floor=0, absent=True)[0])


# -- (d) the counters -------------------------------------------------------

@pytest.mark.parametrize("n,n_subb,floor,leaves", [
    (4, 1, 0, 2), (8, 2, 0, 2), (4, 1, None, 0), (1, 1, 0, 0),
], ids=["4dev", "8dev-subb2", "4dev-floor", "1dev"])
def test_wire_counters_written_once_a_prepared_step(n, n_subb, floor,
                                                    leaves):
    before = _comm_counts()
    model, _ = _build(n, floor=floor, n_subb=n_subb)
    _lowered(model)
    _lowered(model)                  # a second trace counts nothing
    delta = {k: v - before[k] for k, v in _comm_counts().items()}
    total = 4 * sum(p.size for p in jax.tree.leaves(model.params))
    fcs = 4 * (128 * 512 + 512 * 256)
    rows = 4 // n_subb
    assert delta == {
        "comm.gathered_leaves": leaves,
        "comm.gathered_bytes": leaves and n_subb * n * rows * 4 * (
            (128 + 512) + (512 + 256)),
        "comm.allreduce_bytes": total - (fcs if leaves else 0),
    }


def test_numerics_plane_reads_a_worker_share_of_a_summed_leaf():
    """The health plane (``numerics=true``) is handed a taken leaf as its
    mean over the workers, not the sum: a norm of a mean is at most the
    largest local norm, so with the other leaves local as before no
    worker reads above sqrt(2) of the plain path's largest (the sum of
    eight would read several times it: the FCs hold nearly every
    parameter)."""
    norms = {}
    for floor in (None, 0):
        model, _ = _build(8, floor=floor, numerics=True)
        model.train_iter(1, None)
        norms[floor] = np.asarray(
            jax.device_get(model.numerics_aux)["grad_norm"])
    assert norms[0].shape == (8,) and np.all(norms[0] > 0)
    assert norms[0].max() <= 1.5 * norms[None].max()
