"""The lazy jit is the only compile path: per rule and ``steps_per_call``,
the train program compiles once, the model's own avals lower to the program
the live arguments run, and a second ``compile_iter_fns`` trains on."""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import TinyModel
from theanompi_tpu.parallel import steps
from theanompi_tpu.parallel.exchanger import get_exchanger
from theanompi_tpu.parallel.mesh import worker_mesh
from theanompi_tpu.utils import telemetry


def _xla_compiles():
    return telemetry.totals().get("compile.xla", (0, 0))[0]


def _live_args(model, spc):
    batches = [model.data.next_train_batch(j + 1) for j in range(spc)]
    dev = steps.put_batch(model.mesh, batches[0], model.batch_spec()) \
        if spc == 1 else steps.put_batch_stack(model.mesh, batches,
                                               model.batch_spec())
    return (model.step_state, dev, jnp.float32(model.current_lr),
            model._step_rng, jnp.int32(spc))


@pytest.mark.parametrize("spc", [1, 4])
@pytest.mark.parametrize("rule", ["bsp", "easgd", "asgd", "gosgd"])
def test_train_program_compiles_once_and_avals_lower_to_it(rule, spc):
    config = {"mesh": worker_mesh(8), "size": 8, "rank": 0, "verbose": False,
              "batch_size": 8, "rule": rule, "steps_per_call": spc}
    model = TinyModel(config)
    exchanger = get_exchanger(rule, config)
    model.compile_iter_fns(exchanger)
    model.data.shuffle_data(0)

    # (ii) the avals the harness's scope join lowers with are the live
    # arguments' own: one program text
    from_avals = model.train_fn.lower(
        *model._train_input_avals(spc, exchanger)).as_text()
    assert from_avals == model.train_fn.lower(
        *_live_args(model, spc)).as_text()

    # (i) the first dispatch compiles the train program; the next two
    # compile nothing at all, small programs included
    model.train_iter(spc, None)
    assert np.isfinite(float(model.current_info["cost"]))
    assert model.train_fn._cache_size() == 1
    after_first = _xla_compiles()
    assert after_first >= 1
    for call in (2, 3):
        model.train_iter(call * spc, None)
    assert np.isfinite(float(model.current_info["cost"]))
    assert model.train_fn._cache_size() == 1
    assert _xla_compiles() == after_first

    # (iii) a recompile at the other steps_per_call builds a new program
    # over the same model and trains on, finite
    other = 4 if spc == 1 else 1
    model.steps_per_call = other
    model.compile_iter_fns(exchanger)
    model.train_iter(other, None)
    assert model.train_fn._cache_size() == 1
    assert np.isfinite(float(model.current_info["cost"]))
