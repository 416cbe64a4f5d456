"""Transformer LM (beyond-parity model family) through the full framework:
contract compliance, BSP training convergence, rule/exchanger compatibility.
"""

import jax
import numpy as np
import pytest

from theanompi_tpu.models.transformer_lm import LMData, TransformerLM
from theanompi_tpu.parallel.exchanger import BSP_Exchanger, get_exchanger
from theanompi_tpu.parallel.mesh import worker_mesh


def _model(n=4, **cfg):
    mesh = worker_mesh(n)
    config = {"mesh": mesh, "size": n, "rank": 0, "verbose": False,
              "batch_size": 8, "seq_len": 32, "vocab": 32, "d_model": 64,
              "n_layer": 2, "n_head": 4, "compute_dtype": "float32",
              "synthetic_train": 512, "synthetic_val": 128,
              "sync_each_iter": True, **cfg}
    import jax.numpy as jnp
    if config["compute_dtype"] == "float32":
        config["compute_dtype"] = jnp.float32
    m = TransformerLM(config)
    return m, config


def test_lm_data_next_token_alignment():
    d = LMData({"size": 1, "seq_len": 16, "vocab": 32,
                "synthetic_train": 64, "synthetic_val": 64}, batch_size=8)
    d.shuffle_data(0)
    b = d.next_train_batch(1)
    assert b["x"].dtype == np.int32 and b["y"].dtype == np.int32
    assert b["x"].shape == b["y"].shape == (8, 16)
    # y is x shifted by one within the underlying sequence: where no noise
    # flip hit, y[t] == (x[t]+1) % vocab — check it holds for most positions
    match = (b["y"] == (b["x"] + 1) % 32).mean()
    assert match > 0.8, match


def test_lm_trains_under_bsp():
    m, config = _model()
    m.compile_iter_fns(BSP_Exchanger(config))
    m.data.shuffle_data(0)
    costs = []
    for i in range(1, 13):
        m.train_iter(i, None)
        costs.append(float(m.current_info["cost"]))
    # the modular-increment rule is easy: loss must drop well below ln(V)
    assert costs[-1] < costs[0] * 0.6, costs
    m.begin_val()
    m.val_iter(1, None)
    m.end_val()


@pytest.mark.parametrize("rule", ["easgd", "gosgd"])
def test_lm_runs_under_async_rules(rule):
    m, config = _model(sync_freq=2, exch_prob=0.8)
    exch = get_exchanger(rule, config)
    m.compile_iter_fns(exch)
    m.data.shuffle_data(0)
    for i in range(1, 5):
        m.train_iter(i, None)
        exch.exchange(None, i)
    assert np.isfinite(float(m.current_info["cost"]))


def test_lm_session_api():
    """Through the 3-call rule API, like any zoo model."""
    import theanompi_tpu as tmpi
    rule = tmpi.BSP()
    rule.init(devices=4, modelfile="theanompi_tpu.models.transformer_lm",
              modelclass="TransformerLM", epochs=1, batch_size=8,
              seq_len=32, vocab=32, d_model=64, n_layer=1, n_head=4,
              compute_dtype="float32", synthetic_train=256,
              synthetic_val=128, verbose=False, scale_lr=False)
    rec = rule.wait()
    assert rec.epoch_records and np.isfinite(rec.epoch_records[-1]["val_cost"])


def test_remat_is_loss_equivalent(mesh4):
    """remat=True (per-block jax.checkpoint) changes memory, not math."""
    import jax.numpy as jnp
    from theanompi_tpu.models.transformer_lm import TransformerLM
    from theanompi_tpu.parallel.exchanger import BSP_Exchanger

    def run(remat):
        cfg = {"mesh": mesh4, "size": 4, "rank": 0, "verbose": False,
               "remat": remat, "batch_size": 8, "seq_len": 16, "vocab": 32,
               "d_model": 32, "n_head": 4, "n_layer": 2,
               "synthetic_train": 64, "compute_dtype": jnp.float32}
        m = TransformerLM(cfg)
        m.compile_iter_fns(BSP_Exchanger(cfg))
        m.data.shuffle_data(0)
        costs = []
        for i in range(4):
            m.train_iter(i, None)
            costs.append(float(m.current_info["cost"]))
        return costs

    np.testing.assert_allclose(run(True), run(False), rtol=1e-6, atol=1e-8)
