"""Fused compression-pipeline contracts (ISSUE 18, docs/design.md §24).

Three layers, all runnable on the CPU venue:

* the NEW jnp oracles must be bit-exact (or honestly allclose, where the
  fold reassociates a division) against the UNFUSED formulas they
  replaced in ``parallel/strategies.py`` — the oracles are the non-TPU
  dispatch targets, so these identities are what keeps every CPU/
  forced-oracle run on the pre-fusion numbers;
* the dispatch plumbing: the memoized ``THEANOMPI_TPU_NO_PALLAS`` gate;
* the traffic model: :data:`devprof.COMPRESS_ROW_COLUMNS` schema (pinned
  disjoint from the other row vocabularies), the modeled ≥2× HBM
  shrinks the acceptance gates on, and the live-model report.

The kernel-vs-oracle bit-equality tests live in tests/test_strategies.py
(interpret mode, TPU venue) — the tpulint ``oracle-pair`` checker pins
that every ``PALLAS_ORACLES`` entry has one.
"""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import _pallas_util, compress, factor_pack
from theanompi_tpu.parallel import strategies
from theanompi_tpu.utils import devprof

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def clean_dispatch(monkeypatch):
    """Each case owns the env gate and the process-wide memo; both are
    restored afterwards so test order can't leak a forced-oracle state."""
    monkeypatch.delenv("THEANOMPI_TPU_NO_PALLAS", raising=False)
    _pallas_util.reset_dispatch_cache()
    yield monkeypatch
    _pallas_util.reset_dispatch_cache()


# ---------------------------------------------------------------------------
# oracle vs the unfused legacy formulas
# ---------------------------------------------------------------------------

def test_encode_oracle_matches_legacy_pack():
    r = np.random.RandomState(0)
    flat = jnp.asarray(r.randn(compress.PACK_ALIGN).astype(np.float32))
    state = jnp.asarray(r.randn(compress.PACK_ALIGN).astype(np.float32))
    packed, absc = compress.pack_signs_encode_jnp(flat, state)
    c = flat + state
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(compress.pack_signs_jnp(c)))
    np.testing.assert_array_equal(np.asarray(absc), np.abs(np.asarray(c)))


def test_signed_residual_oracle_bit_exact_vs_legacy():
    """``where(bit, |c|−s, s−|c|)`` ≡ ``c − s·sign(where(c==0,1,c))`` in
    IEEE fp32, including c == 0 (packed bit 1) — the identity the fused
    onebit state update rests on."""
    r = np.random.RandomState(1)
    c = r.randn(compress.PACK_ALIGN).astype(np.float32)
    c[::53] = 0.0
    c = jnp.asarray(c)
    scale = jnp.float32(0.123)
    legacy = c - scale * jnp.sign(jnp.where(c == 0, 1.0, c))
    got = compress.signed_residual_jnp(jnp.abs(c),
                                       compress.pack_signs_jnp(c), scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(legacy))


def test_weighted_mean_oracle_matches_sum_then_divide():
    """The /size fold moves the division from the decoded vector onto the
    [w]-length scales — allclose, not bit-equal (last-ulp reassociation),
    which is why the PINNED contracts compare fused-vs-fused."""
    r = np.random.RandomState(2)
    w, size = 4, 4
    c = r.randn(w, compress.PACK_ALIGN).astype(np.float32)
    scales = jnp.asarray(np.abs(r.randn(w)).astype(np.float32) + 0.1)
    packed = jnp.stack(
        [compress.pack_signs_jnp(jnp.asarray(ci)) for ci in c])
    got = compress.unpack_signs_weighted_mean_jnp(packed, scales, size)
    legacy = compress.unpack_signs_weighted_sum_jnp(packed, scales) / size
    np.testing.assert_allclose(np.asarray(got), np.asarray(legacy),
                               rtol=1e-6, atol=1e-7)


def test_topk_encode_oracle_selection_and_residual():
    r = np.random.RandomState(3)
    rows, chunk, k = 4, 256, 8
    c2 = jnp.asarray(r.randn(rows, chunk).astype(np.float32))
    vals, idx, new_c2 = compress.topk_encode(c2, k)
    assert vals.dtype == jnp.bfloat16 and idx.dtype == jnp.int16
    _, want_idx = jax.lax.top_k(jnp.abs(c2), k)
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.asarray(want_idx).astype(np.int16))
    c2n, idxn = np.asarray(c2), np.asarray(idx)
    new_n = np.asarray(new_c2)
    for rr in range(rows):
        sel = c2n[rr, idxn[rr].astype(np.int64)]
        np.testing.assert_array_equal(
            np.asarray(vals[rr], dtype=np.float32),
            sel.astype(jnp.bfloat16).astype(np.float32))
        # selected slots hold the bf16 rounding residual, others untouched
        np.testing.assert_array_equal(
            new_n[rr, idxn[rr].astype(np.int64)],
            sel - np.asarray(vals[rr], dtype=np.float32))
        mask = np.ones(chunk, bool)
        mask[idxn[rr].astype(np.int64)] = False
        np.testing.assert_array_equal(new_n[rr, mask], c2n[rr, mask])


def test_topk_encode_oracle_tie_break_lower_index():
    c2 = jnp.asarray([[1.0, -2.0, 2.0, 0.5]], jnp.float32)
    _, idx, _ = compress.topk_encode(c2, 2)
    # |−2| ties |2|: lax.top_k (and the kernel's min-index argmax) picks
    # the lower index first
    np.testing.assert_array_equal(np.asarray(idx), [[1, 2]])


def test_topk_decode_oracle_matches_numpy_scatter():
    r = np.random.RandomState(4)
    w, rows, chunk, k = 3, 2, 128, 16
    encs = [compress.topk_encode(
        jnp.asarray(r.randn(rows, chunk).astype(np.float32)), k)
        for _ in range(w)]
    all_vals = jnp.stack([e[0] for e in encs])
    all_idx = jnp.stack([e[1] for e in encs])
    got = compress.topk_decode(all_vals, all_idx, chunk, size=w)
    dense = np.zeros(rows * chunk, np.float32)
    vn = np.asarray(all_vals, dtype=np.float32)
    inn = np.asarray(all_idx)
    for wi in range(w):
        for rr in range(rows):
            for j in range(k):
                dense[rr * chunk + inn[wi, rr, j]] += vn[wi, rr, j]
    np.testing.assert_allclose(np.asarray(got), dense / w,
                               rtol=1e-6, atol=1e-7)


def test_topk_decode_size_fold_is_elementwise_divide():
    r = np.random.RandomState(5)
    vals, idx, _ = compress.topk_encode(
        jnp.asarray(r.randn(2, 128).astype(np.float32)), 8)
    all_vals, all_idx = vals[None], idx[None]
    folded = compress.topk_decode(all_vals, all_idx, 128, size=4)
    unfolded = compress.topk_decode(all_vals, all_idx, 128, size=1) / 4
    np.testing.assert_array_equal(np.asarray(folded), np.asarray(unfolded))


def test_matmul_pack_oracle_pads_with_exact_zeros():
    r = np.random.RandomState(6)
    m = jnp.asarray(r.randn(13, 32).astype(np.float32))
    q = jnp.asarray(r.randn(32, 2).astype(np.float32))
    out = factor_pack.matmul_pack_jnp(m, q, factor_pack.pad_rows(13))
    assert out.shape == (16, 2)
    np.testing.assert_array_equal(np.asarray(out)[13:], 0.0)
    np.testing.assert_allclose(np.asarray(out)[:13], np.asarray(m @ q),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# dispatch plumbing: env gate, memo
# ---------------------------------------------------------------------------

def test_public_dispatchers_match_with_no_pallas_toggled(clean_dispatch):
    """Every public fused entry point must return identical bits with the
    forced-oracle gate on and off.  On this CPU venue both sides resolve
    to the oracle, so the equality is trivially bit-exact — what the test
    pins is the dispatch seam itself: the env gate + memo reset reaches
    every entry point and flips nothing numerically."""
    r = np.random.RandomState(7)
    flat = jnp.asarray(r.randn(compress.PACK_ALIGN).astype(np.float32))
    state = jnp.asarray(r.randn(compress.PACK_ALIGN).astype(np.float32))
    c2 = jnp.asarray(r.randn(2, 256).astype(np.float32))
    m = jnp.asarray(r.randn(12, 32).astype(np.float32))
    q = jnp.asarray(r.randn(32, 2).astype(np.float32))

    def run_all():
        packed, absc = compress.pack_signs_encode(flat, state)
        scale = jnp.mean(absc)
        res = compress.signed_residual(absc, packed, scale)
        mean = compress.unpack_signs_weighted_mean(
            packed[None], scale[None], 2)
        vals, idx, new_c2 = compress.topk_encode(c2, 8)
        dense = compress.topk_decode(vals[None], idx[None], 256, size=2)
        tile = factor_pack.matmul_pack(m, q)
        return [packed, absc, res, mean, vals, idx, new_c2, dense, tile]

    base = run_all()
    clean_dispatch.setenv("THEANOMPI_TPU_NO_PALLAS", "1")
    _pallas_util.reset_dispatch_cache()
    assert _pallas_util.dispatch_pallas() is False
    forced = run_all()
    for a, b in zip(base, forced):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dispatch_gate_is_memoized_until_reset(clean_dispatch):
    assert _pallas_util.dispatch_pallas() is False      # CPU venue
    # flipping the env WITHOUT a reset must not re-read it: a per-call
    # os.environ lookup was the satellite this memo removed
    clean_dispatch.setenv("THEANOMPI_TPU_NO_PALLAS", "1")
    assert _pallas_util.dispatch_pallas() is False
    assert _pallas_util._DISPATCH_MEMO is False
    _pallas_util.reset_dispatch_cache()
    assert _pallas_util._DISPATCH_MEMO is None
    assert _pallas_util.dispatch_pallas() is False


def test_topk_chunk_over_int16_range_raises():
    """Satellite 1: the docstring previously claimed chunk ≤ 65536 but the
    int16 wire offsets wrap past 32768 — the assert is the contract."""
    strategies.TopK(chunk=32768)                      # boundary: fine
    with pytest.raises(AssertionError, match="32768"):
        strategies.TopK(chunk=40000)


def test_onebit_scale_uses_true_length_only():
    """Satellite 2: the scale is mean(|c|) over the TRUE vector, not the
    zero-padded pack grid — padding must not deflate it."""
    n = 100                      # pads to PACK_ALIGN = 32768
    tree = {"w": jnp.ones((n,), jnp.float32) * 2.0}
    strat = strategies.OneBit()
    state = strat.init_state(tree)
    assert state.shape[0] == compress.PACK_ALIGN
    # drive the scale computation exactly as __call__ does, minus the mesh
    from theanompi_tpu.utils import helper_funcs
    flat = helper_funcs.flatten_tree(
        tree, pad_to_multiple_of=compress.PACK_ALIGN)
    packed, absc = compress.pack_signs_encode(flat, state)
    n_true = helper_funcs.tree_size(tree)
    scale = jnp.mean(absc[:n_true]) + 1e-12
    np.testing.assert_allclose(float(scale), 2.0, rtol=1e-6)
    # the padded mean the old code computed would have been ~100/32768 of it
    assert float(jnp.mean(absc)) < 0.1


# ---------------------------------------------------------------------------
# traffic model + report schema
# ---------------------------------------------------------------------------

def test_compress_row_columns_disjoint_from_other_vocabularies():
    vocabularies = {
        "PIPELINE_ROW_COLUMNS": devprof.PIPELINE_ROW_COLUMNS,
        "USHARD_ROW_COLUMNS": devprof.USHARD_ROW_COLUMNS,
    }
    compress_cols = set(devprof.COMPRESS_ROW_COLUMNS)
    assert len(compress_cols) == len(devprof.COMPRESS_ROW_COLUMNS)
    for name, cols in vocabularies.items():
        clash = compress_cols & set(cols)
        assert not clash, f"COMPRESS_ROW_COLUMNS collide with {name}: {clash}"


def test_traffic_model_pinned_shrinks():
    """The acceptance numbers: ≥2× total HBM shrink for onebit, by
    construction of the stage lists.  Pinned to
    3 decimals so a stage silently dropped from the accounting fails."""
    onebit = devprof.compress_traffic_model("onebit", 1 << 22, 2)
    assert onebit["compress_hbm_shrink"] == pytest.approx(2.68, abs=0.02)
    assert onebit["compress_decode_shrink"] == pytest.approx(2.882, abs=0.02)
    assert onebit["compress_hbm_shrink"] >= 2.0
    # topk has no fused pipeline (kernel pair deleted in PR 21): shrink 1
    topk = devprof.compress_traffic_model("topk", 1 << 22, 2)
    assert topk["compress_hbm_shrink"] == 1.0
    assert topk["compress_decode_shrink"] == 1.0
    psgd = devprof.compress_traffic_model(
        "powersgd2", 1 << 22, 2, leaf_shapes=[(512, 256), (256,)])
    assert psgd is not None and psgd["compress_hbm_shrink"] > 1.0
    # every returned dict carries exactly the declared columns + metadata
    for rep in (onebit, topk, psgd):
        assert set(devprof.COMPRESS_ROW_COLUMNS) <= set(rep)
        for _, stages in rep["stages"].items():
            assert all(b > 0 for _, b in stages)


def test_traffic_model_none_for_uncompressed_strategies():
    assert devprof.compress_traffic_model("bsp", 1 << 20, 2) is None
    assert devprof.compress_traffic_model("nccl16", 1 << 20, 2) is None
    # powersgd with nothing compressible (all leaves too small/1-D)
    assert devprof.compress_traffic_model(
        "powersgd2", 1 << 20, 2, leaf_shapes=[(8,), (4, 4)]) is None


def test_traffic_report_from_live_model_stub():
    """compress_traffic_report reads only (exchanger.strategy, params,
    mesh.shape[WORKER_AXIS]) — the stub pins that surface."""
    from theanompi_tpu.parallel.mesh import WORKER_AXIS
    strat = strategies.TopK(chunk=4096)
    model = types.SimpleNamespace(
        exchanger=types.SimpleNamespace(strategy=strat),
        params={"w": np.zeros((64, 32), np.float32),
                "b": np.zeros((32,), np.float32)},
        mesh=types.SimpleNamespace(shape={WORKER_AXIS: 2}))
    rep = devprof.compress_traffic_report(model)
    assert set(rep) == set(devprof.COMPRESS_ROW_COLUMNS)
    want = devprof.compress_traffic_model(
        "topk", 64 * 32 + 32, 2, chunk=4096, k_c=strat._k_c())
    assert rep["compress_hbm_shrink"] == want["compress_hbm_shrink"]
    # non-compression strategy → None
    model.exchanger.strategy = strategies.get_strategy("allreduce")
    assert devprof.compress_traffic_report(model) is None
