"""Token-file dataset (models/data/tokens.py): nanoGPT-style train.bin /
val.bin streams through the standard DataBase contract."""

import numpy as np
import pytest

import jax.numpy as jnp

from theanompi_tpu.models.data.tokens import TokenFileData
from theanompi_tpu.models.transformer_lm import TransformerLM
from theanompi_tpu.parallel.exchanger import BSP_Exchanger
from theanompi_tpu.parallel.mesh import worker_mesh


def _write_corpus(tmp_path, n_train=4096, n_val=1024, vocab=16):
    d = tmp_path / "toks"
    d.mkdir()
    # deterministic modular-increment stream: learnable next-token rule
    (np.arange(n_train, dtype=np.uint16) % vocab).tofile(d / "train.bin")
    (np.arange(n_val, dtype=np.uint16) % vocab).tofile(d / "val.bin")
    return str(d)


def test_windows_and_shifted_targets(tmp_path):
    root = _write_corpus(tmp_path)
    data = TokenFileData({"size": 2, "data_dir": root, "seq_len": 8},
                         batch_size=4)
    b = data.next_train_batch(0)          # unshuffled: identity permutation
    assert b["x"].shape == (8, 8) and b["y"].shape == (8, 8)
    # window i covers tokens [8i, 8i+8]; y is x shifted by one
    np.testing.assert_array_equal(b["x"][0], np.arange(8) % 16)
    np.testing.assert_array_equal(b["y"][0], np.arange(1, 9) % 16)
    np.testing.assert_array_equal(b["y"][:, :-1], b["x"][:, 1:])


def test_host_slices_partition(tmp_path):
    root = _write_corpus(tmp_path)
    cfg = {"size": 4, "data_dir": root, "seq_len": 8}
    whole = TokenFileData({**cfg, "process_count": 1}, batch_size=4)
    parts = [TokenFileData({**cfg, "process_count": 2, "process_index": h},
                           batch_size=4) for h in (0, 1)]
    for d in (whole, *parts):
        d.shuffle_data(42)
    g = whole.next_train_batch(0)
    a, b = (p.next_train_batch(0) for p in parts)
    np.testing.assert_array_equal(np.concatenate([a["x"], b["x"]]), g["x"])
    np.testing.assert_array_equal(np.concatenate([a["y"], b["y"]]), g["y"])


def test_cursor_roundtrip(tmp_path):
    root = _write_corpus(tmp_path)
    data = TokenFileData({"size": 2, "data_dir": root, "seq_len": 8},
                         batch_size=4)
    data.shuffle_data(7)
    data.next_train_batch(0)
    cur = data.get_cursor()
    want = data.next_train_batch(1)
    d2 = TokenFileData({"size": 2, "data_dir": root, "seq_len": 8},
                       batch_size=4)
    d2.set_cursor(cur)
    got = d2.next_train_batch(1)
    np.testing.assert_array_equal(got["x"], want["x"])


def test_lm_trains_and_generates_from_token_files(tmp_path, mesh8):
    # vocab COPRIME with seq_len so window starts cycle through all
    # residues — the +1 rule must be learned from content, not position
    root = _write_corpus(tmp_path, n_train=8192, vocab=13)
    mesh = worker_mesh(4)
    model = TransformerLM({
        "mesh": mesh, "size": 4, "rank": 0, "verbose": False,
        "data_dir": root, "batch_size": 8, "seq_len": 16, "vocab": 13,
        "d_model": 64, "n_head": 4, "n_layer": 2, "learning_rate": 3e-3,
        "compute_dtype": jnp.float32})
    model.compile_iter_fns(BSP_Exchanger(model.config))
    model.data.shuffle_data(0)
    costs = []
    for i in range(40):
        model.train_iter(i, None)
        costs.append(float(model.current_info["cost"]))
    assert costs[-1] < 0.5 * costs[0]
    out = model.generate(np.array([[3, 4, 5, 6]], np.int32),
                         max_new_tokens=6)
    np.testing.assert_array_equal(out[0], np.arange(7, 13) % 13)
    model.begin_val()
    model.val_iter(0, None)
    model.end_val()


def test_make_token_dataset_script(tmp_path):
    """Text → byte-token files → loadable by TokenFileData."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    txt = tmp_path / "corpus.txt"
    txt.write_text("hello token world! " * 400)
    out = tmp_path / "toks"
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts/make_token_dataset.py"),
         str(txt), "--out", str(out), "--val-frac", "0.1"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    data = TokenFileData({"size": 2, "data_dir": str(out), "seq_len": 16,
                          "vocab": 256}, batch_size=4)
    b = data.next_train_batch(0)
    assert b["x"].shape == (8, 16)
    # byte-level: tokens are the utf-8 bytes of the corpus
    assert bytes(b["x"][0].astype(np.uint8)).decode() in \
        "hello token world! " * 3


def test_missing_files_error(tmp_path):
    (tmp_path / "empty").mkdir()
    with pytest.raises(FileNotFoundError, match="token file"):
        TokenFileData({"size": 1, "data_dir": str(tmp_path / "empty"),
                       "seq_len": 8}, batch_size=4)


def test_vocab_guard_fires_with_model_default_vocab(tmp_path):
    """Review r3: the out-of-range check must fire even when the user relies
    on the model's class-default vocab (no 'vocab' in config) — the model
    passes its RESOLVED vocab into TokenFileData."""
    root = _write_corpus(tmp_path, vocab=64)
    data = TokenFileData({"size": 1, "data_dir": root, "seq_len": 8},
                         batch_size=4, vocab=32)   # corpus ids reach 63
    with pytest.raises(AssertionError, match="vocab=32"):
        data.next_train_batch(0)
    # config['vocab'] still wins over the passed default when both exist
    data2 = TokenFileData({"size": 1, "data_dir": root, "seq_len": 8,
                           "vocab": 64}, batch_size=4, vocab=32)
    data2.next_train_batch(0)   # no raise
