"""Checkpoint/resume roundtrip, loader determinism (SURVEY.md §4 item d),
prefetch-loader equivalence, helper roundtrips, recorder accounting."""

import os

import jax
import numpy as np
import pytest

from tests.conftest import SyntheticData, TinyModel
from theanompi_tpu.models.data.prefetch import PrefetchLoader
from theanompi_tpu.parallel import steps
from theanompi_tpu.parallel.exchanger import BSP_Exchanger
from theanompi_tpu.parallel.mesh import worker_mesh
from theanompi_tpu.utils import checkpoint as ckpt
from theanompi_tpu.utils import helper_funcs as hf
from theanompi_tpu.utils.recorder import Recorder


# -- checkpoint -------------------------------------------------------------

def _model(n=4, **cfg):
    mesh = worker_mesh(n)
    config = {"mesh": mesh, "size": n, "rank": 0, "verbose": False,
              "batch_size": 8, **cfg}
    m = TinyModel(config)
    m.compile_iter_fns(BSP_Exchanger(config))
    m.data.shuffle_data(0)
    return m


def test_checkpoint_resume_roundtrip(tmp_path):
    d = str(tmp_path / "ckpt")
    m1 = _model()
    for i in range(3):
        m1.train_iter(i + 1, None)
    m1.save(d, epoch=5, count=3)
    p_saved = jax.device_get(steps.unbox(m1.step_state["params"]))

    m2 = _model()
    epoch = m2.load(d)
    assert epoch == 5
    p_loaded = jax.device_get(steps.unbox(m2.step_state["params"]))
    for a, b in zip(jax.tree_util.tree_leaves(p_saved),
                    jax.tree_util.tree_leaves(p_loaded)):
        np.testing.assert_array_equal(a, b)
    # resumed model must keep training identically to the original — the
    # checkpoint carries the data cursor, so no manual realignment
    m1.train_iter(4, None)
    m2.train_iter(4, None)
    for a, b in zip(
            jax.tree_util.tree_leaves(
                jax.device_get(steps.unbox(m1.step_state["params"]))),
            jax.tree_util.tree_leaves(
                jax.device_get(steps.unbox(m2.step_state["params"])))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def _train_loop(m, exch, counts):
    """Reference worker cadence: train_iter then the rule's exchange hook."""
    for c in counts:
        m.train_iter(c, None)
        exch.exchange(None, c)


@pytest.mark.parametrize("rule", ["bsp", "gosgd"])
def test_exact_resume_across_kill(tmp_path, rule):
    """Deterministic replay must survive a save/kill/resume boundary
    bit-identically (VERDICT: checkpoint completeness) — including the
    per-worker diverged replicas, GoSGD α, both PRNG keys, and the data
    cursor, mid-epoch."""
    from theanompi_tpu.parallel.exchanger import get_exchanger
    d = str(tmp_path / "ckpt")
    n = 4

    def make():
        mesh = worker_mesh(n)
        config = {"mesh": mesh, "size": n, "rank": 0, "verbose": False,
                  "batch_size": 8, "exch_prob": 1.0}
        m = TinyModel(config)
        exch = get_exchanger(rule, config)
        m.compile_iter_fns(exch)
        return m, exch

    # uninterrupted run: 6 iterations
    mA, eA = make()
    mA.data.shuffle_data(0)
    _train_loop(mA, eA, range(1, 7))
    ref = jax.device_get(mA.step_state)

    # interrupted run: 3 iterations, save mid-epoch, "kill", rebuild, resume
    mB, eB = make()
    mB.data.shuffle_data(0)
    _train_loop(mB, eB, range(1, 4))
    mB.save(d, epoch=0, count=3)
    del mB, eB

    mC, eC = make()
    assert mC.load(d) == 0
    _train_loop(mC, eC, range(4, 7))
    got = jax.device_get(mC.step_state)
    for key in ref:
        for a, b in zip(jax.tree_util.tree_leaves(ref[key]),
                        jax.tree_util.tree_leaves(got[key])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefetch_cursor_tracks_consumer():
    """The prefetch producer runs ahead; get_cursor must report the CONSUMED
    position, and a fresh loader resumed from it continues identically."""
    base = SyntheticData({"size": 2}, batch_size=8)
    w = PrefetchLoader(SyntheticData({"size": 2}, batch_size=8))
    base.shuffle_data(5)
    w.shuffle_data(5)
    for i in range(3):
        np.testing.assert_array_equal(base.next_train_batch(i)["x"],
                                      w.next_train_batch(i)["x"])
    assert w.get_cursor()["train_ptr"] == base.get_cursor()["train_ptr"] == 3

    w2 = PrefetchLoader(SyntheticData({"size": 2}, batch_size=8))
    w2.set_cursor(w.get_cursor())
    np.testing.assert_array_equal(base.next_train_batch(3)["x"],
                                  w2.next_train_batch(3)["x"])


def test_imagenet_cursor_restores_aug_stream():
    """ImageNet augmentation draws from a stateful RandomState; the cursor
    must capture it so crops/mirrors replay exactly after resume."""
    from theanompi_tpu.models.data.imagenet import ImageNet_data
    cfg = {"size": 1, "synthetic_batches": 4}
    d1 = ImageNet_data(cfg, batch_size=4)
    d1.shuffle_data(1)
    for i in range(2):
        d1.next_train_batch(i)
    cur = d1.get_cursor()
    a = d1.next_train_batch(2)
    d2 = ImageNet_data(cfg, batch_size=4)
    d2.set_cursor(cur)
    b = d2.next_train_batch(2)
    np.testing.assert_array_equal(a["x"], b["x"])


def test_bsp_checkpoint_is_worker_count_portable(tmp_path):
    """Elastic resume: a BSP grads-mode checkpoint stores ONE replica, so it
    restores onto a mesh of any worker count — train on 4 chips, resume on
    8 (the reference could not change -np between runs)."""
    d = str(tmp_path / "ckpt")
    m4 = _model(n=4)
    for i in range(3):
        m4.train_iter(i + 1, None)
    m4.save(d, epoch=0, count=3)
    ref = jax.device_get(steps.unbox(m4.step_state["params"]))

    m8 = _model(n=8)
    assert m8.load(d) == 0
    got = jax.device_get(m8.step_state["params"])
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        for w in range(8):
            np.testing.assert_array_equal(np.asarray(b)[w], np.asarray(a))
    m8.train_iter(4, None)               # and it keeps training
    # async-rule (boxed) checkpoints are NOT portable — they must fail
    # loudly, not silently collapse replicas
    from theanompi_tpu.parallel.exchanger import GOSGD_Exchanger
    mesh = worker_mesh(4)
    cfg = {"mesh": mesh, "size": 4, "rank": 0, "verbose": False,
           "batch_size": 8}
    g4 = TinyModel(cfg)
    g4.compile_iter_fns(GOSGD_Exchanger(cfg))
    g4.data.shuffle_data(0)
    g4.train_iter(1, None)
    d2 = str(tmp_path / "gossip")
    g4.save(d2, epoch=0, count=1)
    mesh8 = worker_mesh(8)
    cfg8 = {"mesh": mesh8, "size": 8, "rank": 0, "verbose": False,
            "batch_size": 8}
    g8 = TinyModel(cfg8)
    g8.compile_iter_fns(GOSGD_Exchanger(cfg8))
    # round-5: the raw leaf-shape mismatch ("incompatible checkpoint")
    # became a targeted error naming the per-worker-state limitation
    with pytest.raises(ValueError, match="no.*worker-count refit"):
        g8.load(d2)


def test_async_ckpt_matches_sync(tmp_path):
    """async_ckpt moves only the disk write off-thread: the landed files
    must be byte-equivalent to a synchronous save of the same state."""
    d_sync = str(tmp_path / "sync")
    d_async = str(tmp_path / "async")
    m = _model(async_ckpt=True)
    for i in range(2):
        m.train_iter(i + 1, None)
    m.config["async_ckpt"] = False
    m.save(d_sync, epoch=0, count=2)
    m.config["async_ckpt"] = True
    m.save(d_async, epoch=0, count=2)
    m.wait_pending_ckpt()

    a = np.load(os.path.join(d_sync, "ckpt_epoch0.npz"))
    b = np.load(os.path.join(d_async, "ckpt_epoch0.npz"))
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k])
    # and the async checkpoint restores
    m2 = _model()
    assert m2.load(d_async) == 0


def test_async_ckpt_write_failure_surfaces():
    """A failed background write must raise at the next join point — a
    silently-lost checkpoint would let a supervisor resume from an older
    epoch with no signal."""
    m = _model(async_ckpt=True)
    m.train_iter(1, None)
    m.save("/proc/definitely/not/writable", epoch=0, count=1)
    with pytest.raises(RuntimeError, match="async checkpoint write failed"):
        m.wait_pending_ckpt()


def test_checkpoint_latest_and_missing(tmp_path):
    d = str(tmp_path / "none")
    assert ckpt.latest_epoch(d) is None
    m = _model()
    m.save(d, epoch=1)
    m.save(d, epoch=2)
    assert ckpt.latest_epoch(d) == 2
    # params_epoch dir holds reference-style per-leaf .npy snapshots
    assert os.path.isdir(os.path.join(d, "params_epoch2"))


def test_corrupted_latest_checkpoint_falls_back_to_newest_valid(tmp_path):
    """A SIGKILL mid-save must never brick `--supervise` resume: with the
    newest checkpoint truncated (pre-atomic writer) or its sidecar torn,
    latest_epoch falls back to the newest VALID epoch and model.load
    resumes from it."""
    d = str(tmp_path / "c")
    m = _model()
    m.save(d, epoch=0)
    m.save(d, epoch=1)
    assert ckpt.checkpoint_valid(d, 1)
    # simulate the mid-save kill: epoch 1's archive truncated to half
    path1 = os.path.join(d, "ckpt_epoch1.npz")
    blob = open(path1, "rb").read()
    with open(path1, "wb") as f:
        f.write(blob[:len(blob) // 2])
    assert not ckpt.checkpoint_valid(d, 1)
    assert ckpt.latest_epoch(d) == 0               # newest VALID wins
    m2 = _model()
    assert m2.load(d) == 0                          # resume did not brick
    # torn LATEST pointer alone must not brick either
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("not-an-int")
    assert ckpt.latest_epoch(d) == 0
    # a fully healthy dir keeps the fast path
    m.save(d, epoch=1)
    assert ckpt.latest_epoch(d) == 1
    # sidecar torn: same fallback
    with open(os.path.join(d, "ckpt_epoch1.json"), "w") as f:
        f.write('{"epoch": 1, "count"')
    assert ckpt.latest_epoch(d) == 0


def test_checkpoint_writes_are_atomic_no_temp_residue(tmp_path):
    d = str(tmp_path / "a")
    m = _model()
    m.save(d, epoch=0)
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]
    # every artifact is complete and parseable immediately after save
    assert ckpt.checkpoint_valid(d, 0)
    with open(os.path.join(d, "LATEST")) as f:
        assert int(f.read()) == 0


def test_save_params_npy_roundtrip(tmp_path):
    d = str(tmp_path / "p")
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "nest": {"b": np.ones((4,), np.float32)}}
    hf.save_params(tree, d)
    loaded = hf.load_params(tree, d)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(loaded)):
        np.testing.assert_array_equal(a, b)


# -- flatten/unflatten ------------------------------------------------------

def test_flatten_unflatten_roundtrip():
    rng = np.random.RandomState(0)
    tree = {"w": rng.randn(3, 4).astype(np.float32),
            "b": rng.randn(5).astype(np.float32)}
    flat = hf.flatten_tree(tree, pad_to_multiple_of=8)
    assert flat.shape[0] % 8 == 0
    back = hf.unflatten_like(tree, flat)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]), tree[k])


# -- data -------------------------------------------------------------------

def test_shuffle_determinism_and_coverage():
    cfg = {"size": 4}
    d1 = SyntheticData(cfg, batch_size=8)
    d2 = SyntheticData(cfg, batch_size=8)
    d1.shuffle_data(42)
    d2.shuffle_data(42)
    b1 = d1.next_train_batch(1)
    b2 = d2.next_train_batch(1)
    np.testing.assert_array_equal(b1["x"], b2["x"])   # common-seed identical
    d2.shuffle_data(43)
    b3 = d2.next_train_batch(1)
    assert not np.array_equal(b1["x"], b3["x"])       # reshuffles

    # one epoch covers each sample at most once (disjoint strided shards)
    d1.shuffle_data(1)
    seen = []
    for i in range(d1.n_batch_train):
        seen.append(d1.next_train_batch(i)["y"].shape[0])
    assert sum(seen) <= len(d1.y_train)


def test_global_batch_scales_with_size():
    d = SyntheticData({"size": 8}, batch_size=8)
    b = d.next_train_batch(1)
    assert b["x"].shape[0] == 64
    assert b["y"].dtype == np.int32


def test_prefetch_loader_equivalence():
    direct = SyntheticData({"size": 2}, batch_size=8)
    wrapped = PrefetchLoader(SyntheticData({"size": 2}, batch_size=8))
    direct.shuffle_data(9)
    wrapped.shuffle_data(9)
    for i in range(direct.n_batch_train):
        a = direct.next_train_batch(i + 1)
        b = wrapped.next_train_batch(i + 1)
        np.testing.assert_array_equal(a["x"], b["x"])
        np.testing.assert_array_equal(a["y"], b["y"])
    assert wrapped.n_batch_train == direct.n_batch_train


def test_prefetch_overlaps_slow_io_with_compute():
    """The point of para_load (SURVEY.md §2.8): loader latency must hide
    behind compute.  Producer costs 30ms/batch; consumer 'computes' 45ms;
    with depth-2 prefetch the summed load-wait must be a fraction of the
    serial 6×30ms."""
    import time

    class SlowData(SyntheticData):
        def next_train_batch(self, count):
            time.sleep(0.03)
            return super().next_train_batch(count)

    w = PrefetchLoader(SlowData({"size": 1}, batch_size=8))
    w.shuffle_data(0)
    t_load = 0.0
    for i in range(6):
        t0 = time.perf_counter()
        w.next_train_batch(i + 1)
        t_load += time.perf_counter() - t0
        time.sleep(0.045)            # stand-in for the training step
    # serial loading would cost 6×30ms = 180ms of load wait; require clear
    # overlap but leave headroom for CI scheduler noise
    assert t_load < 0.75 * 6 * 0.03, f"load wait {t_load:.3f}s — no overlap"


def test_para_load_stages_batches_onto_device():
    """With para_load=True the producer thread device_puts batches; the
    training loop must consume device-resident arrays (t_load' covers only
    the queue get) and still train correctly."""
    import jax.numpy as jnp
    m = _model(para_load=True)
    r = Recorder({"verbose": False, "printFreq": 1})
    m.data.shuffle_data(0)
    b = m.data.next_train_batch(1)
    assert isinstance(jax.tree_util.tree_leaves(b)[0], jax.Array)
    m.data.set_cursor(m.data.get_cursor())   # restart producer at ptr=1
    for i in range(2, 5):
        m.train_iter(i, r)
    assert np.isfinite(float(jnp.mean(np.asarray(m.current_info["cost"]))))
    # equivalence with the unwrapped path
    m2 = _model()
    m2.data.shuffle_data(0)
    m2.data.next_train_batch(1)
    for i in range(2, 5):
        m2.train_iter(i, None)
    for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(m.step_state["params"])),
            jax.tree_util.tree_leaves(jax.device_get(m2.step_state["params"]))):
        np.testing.assert_array_equal(a, b)


def test_prefetch_loader_surfaces_errors():
    class Boom(SyntheticData):
        def next_train_batch(self, count):
            raise RuntimeError("loader exploded")

    w = PrefetchLoader(Boom({"size": 1}, batch_size=8))
    w.shuffle_data(0)
    with pytest.raises(RuntimeError, match="loader exploded"):
        w.next_train_batch(1)


def test_hkl_batch_files_read_via_h5py(tmp_path):
    """Reference data prep produces hickle .hkl files (HDF5 inside,
    SURVEY.md §2.8); they must load without hickle installed."""
    h5py = pytest.importorskip("h5py")
    from theanompi_tpu.models.data.imagenet import (ImageNet_data,
                                                    _load_batch_file)

    rng = np.random.RandomState(7)
    batch = rng.randint(0, 256, (4, 3, 256, 256), dtype=np.uint8)  # bc01
    p = str(tmp_path / "0000.hkl")
    with h5py.File(p, "w") as f:      # hickle v2/v3 layout: root 'data'
        f.create_dataset("data", data=batch)
    np.testing.assert_array_equal(_load_batch_file(p), batch)

    # and a full ImageNet_data epoch over a tiny .hkl-backed data dir
    d = tmp_path / "imagenet"
    for sub in ("train_hkl", "val_hkl"):
        (d / sub).mkdir(parents=True)
        for i in range(2):
            with h5py.File(str(d / sub / f"{i:04d}.hkl"), "w") as f:
                f.create_dataset("data", data=batch)
    np.save(str(d / "train_labels.npy"), np.arange(8) % 4)
    np.save(str(d / "val_labels.npy"), np.arange(8) % 4)
    np.save(str(d / "img_mean.npy"),
            np.zeros((3, 256, 256), np.float32))
    data = ImageNet_data({"size": 1, "data_dir": str(d)}, batch_size=4)
    assert not data.synthetic
    data.shuffle_data(0)
    b = data.next_train_batch(0)
    assert b["x"].shape == (4, 227, 227, 3)
    assert b["x"].dtype == np.uint8
    # a mean image and a shared window: the window's offsets ride along
    assert b["crop_off"].shape == (4, 2)
    v = data.next_val_batch(0)
    assert v["y"].shape == (4,)


# -- recorder ---------------------------------------------------------------

def test_recorder_accounting(tmp_path):
    r = Recorder({"verbose": False, "printFreq": 2,
                  "record_dir": str(tmp_path)})
    for i in range(1, 5):
        r.start(); r.end("train")
        r.train_error(i, cost=1.0 / i, error=0.5, n_images=32)
        r.print_train_info(i)
    assert len(r._all_records) == 2
    assert r.n_images_total == 128
    r.val_error(4, 0.9, 0.4, 0.1)
    rec = r.print_val_info(4)
    assert rec["val_error"] == 0.4
    r.save()
    assert os.path.exists(os.path.join(str(tmp_path), "inforec_rank0.jsonl"))


def test_sync_each_iter_writes_wait_bucket():
    """In blocking mode t_train (dispatch) + t_wait (device-bound block) sum
    to wall time — the wait bucket must actually be written (VERDICT: it had
    no writer anywhere)."""
    m = _model(sync_each_iter=True)
    r = Recorder({"verbose": False, "printFreq": 1})
    m.train_iter(1, r)
    assert "wait" in r.t_sec_total
    assert r.t_sec_total["wait"] >= 0.0
    assert r.t_sec_total["train"] > 0.0


def test_recorder_accepts_device_scalars():
    import jax.numpy as jnp
    r = Recorder({"verbose": False, "printFreq": 1})
    r.start(); r.end("train")
    r.train_error(1, cost=jnp.float32(2.0), error=jnp.float32(0.25),
                  n_images=8)
    r.print_train_info(1)
    assert r._all_records[-1]["cost"] == 2.0


def test_pooled_prefetch_stream_bit_identical(tmp_path):
    """round-4: the pooled producer (sequential plans, thread-pool
    materialization) must emit EXACTLY the serial producer's batch stream —
    same order, same augmentation draws — for any pool size."""
    import numpy as np
    from theanompi_tpu.models.data.imagenet import ImageNet_data
    from theanompi_tpu.models.data.prefetch import PrefetchLoader

    cfg = {"size": 1, "synthetic_batches": 6, "n_class": 10, "seed": 9}
    serial = PrefetchLoader(ImageNet_data(dict(cfg), batch_size=4),
                            n_workers=1)
    pooled = PrefetchLoader(ImageNet_data(dict(cfg), batch_size=4),
                            n_workers=4)
    serial.shuffle_data(3)
    pooled.shuffle_data(3)
    for i in range(6):
        a = serial.next_train_batch(i)
        b = pooled.next_train_batch(i)
        np.testing.assert_array_equal(a["x"], b["x"])
        np.testing.assert_array_equal(a["y"], b["y"])
    # cursor semantics survive pooling (mid-epoch resume contract)
    assert serial.get_cursor()["train_ptr"] == \
        pooled.get_cursor()["train_ptr"]
