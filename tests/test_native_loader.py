"""The image wire: uint8 crops from the host, the arithmetic on the device.

The host pass (theanompi_tpu/native/loader.cc, NumPy without a compiler) is
a gather — crop window and mirror — so native and NumPy must agree to the
byte.  ``ModelBase.stage_input`` then computes the reference's
``float32(pixel) - mean`` in the step program: one float32 subtraction, so
exact equality with the NumPy expression is the correct assertion (not
allclose), in every mean mode.
"""

import os
import re
import types

import numpy as np
import pytest

from theanompi_tpu import native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _params(rng, n, h, w, crop, per_image):
    m = n if per_image else 1
    oy = rng.randint(0, h - crop + 1, size=m).astype(np.int32)
    ox = rng.randint(0, w - crop + 1, size=m).astype(np.int32)
    flip = rng.randint(0, 2, size=m).astype(np.uint8)
    return oy, ox, flip


def _gather(x, oy, ox, flip, crop):
    """Crop + mirror, written out: NHWC uint8 in, NHWC uint8 out."""
    n = x.shape[0]
    oy, ox, flip = (np.broadcast_to(a, (n,)) for a in (oy, ox, flip))
    out = np.empty((n, crop, crop, x.shape[-1]), np.uint8)
    for i in range(n):
        win = x[i, oy[i]:oy[i] + crop, ox[i]:ox[i] + crop, :]
        out[i] = win[:, ::-1, :] if flip[i] else win
    return out


@pytest.mark.parametrize("n_threads", [1, 8])
@pytest.mark.parametrize("per_image", [False, True])
@pytest.mark.parametrize("layout", ["nhwc", "nchw"])
def test_native_matches_numpy(per_image, layout, n_threads):
    if not native.native_available():
        pytest.skip("no native toolchain in this environment")
    rng = np.random.RandomState(0)
    n, h, w, c, crop = 7, 20, 24, 3, 13
    nhwc = rng.randint(0, 256, (n, h, w, c), dtype=np.uint8)
    x = np.ascontiguousarray(nhwc.transpose(0, 3, 1, 2)) \
        if layout == "nchw" else nhwc
    oy, ox, flip = _params(rng, n, h, w, crop, per_image)

    got = native.augment_batch(x, oy, ox, flip, crop, n_threads=n_threads)
    assert got.shape == (n, crop, crop, c) and got.dtype == np.uint8
    np.testing.assert_array_equal(got, _gather(nhwc, oy, ox, flip, crop))
    np.testing.assert_array_equal(got, native._augment_numpy(
        x, np.broadcast_to(oy, (n,)), np.broadcast_to(ox, (n,)),
        np.broadcast_to(flip, (n,)), crop))


def test_windows_outside_the_image_are_refused():
    """The native pass reads where the offsets point: a window that leaves
    the image raises before any pointer is handed over."""
    x = np.zeros((2, 8, 8, 3), np.uint8)
    with pytest.raises(ValueError, match="leave the 8x8"):
        native.augment_batch(x, [0, 3], [0, 0], [0, 0], 6)
    with pytest.raises(ValueError, match="leave the 8x8"):
        native.augment_batch(x, [0, 0], [-1, 0], [0, 0], 6)
    assert native.augment_batch(x, [0, 2], [2, 0], [0, 1], 6).shape == \
        (2, 6, 6, 3)


def test_imagenet_data_contract_shapes():
    """Train and validation batches are uint8 crops and int32 labels; a
    scalar mean puts no third leaf on the wire."""
    from theanompi_tpu.models.data.imagenet import ImageNet_data

    d = ImageNet_data({"size": 1, "synthetic_batches": 2, "n_class": 10,
                       "aug_per_image": True}, batch_size=4)
    for b in (d.next_train_batch(0), d.next_val_batch(0)):
        assert sorted(b) == ["x", "y"]
        assert b["x"].shape == (4, 227, 227, 3) and b["x"].dtype == np.uint8
        assert b["y"].shape == (4,) and b["y"].dtype == np.int32


MEAN_MODES = ["scalar", "channel", "image_shared", "image_per_image"]


def _data_with_mean(mean_mode, seed=5, **cfg):
    """A synthetic data object whose mean is of the asked kind: the data
    object reads what it observes (the mean's rank, ``aug_per_image``)."""
    from theanompi_tpu.models.data.imagenet import RAW, ImageNet_data

    d = ImageNet_data({"size": 1, "synthetic_batches": 8, "n_class": 10,
                       "aug_per_image": mean_mode == "image_per_image",
                       "seed": seed, **cfg}, batch_size=4, crop=200)
    r = np.random.RandomState(11)
    if mean_mode == "scalar":
        d.img_mean = np.float32(117.3)
    elif mean_mode == "channel":
        d.img_mean = (r.rand(3) * 255).astype(np.float32)
    else:
        d.img_mean = (r.rand(RAW, RAW, 3) * 255).astype(np.float32)
    return d


def _reference_f32(d, x, draws):
    """What the float32 host pass delivered: ``float32(u8) - mean``, the
    mean image cut under the shared window, at its center for per-image
    windows, never mirrored."""
    oy, ox, flip = draws
    c = d.crop
    crops = _gather(x, oy, ox, flip, c).astype(np.float32)
    m = d.img_mean
    if np.ndim(m) == 3:
        if oy.shape[0] == 1:
            m = m[oy[0]:oy[0] + c, ox[0]:ox[0] + c, :]
        else:
            cy, cx = (x.shape[1] - c) // 2, (x.shape[2] - c) // 2
            m = m[cy:cy + c, cx:cx + c, :]
    return crops - m


def _stage(d, batch):
    """``ModelBase.stage_input`` on a delivered batch, traced under jit as
    the step program traces it."""
    import jax
    from theanompi_tpu.models.model_base import ModelBase

    model = types.SimpleNamespace(data=d)
    return np.asarray(jax.jit(
        lambda b: ModelBase.stage_input(model, b["x"], b.get("crop_off"))
    )(batch))


@pytest.mark.parametrize("mirror", [0, 1])
@pytest.mark.parametrize("mean_mode", MEAN_MODES)
def test_stage_input_bit_equal_to_f32_pass(mean_mode, mirror):
    d = _data_with_mean(mean_mode)
    n, h, w = d._synth_x.shape[:3]
    oy, ox, flip = d._draw(n, h, w, train=True)
    flip = np.full_like(flip, mirror)
    if mean_mode == "image_per_image":      # a mixed batch mirrors some
        flip[::2] = 1 - mirror
    batch = d._transform(d._synth_x, d._synth_y, (oy, ox, flip))
    assert batch["x"].dtype == np.uint8
    assert ("crop_off" in batch) == (mean_mode == "image_shared")
    got = _stage(d, batch)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(
        got, _reference_f32(d, d._synth_x, (oy, ox, flip)))
    if mean_mode == "image_shared":
        # the window's mean, not the center's: the deviation the optional
        # uint8 wire once documented is gone
        assert (oy[0], ox[0]) != ((h - d.crop) // 2, (w - d.crop) // 2)
        assert batch["crop_off"].dtype == np.int32
        np.testing.assert_array_equal(
            batch["crop_off"], np.tile([oy[0], ox[0]], (n, 1)))
    # validation: center crop, no mirror, the mean under that window
    v = d.next_val_batch(0)
    cy = np.full(1, (h - d.crop) // 2, np.int32)
    np.testing.assert_array_equal(
        _stage(d, v),
        _reference_f32(d, d._synth_x, (cy, cy, np.zeros(1, np.uint8))))


def test_stage_input_passes_float_through():
    """The benchmark's reference check feeds float32 crops."""
    import jax.numpy as jnp
    from theanompi_tpu.models.model_base import ModelBase

    x = jnp.ones((2, 8, 8, 3), jnp.float32)
    assert ModelBase.stage_input(types.SimpleNamespace(data=None), x) is x


@pytest.mark.parametrize("mean_mode", ["scalar", "image_shared"])
def test_pooled_stream_is_serial_stream_and_uint8(mean_mode):
    """Through PrefetchLoader with a pool of 4 the batch stream is the
    serial one bit for bit, every leaf of it."""
    from theanompi_tpu.models.data.prefetch import PrefetchLoader

    serial = _data_with_mean(mean_mode, seed=9)
    pooled = PrefetchLoader(_data_with_mean(mean_mode, seed=9), n_workers=4)
    serial.shuffle_data(3)
    pooled.shuffle_data(3)
    for i in range(6):
        a = serial.next_train_batch(i)
        b = pooled.next_train_batch(i)
        assert sorted(a) == sorted(b)
        assert b["x"].dtype == np.uint8
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


class _FedFloat32:
    """Stands where the loader stood and hands over float32 batches
    computed in the test from the same uint8 stream."""

    def __init__(self, data):
        self._d = data
        self.img_mean = data.img_mean
        self.get_cursor, self.set_cursor = data.get_cursor, data.set_cursor

    def next_train_batch(self, count):
        d = self._d
        draws = d._draw(d._synth_x.shape[0], 256, 256, train=True)
        return {"x": _reference_f32(d, d._synth_x, draws),
                "y": d._synth_y}


@pytest.mark.parametrize("mean_mode", ["scalar", "image_shared"])
def test_alexnet_costs_same_bits_as_float32_feed(mesh8, mean_mode):
    """Three train steps on the 8-device mesh: the loader's uint8 batches,
    cast and mean-subtracted inside the step program (with the shared
    window's offsets sharded beside the labels), against the float32 batch
    the host pass used to deliver."""
    import jax.numpy as jnp
    from theanompi_tpu.models.alex_net import AlexNet
    from theanompi_tpu.models.data.imagenet import RAW
    from theanompi_tpu.parallel.exchanger import BSP_Exchanger

    cfg = {"mesh": mesh8, "size": 8, "rank": 0, "verbose": False,
           "batch_size": 1, "synthetic_batches": 4, "n_class": 10,
           "compute_dtype": jnp.float32, "seed": 3}
    mean = (np.random.RandomState(2).rand(RAW, RAW, 3) * 255).astype(
        np.float32) if mean_mode == "image_shared" else np.float32(122.0)

    def costs(feed_float):
        m = AlexNet(dict(cfg))
        m.data.img_mean = mean
        if feed_float:
            m.data = _FedFloat32(m.data)
        m.compile_iter_fns(BSP_Exchanger(cfg))
        out = []
        for i in range(1, 4):
            m.train_iter(i, None)
            out.append(float(m.current_info["cost"]))
        return m, out

    m, wire = costs(False)
    assert all(np.isfinite(wire))
    assert costs(True)[1] == wire
    # the VAL path stages uint8 too (stage_input is shared — a raw 0..255
    # val input would score garbage silently)
    m.begin_val()
    m.val_iter(0)
    m.end_val()


def test_mean_image_survives_para_load(tmp_path):
    """With para_load on, the model's data is a PrefetchLoader:
    stage_input must still read the REAL mean image through the wrapper
    and cut it under the batch's window, not fall back to the scalar."""
    import subprocess
    import sys as _sys

    import jax.numpy as jnp
    from theanompi_tpu.models.alex_net import AlexNet
    from theanompi_tpu.models.data.prefetch import PrefetchLoader
    from theanompi_tpu.parallel.mesh import worker_mesh

    d = str(tmp_path / "mini_imagenet")
    subprocess.run(
        [_sys.executable, "scripts/make_batch_dataset.py", "--synthetic",
         "4", "--batch-size", "4", "--out", d],
        check=True, capture_output=True, cwd=REPO)
    cfg = {"mesh": worker_mesh(1), "size": 1, "rank": 0, "verbose": False,
           "batch_size": 4, "data_dir": d, "para_load": True,
           "compute_dtype": jnp.float32}
    m = AlexNet(cfg)
    assert isinstance(m.data, PrefetchLoader)
    # the generated img_mean.npy is a full [256,256,3] mean image
    full = np.load(os.path.join(d, "img_mean.npy")).astype(np.float32)
    assert full.ndim == 3 and full.shape[-1] == 3, full.shape
    m.data.shuffle_data(0)
    b = m.data.next_train_batch(0)
    b = {k: np.asarray(v) for k, v in b.items()}
    oy, ox = b["crop_off"][0]
    c = b["x"].shape[1]
    np.testing.assert_array_equal(
        _stage(m.data, b),
        b["x"].astype(np.float32) - full[oy:oy + c, ox:ox + c, :])


def test_aug_wire_u8_is_read_nowhere():
    """The uint8 wire is the only wire: a configuration that still sets
    the old switch finds no reader to honour it in some other way."""
    hits = []
    for top in ("theanompi_tpu", "scripts"):
        path = os.path.join(REPO, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
            if f.endswith((".py", ".cc"))]
        for f in files:
            with open(f, encoding="utf-8") as fh:
                if re.search(r"aug_wire|wire_u8", fh.read()):
                    hits.append(os.path.relpath(f, REPO))
    assert hits == []


def test_so_built_from_other_source_is_not_reused(tmp_path, monkeypatch):
    """The built library is keyed on loader.cc + the compiler flags: a file
    left behind by another source or other flags (the chip tool copies the
    working tree, ignored files included) is never picked up."""
    src = tmp_path / "loader.cc"
    with open(native._SRC, "rb") as f:
        src.write_bytes(f.read())
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    first = native._so_path()
    with open(first, "wb") as f:
        f.write(b"an earlier build of this very source")
    assert native._build() == first              # same key: reused as is

    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    second = native._so_path()
    assert second != first
    built = native._build()
    if built is None:
        pytest.skip("no native toolchain in this environment")
    assert built == second
    import ctypes
    assert ctypes.CDLL(built).tmpi_loader_abi_version() == 2

    monkeypatch.setattr(native, "_CXXFLAGS", native._CXXFLAGS + ("-DX=1",))
    assert native._so_path() not in (first, second)
