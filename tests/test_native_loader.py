"""Native C++ loader hot path vs the NumPy reference implementation.

The native library (theanompi_tpu/native/loader.cc) must be bit-identical to
the NumPy fallback for every supported mode: both compute
``float32(uint8) - float32(mean)`` with no intermediate rounding, so exact
equality is the correct assertion (not allclose).
"""

import numpy as np
import pytest

from theanompi_tpu import native


def _params(rng, n, h, w, crop, per_image):
    m = n if per_image else 1
    oy = rng.randint(0, h - crop + 1, size=m).astype(np.int32)
    ox = rng.randint(0, w - crop + 1, size=m).astype(np.int32)
    flip = rng.randint(0, 2, size=m).astype(np.uint8)
    return oy, ox, flip


@pytest.mark.parametrize("per_image", [False, True])
@pytest.mark.parametrize("layout", ["nhwc", "nchw"])
@pytest.mark.parametrize("mean_kind", ["scalar", "image"])
def test_native_matches_numpy(per_image, layout, mean_kind):
    if not native.native_available():
        pytest.skip("no native toolchain in this environment")
    rng = np.random.RandomState(0)
    n, h, w, c, crop = 7, 20, 24, 3, 13
    x = rng.randint(0, 256, (n, h, w, c), dtype=np.uint8)
    if layout == "nchw":
        x = np.ascontiguousarray(x.transpose(0, 3, 1, 2))
    oy, ox, flip = _params(rng, n, h, w, crop, per_image)
    mean = (rng.randn(crop, crop, c).astype(np.float32) * 10
            if mean_kind == "image" else None)
    ms = 0.0 if mean_kind == "image" else 117.5

    got = native.augment_batch(x, oy, ox, flip, crop, mean=mean,
                               mean_scalar=ms)
    want = native._augment_numpy(
        x, np.broadcast_to(oy, (n,)), np.broadcast_to(ox, (n,)),
        np.broadcast_to(flip, (n,)), crop, mean, ms)
    assert got.shape == (n, crop, crop, c)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


def test_single_thread_matches_multi():
    if not native.native_available():
        pytest.skip("no native toolchain in this environment")
    rng = np.random.RandomState(1)
    n, h, w, c, crop = 16, 32, 32, 3, 27
    x = rng.randint(0, 256, (n, h, w, c), dtype=np.uint8)
    oy, ox, flip = _params(rng, n, h, w, crop, True)
    a = native.augment_batch(x, oy, ox, flip, crop, n_threads=1)
    b = native.augment_batch(x, oy, ox, flip, crop, n_threads=8)
    np.testing.assert_array_equal(a, b)


def test_imagenet_data_uses_fused_pass():
    """The ImageNet data object routes through augment_batch in both
    synthetic and per-image modes and produces the contract shapes."""
    from theanompi_tpu.models.data.imagenet import ImageNet_data

    d = ImageNet_data({"size": 1, "synthetic_batches": 2, "n_class": 10,
                       "aug_per_image": True}, batch_size=4)
    b = d.next_train_batch(0)
    assert b["x"].shape == (4, 227, 227, 3) and b["x"].dtype == np.float32
    assert b["y"].shape == (4,) and b["y"].dtype == np.int32
    v = d.next_val_batch(0)
    assert v["x"].shape == (4, 227, 227, 3)


@pytest.mark.parametrize("per_image", [False, True])
def test_u8_wire_mode_matches_f32_pipeline(per_image):
    """round-4 u8-wire lever: uint8 crops shipped to device + on-device
    float32 cast/mean-subtract must equal the host fused pass bit-for-bit
    (scalar mean; identical augmentation RNG draws)."""
    from theanompi_tpu.models.data.imagenet import ImageNet_data

    cfg = {"size": 1, "synthetic_batches": 2, "n_class": 10,
           "aug_per_image": per_image, "seed": 5}
    f32 = ImageNet_data(dict(cfg), batch_size=4)
    u8 = ImageNet_data(dict(cfg, aug_wire_u8=True), batch_size=4)
    f32.shuffle_data(0)
    u8.shuffle_data(0)
    a = f32.next_train_batch(0)
    b = u8.next_train_batch(0)
    assert b["x"].dtype == np.uint8 and a["x"].dtype == np.float32
    np.testing.assert_array_equal(a["y"], b["y"])
    # device-side arithmetic (float32(u8) - scalar mean) == host fused pass
    mean = float(u8.img_mean)
    np.testing.assert_array_equal(
        a["x"], b["x"].astype(np.float32) - np.float32(mean))
    # val path: center crop, no mirror
    av, bv = f32.next_val_batch(0), u8.next_val_batch(0)
    np.testing.assert_array_equal(
        av["x"], bv["x"].astype(np.float32) - np.float32(mean))


def test_u8_wire_trains_alexnet_smoke(mesh8):
    """End to end: AlexNet consumes the uint8 batch, the ModelBase loss
    path casts+subtracts on device, and a train step runs finite."""
    import jax
    import jax.numpy as jnp
    from theanompi_tpu.models.alex_net import AlexNet
    from theanompi_tpu.parallel.exchanger import BSP_Exchanger
    from theanompi_tpu.parallel.mesh import worker_mesh

    mesh = worker_mesh(2)
    cfg = {"mesh": mesh, "size": 2, "rank": 0, "verbose": False,
           "batch_size": 4, "synthetic_batches": 2, "aug_wire_u8": True,
           "compute_dtype": jnp.float32}
    m = AlexNet(cfg)
    m.compile_iter_fns(BSP_Exchanger(cfg))
    m.data.shuffle_data(0)
    m.train_iter(1, None)
    cost = float(m.current_info["cost"])
    assert np.isfinite(cost)
    # the VAL path stages u8 too (ModelBase.stage_input is shared — a raw
    # 0..255 val input would score garbage silently)
    m.begin_val()
    m.val_iter(0)
    m.end_val()


def test_u8_wire_mean_survives_para_load(tmp_path):
    """Regression (round-4 review): with para_load on, the model's data is
    a PrefetchLoader — the u8-wire device mean must still read the REAL
    mean image through the wrapper, not fall back to the scalar 122."""
    import subprocess
    import sys as _sys

    import jax.numpy as jnp
    from theanompi_tpu.models.alex_net import AlexNet
    from theanompi_tpu.parallel.mesh import worker_mesh

    d = str(tmp_path / "mini_imagenet")
    subprocess.run(
        [_sys.executable, "scripts/make_batch_dataset.py", "--synthetic",
         "4", "--batch-size", "4", "--out", d],
        check=True, capture_output=True)
    cfg = {"mesh": worker_mesh(1), "size": 1, "rank": 0, "verbose": False,
           "batch_size": 4, "data_dir": d, "para_load": True,
           "aug_wire_u8": True, "compute_dtype": jnp.float32}
    m = AlexNet(cfg)
    from theanompi_tpu.models.data.prefetch import PrefetchLoader
    assert isinstance(m.data, PrefetchLoader)
    mean = np.asarray(m._u8_input_mean())
    # the generated img_mean.npy is a full [256,256,3] mean image — the
    # device constant must be its center crop, not a scalar
    assert mean.ndim == 3 and mean.shape[-1] == 3, mean.shape
    import os as _os
    full = np.load(_os.path.join(d, "img_mean.npy"))
    c = mean.shape[0]
    cy, cx = (full.shape[0] - c) // 2, (full.shape[1] - c) // 2
    np.testing.assert_allclose(mean, full[cy:cy + c, cx:cx + c, :],
                               rtol=1e-6)


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
    assert ctypes.CDLL(built).tmpi_loader_abi_version() == 1

    monkeypatch.setattr(native, "_CXXFLAGS", native._CXXFLAGS + ("-DX=1",))
    assert native._so_path() not in (first, second)
