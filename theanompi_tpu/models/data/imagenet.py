"""ImageNet data object.

Reference: ``theanompi/models/data/imagenet.py`` (SURVEY.md §2.8) — ImageNet
pre-processed offline into hickle ``.hkl`` files (one file = one 128-image
uint8 batch, inherited from ``uoguelph-mlrg/theano_alexnet``), a mean image
``.npy``, shuffled shard lists with a common seed, and random-crop(256→227)
+ horizontal-mirror augmentation on CPU.

This rebuild keeps that on-disk contract so existing data prep works:
``config['data_dir']`` (or ``$IMAGENET_DIR``) must contain ``train_hkl/`` and
``val_hkl/`` of batch files plus ``img_mean.npy``.  ``.hkl`` is read via
hickle when installed, with a ``.npy``/``.npz`` fallback per file extension.
Batches leave the host as uint8 crops; the step program casts them and
subtracts the mean (``ModelBase.stage_input``).
Without a data dir it synthesizes deterministic random uint8 image batches —
enough for throughput benchmarking and pipeline tests, where only
shapes and rates matter.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

import numpy as np

RAW = 256       # stored image side (reference batch files are 256×256)
CROP = 227      # AlexNet crop (VGG uses 224; configurable)
N_CLASS = 1000


def _load_hkl_h5py(path: str) -> np.ndarray:
    """hickle ``.hkl`` files ARE HDF5 files: read the payload with h5py
    directly (hickle itself is not in this environment).  All hickle versions
    store the array as an HDF5 dataset — commonly named ``data`` or
    ``data_0`` at the root (v1–v3, the era of the reference's files) or
    nested under a group (v4+); take the first dataset found."""
    import h5py

    with h5py.File(path, "r") as f:
        for name in ("data", "data_0"):
            if name in f and isinstance(f[name], h5py.Dataset):
                return np.asarray(f[name])
        found = []

        def visit(name, obj):
            if isinstance(obj, h5py.Dataset):
                found.append((obj.size, name))

        f.visititems(visit)
        if not found:
            raise ValueError(f"{path}: no dataset inside the HDF5/.hkl file")
        # v4+ nests the payload among small metadata datasets — the image
        # batch is by far the largest one.
        return np.asarray(f[max(found)[1]])


def _load_batch_file(path: str) -> np.ndarray:
    if path.endswith(".hkl"):
        try:
            import hickle  # optional dep, as in the reference
            return np.asarray(hickle.load(path))
        except ImportError:
            return _load_hkl_h5py(path)
        except Exception as hickle_err:
            # File is HDF5 but not hickle-shaped (plain h5py-written batch
            # files). If the h5py reader can't make sense of it either,
            # surface the ORIGINAL hickle error, not the fallback's.
            try:
                return _load_hkl_h5py(path)
            except Exception:
                raise hickle_err
    if path.endswith(".npz"):
        with np.load(path) as z:
            return z[list(z.files)[0]]
    return np.load(path)


class ImageNet_data:
    """Sharded batch-file loader with reference augmentation semantics.

    Unlike the in-memory :class:`DataBase`, this is file-batch oriented like
    the reference: an epoch is a shuffled list of batch FILES; each training
    step concatenates ``size`` files' worth of images into the global batch.
    """

    def __init__(self, config: Optional[dict] = None, batch_size: int = 128,
                 crop: int = CROP):
        from . import _host_topology
        self.config = dict(config or {})
        self.size = self.config.get("size", 1)
        self.batch_size = batch_size
        self.global_batch = self.size * batch_size
        self.procs, self.proc_id = _host_topology(self.config)
        assert self.size % self.procs == 0, (
            f"{self.size} workers not divisible by {self.procs} hosts")
        self.crop = int(self.config.get("crop_size", crop))
        self.rng = np.random.RandomState(self.config.get("seed", 42))

        d = self.config.get("data_dir") or os.environ.get("IMAGENET_DIR")
        if d and os.path.isdir(os.path.join(d, "train_hkl")):
            self._init_real(d)
            self.synthetic = False
        else:
            self._init_synthetic()
            self.synthetic = True
        self._train_ptr = 0
        self._val_ptr = 0
        self._shuffle_seed = None
        self._perm = np.arange(len(self.train_files)) if not self.synthetic \
            else None

    # -- real batch files ---------------------------------------------------

    def _init_real(self, d: str) -> None:
        def listdir(sub):
            p = os.path.join(d, sub)
            return sorted(os.path.join(p, f) for f in os.listdir(p)
                          if f.split(".")[-1] in ("hkl", "npy", "npz"))

        self.train_files: List[str] = listdir("train_hkl")
        self.val_files: List[str] = listdir("val_hkl")
        self.train_labels = np.load(os.path.join(d, "train_labels.npy"))
        self.val_labels = np.load(os.path.join(d, "val_labels.npy"))
        mean_path = os.path.join(d, "img_mean.npy")
        self.img_mean = (np.load(mean_path).astype(np.float32)
                         if os.path.exists(mean_path) else
                         np.float32(122.0))
        if isinstance(self.img_mean, np.ndarray) and self.img_mean.ndim == 3:
            # normalize a reference c01 (CHW) mean to HWC once, not per batch
            self.img_mean = self._mean_to_hwc(self.img_mean)
        files_per_step = self.size
        self.n_batch_train = len(self.train_files) // files_per_step
        self.n_batch_val = max(1, len(self.val_files) // files_per_step)
        # multi-host needs equal per-host file shards every val step (the
        # max(1, ...) single-host fallback would index past the list)
        assert self.procs == 1 or len(self.val_files) >= files_per_step, (
            f"{len(self.val_files)} val files < {files_per_step} per step "
            f"with {self.procs} hosts")

    # -- synthetic ----------------------------------------------------------

    def _init_synthetic(self) -> None:
        self.n_batch_train = int(self.config.get("synthetic_batches", 64))
        self.n_batch_val = int(self.config.get("synthetic_val_batches", 4))
        self.train_files = self.val_files = []
        self.img_mean = np.float32(122.0)
        # One cached uint8 batch, re-used every step (throughput only).  Each
        # host draws ONLY its local rows from a host-keyed stream — O(local)
        # time and RAM (at pod scale the full global megabatch would be GBs
        # of dead work per host); distinct hosts get distinct data.
        per = self.global_batch // self.procs
        r = np.random.RandomState([0, self.proc_id])
        self._synth_x = r.randint(0, 256, (per, RAW, RAW, 3), dtype=np.uint8)
        n_class = int(self.config.get("n_class", N_CLASS))
        self._synth_y = r.randint(0, n_class, per).astype(np.int32)

    # -- contract ------------------------------------------------------------

    def shuffle_data(self, seed: int) -> None:
        """Common-seed shuffle of the batch-FILE list (reference semantics:
        all ranks shuffle identically, each takes its stride)."""
        if not self.synthetic:
            self._perm = np.random.RandomState(seed).permutation(
                len(self.train_files))
        self._shuffle_seed = int(seed)
        self._train_ptr = 0
        self._val_ptr = 0

    # -- checkpoint cursor --------------------------------------------------
    def get_cursor(self) -> Dict:
        """Shuffle seed + batch pointers + augmentation RNG state: enough to
        resume the exact sample/crop/mirror stream mid-epoch."""
        keys, pos, has_gauss, cached = self.rng.get_state()[1:]
        return {"shuffle_seed": self._shuffle_seed,
                "train_ptr": int(self._train_ptr),
                "val_ptr": int(self._val_ptr),
                "aug_rng_keys": np.asarray(keys),
                "aug_rng_pos": int(pos),
                "aug_rng_has_gauss": int(has_gauss),
                "aug_rng_cached": float(cached)}

    def set_cursor(self, cursor: Dict) -> None:
        if cursor.get("shuffle_seed") is not None:
            self.shuffle_data(int(cursor["shuffle_seed"]))
        self._train_ptr = int(cursor.get("train_ptr", 0))
        self._val_ptr = int(cursor.get("val_ptr", 0))
        if "aug_rng_keys" in cursor:
            self.rng.set_state(("MT19937",
                                np.asarray(cursor["aug_rng_keys"], np.uint32),
                                int(cursor["aug_rng_pos"]),
                                int(cursor["aug_rng_has_gauss"]),
                                float(cursor["aug_rng_cached"])))

    def _local_files(self, lo: int):
        """This host's slice of the step's ``size`` batch files (each MPI
        rank in the reference loaded only its own file — here each HOST
        loads only its chips' files)."""
        per = self.size // self.procs
        start = lo + self.proc_id * per
        return range(start, start + per)

    def plan_train_batch(self, count: int) -> Dict:
        """Advance the cursor AND the augmentation RNG, returning a pure
        PLAN (round-4 parallel producer): :meth:`materialize` turns a plan
        into the batch statelessly, so a thread pool can materialize
        several plans concurrently while the draws stay sequential — the
        batch stream is bit-identical to the serial path."""
        if self.synthetic:    # _synth_x/_synth_y are already host-local
            n = self._synth_x.shape[0]
            return {"files": None,
                    "draws": self._draw(n, RAW, RAW, train=True)}
        i = self._train_ptr % self.n_batch_train
        self._train_ptr += 1
        idx = [int(self._perm[j]) for j in self._local_files(i * self.size)]
        n = len(idx) * self.batch_size
        h, w = self._stored_hw()
        return {"files": idx, "draws": self._draw(n, h, w, train=True)}

    def _stored_hw(self):
        """Stored image dims, read ONCE from the first batch file (plan-time
        draws must match what materialize will load; the .npy fallback
        accepts non-256 sizes)."""
        if getattr(self, "_hw", None) is None:
            x0 = self._to_nhwc(_load_batch_file(self.train_files[0]))
            self._hw = (int(x0.shape[1]), int(x0.shape[2]))
        return self._hw

    def materialize(self, plan: Dict) -> Dict[str, np.ndarray]:
        """Stateless plan → batch (thread-safe: reads only immutable
        fields; all RNG happened at plan time)."""
        if plan["files"] is None:
            return self._transform(self._synth_x, self._synth_y,
                                   plan["draws"])
        idx = plan["files"]
        xs = np.concatenate([_load_batch_file(self.train_files[j])
                             for j in idx])
        ys = np.concatenate([self.train_labels[j * self.batch_size:
                                               (j + 1) * self.batch_size]
                             for j in idx])
        return self._transform(self._to_nhwc(xs), ys.astype(np.int32),
                               plan["draws"])

    def next_train_batch(self, count: int) -> Dict[str, np.ndarray]:
        return self.materialize(self.plan_train_batch(count))

    def next_val_batch(self, count: int) -> Dict[str, np.ndarray]:
        if self.synthetic:
            return self._augment(self._synth_x, self._synth_y, train=False)
        i = self._val_ptr % self.n_batch_val
        self._val_ptr += 1
        # single-host tolerates fewer val files than workers (short final
        # batch, trimmed below so it still splits across the mesh);
        # multi-host asserts at init
        idx = [j for j in self._local_files(i * self.size)
               if j < len(self.val_files)]
        xs = np.concatenate([_load_batch_file(self.val_files[j])
                             for j in idx])
        ys = np.concatenate([self.val_labels[j * self.batch_size:
                                             (j + 1) * self.batch_size]
                             for j in idx])
        keep = (len(ys) // self.size) * self.size
        assert keep > 0, (f"{len(ys)} val images can't split across "
                          f"{self.size} workers")
        return self._augment(self._to_nhwc(xs[:keep]),
                             ys[:keep].astype(np.int32), train=False)

    @staticmethod
    def _to_nhwc(x: np.ndarray) -> np.ndarray:
        """Reference .hkl files are bc01 (N,C,H,W) or c01b; normalize."""
        from ... import native
        if native.is_nchw(x):
            return np.ascontiguousarray(x.transpose(0, 2, 3, 1))
        # c01b legacy layout (C,H,W,B): channel count leads AND the trailing
        # dim is not a channel count (else it's a small NHWC batch)
        if x.ndim == 4 and x.shape[0] in (1, 3) and x.shape[-1] not in (1, 3):
            return np.ascontiguousarray(x.transpose(3, 1, 2, 0))
        return x

    @staticmethod
    def _mean_to_hwc(m: np.ndarray) -> np.ndarray:
        """Normalize a 3-D mean image to (H, W, C)."""
        if m.shape[-1] in (1, 3):
            return m
        if m.shape[0] in (1, 3):      # CHW (the reference's c01 mean)
            return np.ascontiguousarray(m.transpose(1, 2, 0))
        return m

    def _draw(self, n: int, h: int, w: int, train: bool):
        """The augmentation RNG draws — SEQUENTIAL state (plan time)."""
        c = self.crop
        if train:
            per_img = bool(self.config.get("aug_per_image", False))
            m = n if per_img else 1
            oy = self.rng.randint(0, h - c + 1, size=m).astype(np.int32)
            ox = self.rng.randint(0, w - c + 1, size=m).astype(np.int32)
            flip = self.rng.randint(0, 2, size=m).astype(np.uint8)
        else:
            oy = np.full(1, (h - c) // 2, np.int32)
            ox = np.full(1, (w - c) // 2, np.int32)
            flip = np.zeros(1, np.uint8)
        return oy, ox, flip

    def _augment(self, x: np.ndarray, y: np.ndarray,
                 train: bool) -> Dict[str, np.ndarray]:
        """Reference augmentation: random 256→crop window + horizontal
        mirror at train time (one draw per batch, as the reference's
        per-batch ``param_rand``); center crop at val.
        ``aug_per_image=True`` in config upgrades to independent per-image
        draws."""
        return self._transform(
            x, y, self._draw(x.shape[0], x.shape[1], x.shape[2], train))

    def _transform(self, x: np.ndarray, y: np.ndarray,
                   draws) -> Dict[str, np.ndarray]:
        """Stateless tail of the augmentation (thread-safe given draws).

        The wire is uint8: the host only gathers (crop window + mirror, one
        native pass, NumPy without a compiler); the cast and the mean
        subtraction are the step program's (``ModelBase.stage_input``),
        which computes the reference's ``float32(pixel) - mean``.  A mean
        IMAGE is subtracted under the drawn window when the batch shares
        one: the batch then carries the window's offsets as ``crop_off``,
        ``int32 [n, 2]`` rows of ``(oy, ox)`` that shard like ``y``.  With
        per-image windows no such leaf exists and the mean's center crop
        serves every image, as it always has."""
        from ... import native
        oy, ox, flip = draws
        # augment_batch refuses a window that leaves the loaded images
        # (plan-time draws against heterogeneous batch-file sizes)
        batch = {"x": native.augment_batch(x, oy, ox, flip, self.crop),
                 "y": np.ascontiguousarray(y, dtype=np.int32)}
        if np.ndim(self.img_mean) == 3 and oy.shape[0] == 1:
            batch["crop_off"] = np.tile(
                np.array([oy[0], ox[0]], np.int32), (x.shape[0], 1))
        return batch
