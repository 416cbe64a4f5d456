"""Wall-clock section timing + metric accumulation.

TPU-native rebuild of Theano-MPI's ``theanompi/lib/recorder.py``
(SURVEY.md §2.10): per-iteration section timers (``t_train`` / ``t_comm`` /
``t_wait`` / ``t_load``), images/sec derivation, train cost/error and val
top-1/top-5 accumulation, periodic console printing, and per-epoch dumps for
offline plotting.  The paper's "time per 5120 images" tables come from this
component, so the bucket names and the 5120-image accounting are preserved.

Additions over the reference: JSONL record emission (alongside the ``.npy``
dumps), an images/sec/chip derivation — the north-star metric in
``BASELINE.json`` — and, since the sums say how long but not when or inside
what, every bracket is also one interval in ``telemetry``'s always-on span
ring (``Recorder.end``), on the Unix clock, where the spans inside
``train_iter`` and the loader name it as their parent.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

from . import telemetry

# The reference reports "time per 5120 images" (40 batches of 128).
IMAGES_PER_REPORT = 5120

# `load` = waiting on the data source (pure dequeue wait under para_load);
# `stage` = consumer-thread host stack + device_put (≈0 when the parallel
# loader's window producer stages dispatch inputs off the hot path) — the
# split makes the producer/consumer overlap win visible in records.
# `compile` = building the iteration functions (worker.py brackets
# compile_iter_fns): jit wrappers built and the state placed on the mesh,
# about a second cold or warm (ring span `compile.place`).  It holds NO XLA
# compile: the jit is lazy, so the compile, or the load from the persistent
# cache, lands in the first `train` bracket; the ring span `compile.xla`
# (telemetry.watch_compiles) times it wherever it happens.
# The list itself lives in telemetry.PHASES — ONE source of truth for the
# recorder buckets, the t_<section> record keys below, and the telemetry
# phase-event names (the tpulint schema-drift checker guards the sync).
SECTIONS = telemetry.PHASES

# the per-print record carries every section except `val` (val time is
# reported cumulatively by print_val_info) — derived, so it cannot drift
RECORD_KEYS = tuple("t_" + s for s in SECTIONS if s != "val")


class Recorder:
    """Three-bucket (plus load/val) wall-clock recorder.

    Usage mirrors the reference: the worker hot loop brackets each phase with
    ``recorder.start()`` / ``recorder.end('train')``, accumulates metrics with
    ``train_error`` / ``val_error``, and prints every ``printFreq`` iterations
    with ``print_train_info(count)``.
    """

    # the process-wide telemetry registry (worker.py re-points this at the
    # live instance); the class default is the inert no-op, so recorders
    # built outside a Worker cost one attribute check per bracket
    telemetry = telemetry.DISABLED

    def __init__(self, config: Optional[dict] = None):
        config = config or {}
        self.verbose: bool = config.get("verbose", True)
        self.rank: int = config.get("rank", 0)
        self.size: int = config.get("size", 1)
        self.printFreq: int = config.get("printFreq", 40)
        self.record_dir: str = config.get("record_dir", "./inc")

        self._open = None             # the ring frame of start()
        self.t_sec: Dict[str, float] = defaultdict(float)  # running, since last print
        self.t_sec_total: Dict[str, float] = defaultdict(float)

        self._train_cost: List[float] = []
        self._train_error: List[float] = []
        self._val_cost: List[float] = []
        self._val_error: List[float] = []
        self._val_error_top5: List[float] = []

        self.n_images: int = 0  # images since last print
        self.n_images_total: int = 0
        self.epoch_records: List[dict] = []
        self._all_records: List[dict] = []
        self._wall_start = time.time()
        self._last_print_wall = self._wall_start

    # -- timing ------------------------------------------------------------

    def start(self) -> None:
        if self._open is not None:      # one bracket at a time: the new
            telemetry.drop_bracket(self._open)      # start() wins
        self._open = telemetry.open_bracket()

    def end(self, section: str) -> float:
        """Close the bracket: the sums as ever, and one row in the
        always-on span ring (``telemetry.spans()``) under the section's
        name, parent of the spans opened inside it."""
        assert self._open is not None, "Recorder.end() without start()"
        b, self._open = self._open, None
        dt = telemetry.close_bracket(b, section) / 1e9
        self.t_sec[section] += dt
        self.t_sec_total[section] += dt
        # per-dispatch phase events: one histogram sample + one stream
        # event per bracket — the raw material for telemetry_report's
        # tail percentiles and straggler ranking.  Disabled ≡ one
        # attribute check.
        if self.telemetry.enabled:
            self.telemetry.phase(section, dt, t0_ns=b.t0,
                                 tid=threading.get_ident())
        return dt

    # -- metric accumulation ----------------------------------------------

    def train_error(self, count: int, cost, error, n_images: int = 0) -> None:
        """``cost``/``error`` may be host floats OR device scalars — they are
        only materialized at print cadence, so async dispatch stays async."""
        self._train_cost.append(cost)
        self._train_error.append(error)
        self.n_images += n_images
        self.n_images_total += n_images

    def val_error(self, count: int, cost: float, error: float, error_top5: float = 0.0) -> None:
        self._val_cost.append(float(cost))
        self._val_error.append(float(error))
        self._val_error_top5.append(float(error_top5))

    # -- reporting ---------------------------------------------------------

    def images_per_sec(self) -> float:
        """Throughput since the last print, from WALL time — honest whether
        the hot loop dispatches asynchronously or blocks per iteration (the
        section buckets only sum to wall time in blocking mode)."""
        t = time.time() - self._last_print_wall
        return self.n_images / t if t > 0 else 0.0

    def time_per_5120(self) -> float:
        """The reference's headline unit: seconds per 5120 images processed."""
        ips = self.images_per_sec()
        return IMAGES_PER_REPORT / ips if ips > 0 else float("inf")

    def print_train_info(self, count: int, stride: int = 1) -> Optional[dict]:
        """``stride`` = steps per train_iter dispatch (``steps_per_call``):
        count then only visits multiples of it.  The gate fires once every
        ``ceil(printFreq / stride)`` dispatches — at least printFreq steps
        apart even when stride does not divide printFreq (the old
        ``count % printFreq < stride`` residue test double-fired inside one
        window in that case) — and the averaging slice counts DISPATCH
        entries, not steps.  Returns the emitted record (the worker keys
        its periodic gauge snapshots off it), or None when gated."""
        k = max(1, -(-self.printFreq // stride))      # ceil division
        if (count // stride) % k != 0:
            return None
        # materializing device scalars happens HERE, once per printFreq
        # iters: the one place the loop waits for the device (span `print`)
        with telemetry.span("print"):
            cost = float(np.mean([np.asarray(c)
                                  for c in self._train_cost[-k:]])) \
                if self._train_cost else float("nan")
            err = float(np.mean([np.asarray(e)
                                 for e in self._train_error[-k:]])) \
                if self._train_error else float("nan")
        rec = {"iter": count, "cost": cost, "error": err}
        for key, s in zip(RECORD_KEYS, (s for s in SECTIONS if s != "val")):
            rec[key] = self.t_sec[s]
        rec.update(
            images_per_sec=self.images_per_sec(),
            images_per_sec_per_chip=self.images_per_sec() / max(self.size, 1),
            time_per_5120=self.time_per_5120(),
            wall=time.time() - self._wall_start,
        )
        self._all_records.append(rec)
        if self.telemetry.enabled:
            # the per-rank throughput timeline telemetry_report draws
            self.telemetry.event("train_record", **rec)
        if self.verbose and self.rank == 0:
            print(
                f"iter {count}: cost {cost:.4f} err {err:.4f} | "
                f"train {rec['t_train']:.3f}s comm {rec['t_comm']:.3f}s "
                f"wait {rec['t_wait']:.3f}s load {rec['t_load']:.3f}s "
                f"stage {rec['t_stage']:.3f}s"
                + (f" compile {rec['t_compile']:.3f}s"
                   if rec['t_compile'] > 0 else "") + " | "
                f"{rec['images_per_sec']:.1f} img/s "
                f"({rec['images_per_sec_per_chip']:.1f}/chip, "
                f"{rec['time_per_5120']:.2f}s per 5120)",
                flush=True,
            )
        for s in SECTIONS:
            self.t_sec[s] = 0.0
        self.n_images = 0
        self._last_print_wall = time.time()
        return rec

    def print_val_info(self, count: int) -> dict:
        rec = {
            "iter": count,
            "val_cost": float(np.mean(self._val_cost)) if self._val_cost else float("nan"),
            "val_error": float(np.mean(self._val_error)) if self._val_error else float("nan"),
            "val_error_top5": (
                float(np.mean(self._val_error_top5)) if self._val_error_top5 else float("nan")
            ),
            "t_val": self.t_sec_total["val"],
            # cumulative: shows compile going to ~0 on a cache-hit resume
            "t_compile": self.t_sec_total["compile"],
        }
        self.epoch_records.append(rec)
        if self.telemetry.enabled:
            self.telemetry.event("val_record", **rec)
        if self.verbose and self.rank == 0:
            print(
                f"validation @ iter {count}: cost {rec['val_cost']:.4f} "
                f"top-1 err {rec['val_error']:.4f} top-5 err {rec['val_error_top5']:.4f}",
                flush=True,
            )
        self._val_cost, self._val_error, self._val_error_top5 = [], [], []
        return rec

    def clear_train_info(self) -> None:
        self._train_cost, self._train_error = [], []

    # -- persistence (reference dumps .npy records; we add JSONL) ----------

    def save(self, record_dir: Optional[str] = None) -> None:
        d = record_dir or self.record_dir
        os.makedirs(d, exist_ok=True)
        np.save(os.path.join(d, f"inforec_rank{self.rank}.npy"),
                np.array(self._all_records, dtype=object))
        with open(os.path.join(d, f"inforec_rank{self.rank}.jsonl"), "w") as f:
            for rec in self._all_records:
                f.write(json.dumps(rec) + "\n")
            for rec in self.epoch_records:
                f.write(json.dumps(rec) + "\n")

    def load(self, record_dir: Optional[str] = None) -> None:
        """Restore BOTH record lists, preferring the JSONL (the only dump
        that holds the epoch/validation records — the ``.npy`` carries the
        train records alone).  A resumed run's next ``save()`` then
        rewrites the JSONL with the pre-resume epoch lines intact:
        save → load → save is lossless (json float round-trips are exact).

        Epoch records are recognized by their ``val_cost`` key — the field
        ``print_val_info`` always writes and ``print_train_info`` never
        does."""
        d = record_dir or self.record_dir
        jl = os.path.join(d, f"inforec_rank{self.rank}.jsonl")
        if os.path.exists(jl):
            train: List[dict] = []
            epoch: List[dict] = []
            with open(jl) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        # a worker killed mid-save leaves a truncated last
                        # line; a resume must shrug it off, not crash-loop
                        # the supervisor on every retry
                        continue
                    (epoch if "val_cost" in rec else train).append(rec)
            self._all_records, self.epoch_records = train, epoch
            return
        path = os.path.join(d, f"inforec_rank{self.rank}.npy")
        if os.path.exists(path):
            self._all_records = list(np.load(path, allow_pickle=True))
