"""Background prefetch pipeline — the parallel loader.

TPU-native rebuild of Theano-MPI's flagship data-pipeline feature
(SURVEY.md §2.8): the reference spawned a child process per worker via
``MPI.COMM_SELF.Spawn`` that loaded the next ``.hkl`` batch, augmented it on
CPU, and wrote it straight into the trainer's GPU input buffer through a CUDA
IPC handle, overlapping I/O+augment with compute behind a per-batch
handshake.

On TPU the IPC trick is unnecessary: a background thread runs the (host,
numpy) load+augment for the NEXT batches while the device computes, and
``jax.device_put`` streams the result to the chips asynchronously.  The
"icomm barrier" handshake becomes a bounded queue: depth 2 = classic double
buffering.

Wrap any data object:  ``data = PrefetchLoader(Cifar10_data(cfg))`` — the
wrapper exposes the same duck-typed surface (``next_train_batch``,
``next_val_batch``, ``shuffle_data``, ``n_batch_train``, ``n_batch_val``), so
``para_load`` is a config flag exactly as in the reference.
"""

from __future__ import annotations

import itertools
import queue
import threading
from typing import Optional

from ...utils import telemetry


def _nbytes(batch) -> int:
    """Host bytes of a batch's leaves (a dict of arrays)."""
    leaves = batch.values() if hasattr(batch, "values") else ()
    return sum(int(getattr(v, "nbytes", 0)) for v in leaves)


def _stack_host(batches):
    """Host-side ``[k, ...]`` stack of k per-step batches — delegates to
    ``steps.stack_host`` (lazy: keeps this module importable without
    jax) so the window layout has exactly one definition."""
    from ...parallel.steps import stack_host
    return stack_host(batches)


class PrefetchLoader:
    """Double-buffered background loader over any DataBase-shaped object.

    ``n_workers > 1`` (round-4, SURVEY §7 "input pipeline at AlexNet
    speeds"): when the wrapped data object exposes the ``plan_train_batch``
    / ``materialize`` split (``ImageNet_data``), the producer draws plans
    SEQUENTIALLY (cursor + augmentation RNG stay exact) and a thread pool
    materializes several in flight — disk reads and the native augment
    release the GIL, so file-based pipelines scale near-linearly.  The
    bounded queue holds ordered futures: the batch STREAM is bit-identical
    to the serial path, whatever the pool size.

    ``set_window(k, stage_fn)`` (``steps_per_call`` > 1): production goes
    WINDOW-granular — the queue holds whole ``[k, ...]`` dispatch inputs,
    staged to the mesh by the producer, consumed via
    ``next_train_window`` (docs/design.md §9).

    Every producer writes the same spans into ``telemetry``'s always-on
    ring — ``input.plan`` and ``input.enqueue`` (the blocking ``q.put``) on
    the producer thread, ``input.materialize`` and ``input.device_put`` on
    whichever thread does the work (the pool's, when there is one) — and
    the consumer writes ``load.dequeue``.  All carry the
    queue item's id (``last_batch_id`` after a dequeue), so one batch can
    be followed from its plan to the step that trains on it.
    ``input.device_put`` times the host call only: ``jax.device_put``
    returns before PJRT's threads have transposed and transferred the
    batch; whether they had finished by the time the batch was dequeued is
    the counter ``input.unready_dequeues`` (``is_ready()``, non-blocking)."""

    def __init__(self, data, depth: int = 2, device_put_fn=None,
                 n_workers: int = 1):
        self._data = data
        self.depth = depth
        self.n_workers = max(1, int(n_workers))
        self._device_put_fn = device_put_fn  # optional: stage host→device too
        self.window = 0                      # set_window: spc window mode
        self._stage_window_fn = None
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        # per-producer stop event: a timed-out old producer must keep seeing
        # ITS stop flag set after a restart (a shared cleared Event would
        # revive it against the new queue / shared data object)
        self._stop: Optional[threading.Event] = None
        self._consumed_cursor: dict = {}
        # ids of queue items, unique across epochs and restarts; the id of
        # the item dequeued last is what train_iter stamps on its spans
        self._ids = itertools.count(1)
        self.last_batch_id: Optional[int] = None

    def set_window(self, k: int, stage_fn=None) -> None:
        """Switch to WINDOW-granular production (``steps_per_call`` > 1):
        the producer assembles whole spc windows — k sequential draws
        (cursor/augmentation RNG stay exact), ONE host ``np.stack`` to
        ``[k, ...]`` leaves, one ``stage_fn(window)`` (normally
        ``steps.stage_window`` bound to the mesh) — so the bounded queue
        holds DEVICE-RESIDENT windows, depth 2 = double buffering of
        entire dispatch inputs, and the consumer dequeues via
        :meth:`next_train_window` and dispatches immediately.

        ``k <= 1`` reverts to per-batch production.  ``stage_fn=None``
        leaves the window on the host (tests; the consumer's
        ``put_batch_stack`` then stages it).  ``device_put_fn`` (per-batch
        staging) is ignored while window mode is on — staging happens once
        per window.  A live producer is restarted so the queue granularity
        switches immediately; ``model_base.compile_iter_fns`` calls this
        before the first ``shuffle_data``."""
        k = int(k)
        was = (self.window, self._stage_window_fn)
        self.window = k if k > 1 else 0
        self._stage_window_fn = stage_fn if self.window else None
        if self._thread is not None and \
                (self.window, self._stage_window_fn) != was:
            self._shutdown()
            # rewind to the last CONSUMED position before restarting: the
            # old producer ran ahead and the drained queue held up to
            # ``depth`` unconsumed items — resuming from the wrapped
            # data's live cursor would silently skip them.  Cursor-less
            # duck-typed data can't rewind and degrades to the wrapped
            # object's live position (the set_cursor contract above).
            if self._consumed_cursor and hasattr(self._data, "set_cursor"):
                self._data.set_cursor(self.get_cursor())
            self._restart_producer()

    # duck-typed passthrough surface ---------------------------------------
    @property
    def n_batch_train(self):
        return self._data.n_batch_train

    @property
    def n_batch_val(self):
        return self._data.n_batch_val

    @property
    def batch_size(self):
        return self._data.batch_size

    @property
    def global_batch(self):
        return self._data.global_batch

    def __getattr__(self, name):
        # duck-typed passthrough for anything the wrapper doesn't override
        # (img_mean for stage_input's device mean, synthetic, …) —
        # __getattr__ fires only for MISSING attributes, so the wrapper's
        # own surface wins.  Private/dunder lookups raise normally (also
        # prevents recursion before __init__ sets _data).
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._data, name)

    def shuffle_data(self, seed: int) -> None:
        """Reference cadence: called at epoch start; (re)starts the producer
        for one epoch's worth of train batches."""
        self._shutdown()
        self._data.shuffle_data(seed)
        self._restart_producer()

    # -- checkpoint cursor --------------------------------------------------
    # The producer runs AHEAD of training, so the wrapped data object's
    # cursor is up to ``depth`` batches past what the trainer has consumed.
    # Each queue item therefore carries the wrapped cursor as of *after* that
    # batch was generated; get_cursor reports the last consumed one, making
    # mid-epoch save/resume exact even with para_load on.

    def get_cursor(self):
        c = dict(self._consumed_cursor)
        # val batches are served synchronously on the consumer thread, so the
        # wrapped object's val_ptr is live and authoritative — the producer
        # snapshot only tracks the train stream
        if hasattr(self._data, "get_cursor"):
            c["val_ptr"] = self._data.get_cursor().get("val_ptr", 0)
        return c

    def set_cursor(self, cursor) -> None:
        self._shutdown()
        if hasattr(self._data, "set_cursor"):
            self._data.set_cursor(cursor)
        # else: cursor-less duck-typed data — resume degrades gracefully to
        # wherever the wrapped object stands (same contract as get_cursor's
        # empty dict)
        self._restart_producer()

    def _restart_producer(self) -> None:
        self._consumed_cursor = self._data.get_cursor() \
            if hasattr(self._data, "get_cursor") else {}
        n = self._data.n_batch_train
        # batches left in the current epoch (ptr%n == 0 → a fresh epoch)
        remaining = n - int(self._consumed_cursor.get("train_ptr", 0)) % n
        # pooled producer: the queue must hold one future per in-flight
        # materialization or q.put blocks the submit loop at depth+1 and
        # caps the effective pool (review finding)
        pooled = self.n_workers > 1 and hasattr(self._data,
                                                "plan_train_batch") \
            and not self.window
        self._q = queue.Queue(
            maxsize=self.depth + (self.n_workers if pooled else 0))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._producer, args=(remaining, self._q, self._stop),
            daemon=True)
        self._thread.start()

    def next_train_batch(self, count: int):
        if self.window > 1 and self._q is not None:
            raise RuntimeError(
                "window mode is active — the queue holds whole "
                f"[{self.window}, ...] windows; consume via "
                "next_train_window (or set_window(0) first)")
        if self._q is None:          # shuffle_data not called yet (smoke use)
            return self._maybe_put(self._data.next_train_batch(count))
        batch, cursor = self._dequeue()
        if hasattr(batch, "result"):     # pooled producer: an ordered future
            batch = batch.result()       # (re-raises materialize errors)
        # commit the cursor only AFTER the batch is in hand — a failed
        # materialize must not mark its batch consumed
        self._consumed_cursor = cursor
        self._count_dequeue(batch)
        return batch

    def next_train_window(self, count: int):
        """Dequeue one whole spc window — ALREADY staged to the mesh when
        ``set_window`` got a ``stage_fn`` (queue items are device-resident:
        the consumer's only cost is the dequeue wait).  ``count`` names the
        LAST step of the window, as in ``train_iter``."""
        assert self.window > 1, "set_window(k) first"
        if self._q is None:          # shuffle_data not called yet (smoke use)
            batches = [self._data.next_train_batch(count - self.window + 1 + j)
                       for j in range(self.window)]
            return self._stage(_stack_host(batches))
        window, cursor = self._dequeue()
        # commit only after the window is in hand (same contract as the
        # per-batch path); the cursor is AT WINDOW GRANULARITY — as of
        # after this window's k-th batch was drawn
        self._consumed_cursor = cursor
        self._count_dequeue(window)
        return window

    def next_val_batch(self, count: int):
        # Validation is per-epoch and cheap relative to training — served
        # synchronously (the reference's loader also only covered train).
        return self._maybe_put(self._data.next_val_batch(count))

    def _dequeue(self):
        """One queue pop -> ``(payload, cursor)``, instrumented: the wait
        as the ring span ``load.dequeue`` (always on), and for the
        registry the queue depth at dequeue (min/p50 in the report — 0
        means the consumer is about to starve) and a starved-dequeue
        counter.  Disabled telemetry ≡ one attribute check.  A producer's
        error surfaces here."""
        tm = telemetry.active()
        if tm.enabled:
            depth = self._q.qsize()
            tm.gauge("prefetch.queue_depth", depth)
            tm.observe("prefetch.queue_depth", depth)
            tm.counter("prefetch.dequeues")
            if depth == 0:
                tm.counter("prefetch.starved_dequeues")
        with telemetry.span("load.dequeue") as sp:
            item = self._q.get()
            if isinstance(item, BaseException):
                raise item
            payload, cursor, sp.batch = item
        self.last_batch_id = sp.batch
        return payload, cursor

    @staticmethod
    def _count_dequeue(batch) -> None:
        """``input.dequeues``, and ``input.unready_dequeues`` when a device
        leaf of the batch in hand is still being transposed or transferred
        by the runtime's threads (``is_ready()`` does not block)."""
        telemetry.count("input.dequeues")
        for v in batch.values() if hasattr(batch, "values") else ():
            if hasattr(v, "is_ready") and not v.is_ready():
                telemetry.count("input.unready_dequeues")
                return

    # producer -------------------------------------------------------------
    def _producer(self, n_batches: int, q: queue.Queue,
                  stop: threading.Event) -> None:
        # q/stop are THIS producer's own (not read from self): a restart
        # swaps self._q/_stop, and a slow old producer must neither feed the
        # new queue nor be revived by the new (cleared) event
        try:
            if self.window > 1:
                self._producer_windows(n_batches, q, stop)
                return
            if self.n_workers > 1 and hasattr(self._data,
                                              "plan_train_batch"):
                self._producer_pooled(n_batches, q, stop)
                return
            for i in range(n_batches):
                if stop.is_set():
                    return
                bid = next(self._ids)
                # materialize + device_put time up (relative to the
                # consumer's step time) = the producer becoming the
                # bottleneck
                batch = self._maybe_put(self._draw(i + 1, bid), bid)
                cursor = self._data.get_cursor() \
                    if hasattr(self._data, "get_cursor") else {}
                if stop.is_set():     # restart raced the load: drop, don't put
                    return
                self._enqueue(q, (batch, cursor, bid))
        except BaseException as e:    # surface loader errors in the consumer
            q.put(e)

    def _plan(self, count: int, bid: int):
        with telemetry.span("input.plan", bid):
            return self._data.plan_train_batch(count)

    def _materialize(self, plan, bid: int):
        with telemetry.span("input.materialize", bid):
            return self._data.materialize(plan)

    def _draw(self, count: int, bid: int):
        """One host batch on the calling thread: plan then materialize
        where the data has the split, else its ``next_train_batch`` whole
        (as ``input.materialize``)."""
        if hasattr(self._data, "plan_train_batch"):
            return self._materialize(self._plan(count, bid), bid)
        with telemetry.span("input.materialize", bid):
            return self._data.next_train_batch(count)

    @staticmethod
    def _enqueue(q: queue.Queue, item) -> None:
        """The bounded ``q.put``.  Long = the producer is AHEAD (healthy
        overlap); ~0 everywhere + unready or starved dequeues = the
        producer can't keep up."""
        with telemetry.span("input.enqueue", item[2]):
            q.put(item)

    def _producer_pooled(self, n_batches: int, q: queue.Queue,
                         stop: threading.Event) -> None:
        """Sequential plans, pooled materialization: at most ``depth``
        queued + ``n_workers`` executing batches in flight; the queue keeps
        plan order, so the stream equals the serial producer's exactly."""
        from concurrent.futures import ThreadPoolExecutor
        failed = []                    # any materialize error aborts the

        def on_done(f):                # epoch, matching the serial producer
            if not f.cancelled() and f.exception() is not None:
                failed.append(f)

        with ThreadPoolExecutor(self.n_workers) as pool:
            for i in range(n_batches):
                if stop.is_set() or failed:
                    return             # consumer hits the error at .result()
                bid = next(self._ids)
                plan = self._plan(i + 1, bid)
                cursor = self._data.get_cursor() \
                    if hasattr(self._data, "get_cursor") else {}
                fut = pool.submit(
                    lambda p, b: self._maybe_put(self._materialize(p, b), b),
                    plan, bid)
                fut.add_done_callback(on_done)
                if stop.is_set():
                    return
                # bounded: blocks at depth+n_workers
                self._enqueue(q, (fut, cursor, bid))

    def _producer_windows(self, n_batches: int, q: queue.Queue,
                          stop: threading.Event) -> None:
        """Window-granular producer: k sequential draws, one host stack,
        one mesh staging per window — all OFF the consumer thread, so
        ``train_iter`` dequeues a mesh-resident window and dispatches
        immediately.  Leftover batches < k roll to the next epoch's
        shuffle (the worker loop's ``n_batch_train // spc`` drop-last
        convention).  When the wrapped data exposes the plan/materialize
        split and ``n_workers > 1``, a window's k batches materialize
        concurrently in the pool (plans stay sequential — the batch
        stream is bit-identical to the serial path)."""
        from concurrent.futures import ThreadPoolExecutor
        k = self.window
        pooled = self.n_workers > 1 and hasattr(self._data,
                                                "plan_train_batch")
        pool = ThreadPoolExecutor(self.n_workers) if pooled else None
        try:
            for w in range(n_batches // k):
                if stop.is_set():
                    return
                bid = next(self._ids)       # one id per window
                if pooled:
                    plans = [self._plan(w * k + j + 1, bid)
                             for j in range(k)]
                    futs = [pool.submit(self._materialize, p, bid)
                            for p in plans]
                    batches = [f.result() for f in futs]  # re-raises, ordered
                else:
                    batches = [self._draw(w * k + j + 1, bid)
                               for j in range(k)]
                cursor = self._data.get_cursor() \
                    if hasattr(self._data, "get_cursor") else {}
                window = self._stage(_stack_host(batches), bid)
                if stop.is_set():     # restart raced the stage: drop
                    return
                self._enqueue(q, (window, cursor, bid))
        finally:
            if pool is not None:
                pool.shutdown(wait=False)

    def _stage(self, window, bid=None):
        return self._put(self._stage_window_fn, window, bid)

    def _maybe_put(self, batch, bid=None):
        return self._put(self._device_put_fn, batch, bid)

    @staticmethod
    def _put(put_fn, batch, bid):
        """Host -> mesh through ``put_fn``, when there is one: the bytes
        handed over (``input.bytes_put``) and the host call
        (``input.device_put``; the transfer itself runs on after it)."""
        if put_fn is None:
            return batch
        telemetry.count("input.bytes_put", _nbytes(batch))
        with telemetry.span("input.device_put", bid):
            return put_fn(batch)

    def _shutdown(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            self._stop.set()
            try:                      # drain so the producer can observe stop
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            # Best effort: a producer stuck >5s in one load stays orphaned,
            # but its own stop event is set and it holds the OLD queue, so it
            # can neither feed the restarted pipeline nor be revived.
            self._thread.join(timeout=5)
        self._thread = None
        self._q = None

    def __del__(self):
        try:
            self._shutdown()
        except Exception:
            pass
