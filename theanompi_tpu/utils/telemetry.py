"""Run-wide structured telemetry: metrics registry, event stream, flight
recorder.

The reference's entire observability story was the Recorder's four
wall-clock sums and a console print (the "time per 5120 images" tables);
at pod scale the questions that matter — which rank is the straggler, is
the prefetch queue starving, did HBM peak near OOM, what was a worker
doing in the 30 s before it hung — need a structured, run/rank-tagged
event stream and tooling that reads it across workers
(``scripts/telemetry_report.py``).

Three pieces, one process-wide instance (:func:`init` / :func:`active`):

* **Metrics registry** — named counters, gauges, and bounded-reservoir
  histograms (p50/p95/p99).  Fed by the Recorder's phase brackets
  (every ``recorder.end(section)`` lands one histogram sample AND one
  ``phase`` event), the PrefetchLoader's queue-depth/stall probes, the
  exchanger's per-exchange timings, and the compile cache's ladder
  counters.
* **Event stream** — each event is one JSONL line tagged with ``ts`` /
  ``run`` / ``rank``, appended to
  ``<record_dir>/telemetry_rank{r}.jsonl``.  On :meth:`Telemetry.close`
  a ``telemetry_summary_rank{r}.json`` sidecar lands next to it with the
  final counters/gauges/histogram summaries.
* **Flight recorder** — a bounded in-memory ring of the last N events
  (including ring-only watchdog heartbeats).  On crash, watchdog exit,
  or a fatal signal it is dumped to ``<record_dir>/flight_rank{r}.jsonl``;
  ``launcher.py --supervise`` sweeps per-rank dumps into a
  ``crash_<tag>/`` subdirectory before restarting, so a dead run leaves
  a diagnosable trail that the next attempt cannot overwrite.

**Cost contract** — two tiers.  The *span ring* (:func:`span`,
:func:`count`, read through :func:`spans` / :func:`totals`) is ALWAYS on
and bounded: one row per finished span in a ``deque(maxlen=RING_SPANS)``
plus a running total per name that is never evicted; two clock reads, one
tuple, one append, no lock beyond the GIL, no I/O (about a microsecond a
span, ``tests/test_spans.py`` pins it; at most ~30 spans a step).  The
benchmark's per-layer metrics and the worker's ``trace_dir`` capture read
it, so it cannot depend on a configuration key.  *Export* (registry, JSONL
stream, flight ring) is off unless the config enables it (``record_dir``
set, or ``telemetry=true`` for an in-memory registry; ``telemetry=false``
force-disables).  Disabled, :func:`active` returns the inert
:data:`DISABLED` singleton whose ``enabled`` is ``False`` — every
hot-path call to the *registry* (``counter``/``gauge``/``observe``/
``event``...) guards with that ONE attribute check
(``tests/test_telemetry.py`` pins the overhead; tpulint's
telemetry-hot-path pass enforces it and knows that ``span``/``count``
need no guard).  Enabled, a finished span is also a ``phase`` sample: the
histogram ``phase.<name>`` and one stream event.

This module imports no jax at module scope (scripts read it for
:data:`PHASES` without dragging a backend in); device probes import
lazily inside :meth:`Telemetry.system_snapshot`, and the ``jax.monitoring``
listener behind ``compile.xla`` registers in :func:`watch_compiles`.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

# THE canonical phase list — single source of truth for
# ``recorder.SECTIONS``, the ``print_train_info`` record keys
# (``t_<phase>``), and the telemetry phase-event names
# (``phase`` events' ``sec`` field / ``phase.<name>`` histograms).
# The tpulint ``schema-drift`` checker (``scripts/lint.py``, run by
# ``scripts/tier1.sh``) fails the gate when any consumer drifts.
PHASES = ("compile", "train", "comm", "wait", "load", "stage", "val")

SCHEMA_VERSION = 1
FLIGHT_EVENTS = 256          # ring-buffer length (events, not bytes)

# THE span vocabulary of the always-on ring, beside the recorder's PHASES
# (``Recorder.end`` writes its bracket into the ring under the phase's own
# name).  A dotted name is a part of the phase before the dot.  Every
# ``telemetry.span("...")`` / ``telemetry.count("...")`` literal in the
# package must be listed here and every listed name must have a site: the
# tpulint schema-drift checker guards both directions.  PERF.md §3 says
# which metric or operator reading each one feeds.
SPANS = (
    "load.dequeue",                                # consumer, inside `load`
    "train.args", "train.call", "train.reduce",    # inside `train`
    "exchange", "print",                           # main thread
    "input.plan", "input.enqueue",                 # producer thread
    "input.materialize", "input.device_put",       # pool (or producer)
    "compile.place",                               # compile_iter_fns
    "compile.xla", "compile.cache_load",           # jax.monitoring
)
COUNTS = ("input.dequeues", "input.unready_dequeues", "input.bytes_put",
          "pool_before_relu",
          # BSP's wire a step, written when the step is first traced
          "comm.allreduce_bytes", "comm.gathered_bytes",
          "comm.gathered_leaves",
          # a looped model's work a step, written at its first trace
          "model.loop_steps", "model.layer_applications",
          "model.head_tokens", "model.attn_kernel_applications",
          # [block, V] products the traced heads of either make a step
          "model.head_logit_products",
          # a routed model's share a step, written at its first trace
          "model.experts_held", "model.experts_routed",
          "model.routed_pairs", "model.routed_rows_at_once",
          "model.window_layers", "model.full_layers")
# a 20 s window at 20 steps/s and 30 spans a step, with room to spare
RING_SPANS = 16384
# jax.monitoring duration events -> span names.  jax wraps
# compile_or_get_cached in the first, so a persistent-cache load is inside
# it too; the second is the nested part that tells warm from cold.
COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile.xla",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load",
}


# -- the always-on span ring -------------------------------------------------
# Rows are ``(name, thread_id, t0_ns, t1_ns, parent_frame, batch)`` on the
# Unix clock (``time.time_ns()``: the clock a profiler session's
# ``profile_start_time`` is on).  ``parent_frame`` is the frame open on
# the same thread when the span began (``spans()`` hands out its name): a
# span's self time is its length less its children's.  Running totals are
# keyed by (name, thread) so that each cell has ONE writer and needs no
# lock; ``totals()`` folds them by name.

_ring: deque = deque(maxlen=RING_SPANS)
_totals: Dict[tuple, list] = {}          # (name, tid) -> [count, ns]
_tls = threading.local()
_get_ident = threading.get_ident
_now_ns = time.time_ns


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


def _finish(name, t0, t1, parent, batch, export=True) -> None:
    tid = _get_ident()
    _ring.append((name, tid, t0, t1, parent, batch))
    try:
        tot = _totals[name, tid]
    except KeyError:
        tot = _totals[name, tid] = [0, 0]
    tot[0] += 1
    tot[1] += t1 - t0
    if export and _ACTIVE.enabled:
        _ACTIVE.phase(name, (t1 - t0) / 1e9, t0_ns=t0, tid=tid)


class span:
    """``with telemetry.span("train.call", batch=i):`` — one ring row on
    exit.  Re-entrant and thread-safe; ``batch`` (the loader's batch id,
    shared by the spans of one batch across threads) may also be set on
    the object inside the block, where it is only known after the wait
    (``load.dequeue``)."""

    __slots__ = ("name", "batch", "t0", "parent")

    def __init__(self, name: str, batch=None):
        self.name = name
        self.batch = batch

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self)
        self.t0 = _now_ns()
        return self

    def __exit__(self, *exc):
        t1 = _now_ns()
        _pop(self)
        _finish(self.name, self.t0, t1, self.parent, self.batch)
        return False


def _pop(frame) -> None:
    stack = _stack()
    if stack and stack[-1] is frame:
        stack.pop()
    elif frame in stack:             # frames left open above it by an
        del stack[stack.index(frame):]      # exception: they go with it


def open_bracket() -> span:
    """``Recorder.start()``: push a frame whose name is only known at
    ``end(section)``.  Spans opened inside it hold the frame, so they learn
    their parent's name when the bracket closes."""
    return span(None).__enter__()


def close_bracket(b: span, name: str) -> int:
    """``Recorder.end(section)``: name the frame, write its row; returns
    its length in ns.  Not forwarded to the registry: the recorder feeds
    its own ``phase`` sample."""
    t1 = _now_ns()
    b.name = name
    _pop(b)
    _finish(name, b.t0, t1, b.parent, None, export=False)
    return t1 - b.t0


def drop_bracket(b: span) -> None:
    """A ``start()`` that a second ``start()`` supersedes."""
    _pop(b)


def count(name: str, n: int = 1) -> None:
    """Always-on running total of a counter in :data:`COUNTS`."""
    key = (name, _get_ident())
    try:
        _totals[key][0] += n
    except KeyError:
        _totals[key] = [n, 0]


def spans(t0_ns: Optional[int] = None, t1_ns: Optional[int] = None
          ) -> List[tuple]:
    """Rows ``(name, thread_id, t0_ns, t1_ns, parent, batch)`` still in
    the ring that overlap ``[t0_ns, t1_ns]`` (all of them by default),
    oldest first, ``parent`` as a name."""
    out = []
    for name, tid, t0, t1, parent, batch in list(_ring):
        if (t0_ns is None or t1 >= t0_ns) and (t1_ns is None or t0 <= t1_ns):
            out.append((name, tid, t0, t1,
                        parent.name if parent is not None else None, batch))
    return out


def totals() -> Dict[str, tuple]:
    """``{name: (count, total_ns)}`` since the process started, spans and
    counters alike (a counter's ns is 0); never evicted."""
    out: Dict[str, list] = {}
    for (name, _tid), (n, ns) in list(_totals.items()):
        acc = out.setdefault(name, [0, 0])
        acc[0] += n
        acc[1] += ns
    return {k: (v[0], v[1]) for k, v in out.items()}


def on_trace_clock(unix_ns: int, profile_start_ns: int) -> int:
    """A ring time as a time on a profiler trace's clock: the profiler
    subtracts the session's start (``profile_start_time``, Unix ns, a
    stat of the plane ``Task Environment``) from every timestamp."""
    return unix_ns - profile_start_ns


_watching = False
_watch_lock = threading.Lock()       # registration only, off every hot path


def watch_compiles() -> None:
    """Register, once, the ``jax.monitoring`` listener that writes every
    XLA backend compile (``compile.xla``, persistent-cache loads included)
    and every cache retrieval (``compile.cache_load``, nested in it) into
    the ring as a span ending now.  Called from ``compile_iter_fns``, so
    nothing is imported or registered before a model compiles."""
    global _watching
    if _watching:
        return
    import jax.monitoring

    def on_duration(event, duration_secs, **_):
        name = COMPILE_EVENTS.get(event)
        if name is not None:
            t1 = _now_ns()
            stack = _stack()
            _finish(name, t1 - int(duration_secs * 1e9), t1,
                    stack[-1] if stack else None, None)

    with _watch_lock:
        if not _watching:
            jax.monitoring.register_event_duration_secs_listener(on_duration)
            _watching = True


def host_rss_bytes() -> Optional[int]:
    """Resident set size of this process, or None when unknowable."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        try:
            import resource
            # ru_maxrss is KiB on linux (peak, not current — close enough
            # as the fallback)
            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            return None


def aggregate_memory_stats(stats: List[Optional[dict]]) -> Dict[str, int]:
    """Fold per-device ``memory_stats()`` dicts into the HBM gauge set.

    Device 0 alone hides single-host multi-chip pressure (one hot chip can
    OOM while device 0 reports headroom), so the aggregation is
    worst-case-oriented: bytes-in-use SUMS across devices (total HBM
    footprint), peak takes the MAX (the chip closest to OOM), the limit
    takes the per-device MIN (the binding budget — limits are uniform on
    real hardware, and when they aren't, the smallest one is the wall),
    and ``hbm_min_headroom_bytes`` is the worst single device's
    ``limit − peak``.  Devices reporting no stats (CPU sim) are skipped;
    empty input → empty dict (host gauges still emit)."""
    out: Dict[str, int] = {}
    ms = [m for m in stats if m]
    if not ms:
        return out
    in_use = [int(m["bytes_in_use"]) for m in ms if "bytes_in_use" in m]
    if in_use:
        out["hbm_bytes_in_use"] = sum(in_use)
    peaks = [int(m["peak_bytes_in_use"]) for m in ms
             if "peak_bytes_in_use" in m]
    if peaks:
        out["hbm_peak_bytes"] = max(peaks)
    limits = [int(m["bytes_limit"]) for m in ms if "bytes_limit" in m]
    if limits:
        out["hbm_bytes_limit"] = min(limits)
    headrooms = [int(m["bytes_limit"]) - int(m["peak_bytes_in_use"])
                 for m in ms
                 if "bytes_limit" in m and "peak_bytes_in_use" in m]
    if headrooms:
        out["hbm_min_headroom_bytes"] = min(headrooms)
    return out


class Histogram:
    """Bounded-reservoir histogram with exact count/sum/min/max.

    Samples are exact until ``cap``; past it the reservoir is thinned by
    keeping every other sample and doubling the record stride —
    systematic (deterministic) sampling, so tail percentiles stay
    representative while memory stays bounded."""

    __slots__ = ("count", "total", "min", "max", "_samples", "_stride",
                 "_skip", "_cap")

    def __init__(self, cap: int = 4096):
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self._samples: List[float] = []
        self._cap = int(cap)
        self._stride = 1
        self._skip = 0

    def observe(self, value: float) -> None:
        v = float(value)
        self.count += 1
        self.total += v
        if self.min is None or v < self.min:
            self.min = v
        if self.max is None or v > self.max:
            self.max = v
        self._skip += 1
        if self._skip >= self._stride:
            self._skip = 0
            self._samples.append(v)
            if len(self._samples) >= self._cap:
                self._samples = self._samples[::2]
                self._stride *= 2

    def percentile(self, q: float) -> Optional[float]:
        if not self._samples:
            return None
        s = sorted(self._samples)
        idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
        return s[idx]

    def summary(self) -> dict:
        mean = self.total / self.count if self.count else None
        return {"count": self.count, "sum": round(self.total, 6),
                "min": self.min, "max": self.max,
                "mean": round(mean, 6) if mean is not None else None,
                "p50": self.percentile(50), "p95": self.percentile(95),
                "p99": self.percentile(99)}


class Telemetry:
    """One process-wide (per-rank) registry + stream + flight ring.

    Thread-safe: the worker hot loop, the PrefetchLoader producer, and
    the watchdog monitor all feed it concurrently."""

    enabled = True

    def __init__(self, rank: int = 0, run_id: Optional[str] = None,
                 stream_dir: Optional[str] = None,
                 flight_events: int = FLIGHT_EVENTS, flush_every: int = 64):
        self.rank = int(rank)
        self.run_id = str(run_id) if run_id else \
            f"run{int(time.time())}p{os.getpid()}"
        self.stream_dir = stream_dir
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.hists: Dict[str, Histogram] = {}
        self._ring: deque = deque(maxlen=int(flight_events))
        # REENTRANT: the fatal-signal hook runs its dump on whatever thread
        # the signal lands on — if that thread was inside event() holding
        # the lock, a plain Lock would deadlock the dying process
        self._lock = threading.RLock()
        self._fh = None
        self._unflushed = 0
        self._flush_every = int(flush_every)
        if stream_dir:
            os.makedirs(stream_dir, exist_ok=True)
            # append: a supervised restart continues the same per-rank file
            # (events carry their own run id, so runs stay separable)
            self._fh = open(os.path.join(
                stream_dir, f"telemetry_rank{self.rank}.jsonl"), "a")
        self.event("run_start", schema=SCHEMA_VERSION, pid=os.getpid())

    # -- metrics ------------------------------------------------------------

    def counter(self, name: str, n: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self.gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            h = self.hists.get(name)
            if h is None:
                h = self.hists[name] = Histogram()
            h.observe(value)

    def phase(self, section: str, dt: float, t0_ns: Optional[int] = None,
              tid: Optional[int] = None) -> None:
        """One recorder phase bracket or finished ring span: histogram
        sample + stream event (``t0`` in Unix ns and the thread when the
        caller has them).  Event names/fields are part of the schema
        (docs/design.md §11)."""
        self.observe("phase." + section, dt)
        if t0_ns is None:
            self.event("phase", sec=section, dt=round(dt, 6))
        else:
            self.event("phase", sec=section, dt=round(dt, 6), t0=t0_ns,
                       tid=tid)

    # -- events -------------------------------------------------------------

    def event(self, name: str, /, ring_only: bool = False,
              **fields) -> None:
        # ``name`` is positional-ONLY so a field may also be called
        # "name" (the §17 span events carry one) without colliding
        ev = {"ts": round(time.time(), 3), "run": self.run_id,
              "rank": self.rank, "ev": name}
        ev.update(fields)
        with self._lock:
            self._ring.append(ev)
            if ring_only or self._fh is None:
                return
            try:
                self._fh.write(json.dumps(ev) + "\n")
                self._unflushed += 1
                if self._unflushed >= self._flush_every:
                    self._fh.flush()
                    self._unflushed = 0
            except (OSError, ValueError):
                pass            # telemetry must never fail the run

    def tail(self, n: int = 8) -> List[dict]:
        with self._lock:
            return list(self._ring)[-n:]

    # -- gauge snapshots ----------------------------------------------------

    def system_snapshot(self, **extra) -> dict:
        """Device memory aggregated over ALL local devices
        (:func:`aggregate_memory_stats`: summed bytes-in-use, max peak,
        min limit, worst-device headroom, plus ``device_count``), host
        RSS, the current prefetch queue depth (when the loader exports
        it), and caller extras (iteration rate, count) — recorded as
        gauges AND streamed as one ``gauges`` event."""
        vals = dict(extra)
        try:
            import jax
            devs = jax.local_devices()
            vals["device_count"] = len(devs)
            vals.update(aggregate_memory_stats(
                [d.memory_stats() for d in devs]))
        except Exception:
            pass                # CPU sims often have no memory_stats
        rss = host_rss_bytes()
        if rss:
            vals["host_rss_bytes"] = rss
        qd = self.gauges.get("prefetch.queue_depth")
        if qd is not None:
            # sampled into the stream here (the loader only sets the gauge
            # on its hot path) — telemetry_report's Perfetto export draws
            # its queue-depth counter track from these events
            vals["prefetch.queue_depth"] = qd
        for k, v in vals.items():
            if isinstance(v, (int, float)):
                self.gauge(k, v)
        self.event("gauges", **vals)
        return vals

    # -- summary / flight dump / lifecycle ----------------------------------

    def summary(self) -> dict:
        with self._lock:
            return {"run": self.run_id, "rank": self.rank,
                    "counters": dict(self.counters),
                    "gauges": dict(self.gauges),
                    "hist": {k: h.summary() for k, h in self.hists.items()}}

    def dump_flight(self, reason: str = "",
                    dump_dir: Optional[str] = None) -> Optional[str]:
        """Write the ring buffer to ``flight_rank{r}.jsonl`` — the what-was-
        this-rank-doing trail for crash/stall post-mortems.  First line is a
        header with the reason; returns the path (None without a dir)."""
        d = dump_dir or self.stream_dir
        if not d:
            return None
        path = os.path.join(d, f"flight_rank{self.rank}.jsonl")
        try:
            os.makedirs(d, exist_ok=True)
            with self._lock:
                events = list(self._ring)
            with open(path, "w") as f:
                f.write(json.dumps(
                    {"ts": round(time.time(), 3), "run": self.run_id,
                     "rank": self.rank, "ev": "flight_dump",
                     "reason": str(reason)[:300],
                     "events": len(events)}) + "\n")
                for ev in events:
                    f.write(json.dumps(ev) + "\n")
        except OSError:
            return None
        return path

    def close(self) -> None:
        """Flush + close the stream and write the summary sidecar; the
        instance goes inert (``enabled=False``) so stale references left in
        other components after a re-:func:`init` become no-ops."""
        with self._lock:
            fh, self._fh = self._fh, None
        self.enabled = False
        if fh is not None:
            try:
                fh.flush()
                fh.close()
            except (OSError, ValueError):
                pass
        if self.stream_dir:
            try:
                with open(os.path.join(
                        self.stream_dir,
                        f"telemetry_summary_rank{self.rank}.json"),
                        "w") as f:
                    json.dump(self.summary(), f, indent=1, sort_keys=True)
            except OSError:
                pass


class _Disabled:
    """The inert registry: one attribute check (``enabled``) is the whole
    hot-path cost; every method is a no-op for call sites that don't
    guard."""

    enabled = False
    rank = 0
    run_id = None
    stream_dir = None
    counters: Dict[str, float] = {}
    gauges: Dict[str, float] = {}
    hists: Dict[str, Histogram] = {}

    def counter(self, name, n=1):
        pass

    def gauge(self, name, value):
        pass

    def observe(self, name, value):
        pass

    def phase(self, section, dt, t0_ns=None, tid=None):
        pass

    def event(self, name, /, ring_only=False, **fields):
        pass

    def tail(self, n=8):
        return []

    def system_snapshot(self, **extra):
        return {}

    def summary(self):
        return {}

    def dump_flight(self, reason="", dump_dir=None):
        return None

    def close(self):
        pass


DISABLED = _Disabled()

_ACTIVE: Any = DISABLED


def active():
    """The process-wide registry — :data:`DISABLED` until :func:`init`
    enables one.  Components (prefetch, exchanger, compile cache,
    watchdog) read it lazily so no config threading is needed."""
    return _ACTIVE


def init(config: Optional[dict] = None):
    """(Re)initialize process-wide telemetry from a worker/model config.

    Enablement: ``telemetry=false`` force-disables; otherwise a
    ``record_dir`` enables the streaming registry (events land next to the
    recorder's inforec files), and ``telemetry=true`` without a dir
    enables an in-memory registry (metrics + flight ring, no stream).
    A previous instance is closed first, so repeated
    in-process sessions don't leak file handles or cross-write streams."""
    global _ACTIVE
    config = config or {}
    t = config.get("telemetry", None)
    if t is False or (isinstance(t, str) and t.lower() == "false"):
        new: Any = DISABLED
    else:
        stream_dir = config.get("record_dir") or \
            (t if isinstance(t, str) else None)
        if t or stream_dir:
            new = Telemetry(rank=int(config.get("rank", 0)),
                            run_id=config.get("run_id"),
                            stream_dir=stream_dir,
                            flight_events=int(config.get(
                                "telemetry_flight_events", FLIGHT_EVENTS)),
                            # low-rate emitters that die by SIGKILL (the
                            # center process) flush eagerly so their
                            # span/audit tail survives the kill
                            flush_every=int(config.get(
                                "telemetry_flush_every", 64)))
        else:
            new = DISABLED
    old, _ACTIVE = _ACTIVE, new
    if old is not DISABLED and old is not new:
        old.close()
    return new


def install_signal_hooks(signals=None) -> None:
    """Dump the flight recorder on a fatal signal, then re-raise it with
    the default handler so the exit code stays honest.  Installed by the
    worker CLI entry only (never by the in-process session API — tests
    and host applications own their handlers).

    SIGTERM only by default: SIGINT must keep raising KeyboardInterrupt so
    the worker's unwind path runs (async-checkpoint flush in its finally
    block, flight dump in its except) — a kill-style handler there would
    skip both."""
    import signal as _signal
    sigs = signals or (_signal.SIGTERM,)

    def _handler(signum, frame):
        tm = active()
        if tm.enabled:
            tm.event("fatal_signal", signum=int(signum))
            tm.dump_flight(reason=f"signal {signum}")
            tm.close()
        _signal.signal(signum, _signal.SIG_DFL)
        os.kill(os.getpid(), signum)

    for s in sigs:
        try:
            _signal.signal(s, _handler)
        except (ValueError, OSError):
            pass                # not the main thread / unsupported signal


def sweep_flight_dumps(record_dir: str, tag: str) -> Optional[str]:
    """Move per-rank ``flight_rank*.jsonl`` dumps into
    ``<record_dir>/crash_<tag>/`` — called by ``launcher.py`` after a
    supervised worker dies, so the restart's own eventual dumps cannot
    overwrite the trail that explains the death.  Returns the destination
    (None when there was nothing to sweep)."""
    import glob
    import shutil
    dumps = sorted(glob.glob(os.path.join(record_dir, "flight_rank*.jsonl")))
    if not dumps:
        return None
    dest = os.path.join(record_dir, f"crash_{tag}")
    os.makedirs(dest, exist_ok=True)
    for p in dumps:
        try:
            shutil.move(p, os.path.join(dest, os.path.basename(p)))
        except OSError:
            pass
    return dest
