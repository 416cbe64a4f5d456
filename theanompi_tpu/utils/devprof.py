"""Device-time attribution from ``jax.profiler`` trace captures.

PR 4's ``phase.comm`` histograms time the HOST-side dispatch bracket: the
worker blocks until the exchange collective's result is ready and charges
the wall time to ``comm``.  That accounting goes blind the moment
collectives become async start/done pairs overlapped with backprop
(ROADMAP item 1): the host bracket then measures a queue push, and the
question that actually governs scaling — how much collective time is
*exposed* (serialized against compute) versus *hidden* (overlapped under
it) — is only answerable from the device timeline the XLA profiler
records.  The CUDA-aware-MPI characterization (PAPERS.md, 1810.11112)
makes the same point for GPU clusters: overlap of reduction with
backprop, not raw bandwidth, is the scaling variable.

This module is the ONE trace-proto reader in the repo (the glob/gzip/json
walk ``scripts/profile_model.py`` used to do inline, promoted and
tested).  ``jax.profiler.stop_trace`` writes
``<dir>/plugins/profile/<session>/<host>.trace.json.gz`` — gzipped
Chrome trace-event JSON where every executed HLO op is a complete
(``"ph": "X"``) event carrying ``args.hlo_op`` / ``args.hlo_module``.
That marker is the discriminator: host-side Python/runtime spans have no
``hlo_op``, so the parse needs no tensorboard plugin and stays stdlib.

**Attribution model.**  Op events are grouped into *lanes* (one
``(pid, tid)`` pair — a device plane's op line on TPU, one per-device
executor thread on the CPU sim).  Per lane the comm-op intervals and
compute-op intervals are union-merged, and

* ``comm_secs``      = Σ lanes measure(comm ∪)
* ``compute_secs``   = Σ lanes measure(compute ∪)
* ``exposed_comm_secs`` = Σ lanes [measure(comm ∪) −
  measure(comm ∪ ∩ compute ∪)] — collective time with NO compute running
  on the same lane, i.e. the serialized tail the step actually pays
* ``overlap_ratio``  = 1 − exposed_comm / comm  (None when no comm)

Comm ops are matched by HLO opcode prefix (``all-reduce``,
``all-gather``, ``reduce-scatter``, ``all-to-all``,
``collective-permute``, ... including their async ``-start``/``-done``
forms).  Each async pair is merged into ONE comm interval spanning
start-begin → done-end — the whole in-flight window — matched in
timestamp order per op class, same-lane first, then across lanes with
the merged interval landing on the START's lane (a runtime that parks
the done on a dedicated async-collective stream must not read as a
second, fully-exposed collective while the issuing lane's compute hides
the real one).

Host dispatch anchors: the worker loop and the standalone exchange tag
their dispatches with ``jax.profiler.TraceAnnotation`` spans named
:data:`TRAIN_DISPATCH_SPAN` / :data:`EXCHANGE_SPAN`; the parser counts
them so per-dispatch means don't depend on guessing the iteration count
from op repetitions.

Consumers: the worker's ``trace_dir`` capture feeds the result into the
PR 4 telemetry registry as ``device.*`` gauges (:func:`feed_telemetry` —
names pinned by the tpulint schema-drift checker), ``bench.py``'s
``BENCH_TRACE=1`` folds :data:`TRACE_ROW_COLUMNS` into the row JSON, and
``scripts/profile_model.py`` prints the same breakdown interactively.

No jax at module scope (the lint CLI and stdlib scripts import this for
the schema constants); :func:`capture` imports it lazily.
"""

from __future__ import annotations

import glob
import gzip
import json
import math
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

# Dispatch-anchor span names (host-side jax.profiler.TraceAnnotation):
# worker.py wraps each train_iter dispatch, exchanger.exchange wraps the
# standalone collective dispatch.  Constant strings — the parser matches
# them exactly.
TRAIN_DISPATCH_SPAN = "theanompi.train_dispatch"
EXCHANGE_SPAN = "theanompi.exchange"

# The device.* gauge vocabulary feed_telemetry emits — ONE list, guarded
# by the tpulint schema-drift checker so emitters and report consumers
# cannot desync (docs/design.md §13).
DEVICE_GAUGES = (
    "device.compute_secs",
    "device.comm_secs",
    "device.exposed_comm_secs",
    "device.overlap_ratio",
    "device.lanes",
)
PROFILE_EVENT = "device_profile"

# The bench-row columns BENCH_TRACE=1 adds (profile_row_fields emits
# exactly these keys; scripts/merge_matrix.py treats them — like any
# column — as unknown when absent, never as a regression).
TRACE_ROW_COLUMNS = (
    "overlap_ratio",
    "exposed_comm_secs",
    "device_compute_secs",
    "device_comm_secs",
    "device_mfu",
    # per-lane idle share between compute intervals inside the dispatch
    # window (ROADMAP item 2's pipeline-schedule acceptance metric):
    # span-weighted over compute lanes, 1 − busy/span per lane.  Exposed
    # same-lane collectives count as bubble deliberately — from the
    # compute pipeline's perspective a stall is a stall.
    "bubble_fraction",
)

# The bench-row columns BENCH_BUCKET_BYTES adds (the bucketed-wire rows,
# parallel/buckets.py): the configured bucket size and the collectives
# -per-exchange count the planner produced.  Declared HERE — the one
# jax-free schema home for bench-row vocabularies — so the tpulint
# schema-drift checker can pin bench's emission against it and guarantee
# it stays disjoint from TRACE_ROW_COLUMNS (a name collision would
# silently overwrite a trace column in the row JSON).
BUCKET_ROW_COLUMNS = (
    "bucket_bytes",
    "n_buckets",
)

# The bench-row columns pipelined rows (pp > 1 in BENCH_CFG) add — the
# :func:`pipeline_schedule_report` measurement: the tick-count bubble read
# off the hop events (exact when the capture verifies), the wall-time
# weighted bubble, and the verification bit itself.  Same jax-free schema
# -home discipline as BUCKET_ROW_COLUMNS; disjointness from the other two
# vocabularies is pinned in tests/test_pipeline_schedule.py.
PIPELINE_ROW_COLUMNS = (
    "pipeline_bubble_ticks",
    "pipeline_bubble_time",
    "pipeline_schedule_verified",
)

# The bench-row columns update-plane-sharding rows add (BENCH_USHARD=1 /
# BENCH_USHARD_REPORT=1; parallel/update_sharding.py, docs/design.md §23)
# — the :func:`update_state_report` measurement: per-chip update-plane
# bytes (optimizer state + exchanger extra, actual live-array bytes over
# worker count), the replicated-equivalent bytes the same session would
# hold without sharding, and their ratio (the ~N× headline).  Same
# jax-free schema-home discipline as the vocabularies above; disjointness
# is pinned in tests/test_update_sharding.py.
USHARD_ROW_COLUMNS = (
    "update_state_bytes_per_chip",
    "update_state_bytes_replicated",
    "update_state_shrink",
)

# The bench-row columns compression rows add (onebit/topk/powersgd
# strategies; ops/compress.py, ops/factor_pack.py, docs/design.md §24) —
# the :func:`compress_traffic_report` estimate: local HBM bytes one
# exchange moves through the compression pipeline, modeled at XLA-op
# granularity WITHOUT fusion credit (each jnp-level op reads its operands
# and writes its result — an upper bound for the unfused graph, exact for
# the single-pass Pallas kernels), before (legacy unfused ops) and after
# (fused kernel pipeline), plus the decode-stage ratio on its own (the
# scatter replacement is topk's headline).  Same jax-free schema-home
# discipline as the vocabularies above; disjointness is pinned in
# tests/test_compress_fusion.py.
COMPRESS_ROW_COLUMNS = (
    "compress_hbm_bytes_legacy",
    "compress_hbm_bytes_fused",
    "compress_hbm_shrink",
    "compress_decode_shrink",
)

# HLO opcodes whose device time is collective/communication time.  Async
# pairs (`<op>-start` / `<op>-done`) share the prefix and match too.
COMM_OP_PREFIXES = (
    "all-reduce",
    "all-gather",
    "all-to-all",
    "reduce-scatter",
    "collective-permute",
    "collective-broadcast",
    "ragged-all-to-all",
    "send",
    "recv",
)

_SUFFIX_RE = re.compile(r"\.\d+$")


def op_class(name: str) -> str:
    """HLO instruction name → op class: strip the unique ``.N`` suffix
    (``all-reduce.1`` → ``all-reduce``), keep fusion/async qualifiers —
    they distinguish genuinely different kinds of device time."""
    return _SUFFIX_RE.sub("", str(name))


def is_comm_op(name: str) -> bool:
    """Whether one HLO op name is collective/communication time."""
    return op_class(name).startswith(COMM_OP_PREFIXES)


# -- trace file discovery / loading ----------------------------------------


def find_trace_files(trace_dir: str) -> List[str]:
    """The ``*.trace.json.gz`` files of the NEWEST capture session under
    ``trace_dir`` (jax writes ``plugins/profile/<timestamp>/`` per
    ``stop_trace``; one file per host)."""
    sessions = sorted(
        d for d in glob.glob(os.path.join(trace_dir, "plugins", "profile", "*"))
        if os.path.isdir(d))
    if not sessions:
        return []
    newest = max(sessions, key=os.path.getmtime)
    return sorted(glob.glob(os.path.join(newest, "*.trace.json.gz")))


def load_trace_events(path: str) -> List[dict]:
    """All trace events from one gzipped Chrome-trace file."""
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    evs = data.get("traceEvents", []) if isinstance(data, dict) else []
    return [e for e in evs if isinstance(e, dict)]


# -- interval algebra -------------------------------------------------------


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge possibly-overlapping/nested (start, end) intervals."""
    if not intervals:
        return []
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _measure(union: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in union)


def _intersection_measure(a: List[Tuple[float, float]],
                          b: List[Tuple[float, float]]) -> float:
    """Total overlap between two already-merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _async_base(cls: str) -> Optional[Tuple[str, str]]:
    """``('all-reduce', 'start'|'done')`` for an async-pair op class."""
    for side in ("start", "done"):
        if cls.endswith("-" + side):
            return cls[:-(len(side) + 1)], side
    return None


def _merge_async_pairs(comm_ev: Dict[Tuple, List[Tuple[float, float, str]]]
                       ) -> Dict[Tuple, List[Tuple[float, float]]]:
    """Comm events → per-lane intervals, with each async
    ``<op>-start``/``<op>-done`` pair merged into ONE interval spanning
    start-begin → done-end.

    Under XLA's latency-hiding scheduler the pair brackets one in-flight
    collective; counting the two ops as separate slivers mis-attributes
    it twice over: the in-flight window between them vanishes from
    ``comm_secs``, and when the runtime puts the halves on DIFFERENT
    lanes (a dedicated async-collective stream), the same collective is
    counted on both lanes — the done sliver then reads as fully exposed
    even while the start's lane is busy with the compute that hides it.
    Pairs are matched k-th-start ↔ k-th-done in timestamp order per op
    class, same-lane first, then across lanes (the merged interval lands
    on the START's lane — where the collective was issued, and where the
    compute that may hide it runs).  Unpaired halves and plain sync
    collectives keep their own intervals."""
    out: Dict[Tuple, List[Tuple[float, float]]] = {
        lane: [] for lane in comm_ev}
    # base op class -> side -> [(ts, end, lane)], ts-ordered
    leftovers: Dict[str, Dict[str, List[Tuple[float, float, Tuple]]]] = {}
    for lane, evs in comm_ev.items():
        by_base: Dict[str, Dict[str, List[Tuple[float, float]]]] = {}
        for ts, end, cls in evs:
            ab = _async_base(cls)
            if ab is None:
                out[lane].append((ts, end))
            else:
                by_base.setdefault(ab[0], {}).setdefault(
                    ab[1], []).append((ts, end))
        for base, sides in by_base.items():
            starts = sorted(sides.get("start", []))
            dones = sorted(sides.get("done", []))
            for (s0, s1), (d0, d1) in zip(starts, dones):
                out[lane].append((s0, max(s1, d1, d0)))
            for side, rest in (("start", starts[len(dones):]),
                               ("done", dones[len(starts):])):
                for ts, end in rest:
                    leftovers.setdefault(base, {}).setdefault(
                        side, []).append((ts, end, lane))
    # cross-lane pairing of the leftovers (start on the compute lane,
    # done on a dedicated async stream — or vice versa)
    for base, sides in leftovers.items():
        starts = sorted(sides.get("start", []))
        dones = sorted(sides.get("done", []))
        for (s0, s1, lane_s), (d0, d1, _lane_d) in zip(starts, dones):
            out[lane_s].append((s0, max(s1, d1, d0)))
        for ts, end, lane in starts[len(dones):] + dones[len(starts):]:
            out[lane].append((ts, end))
    return {lane: iv for lane, iv in out.items() if iv}


# -- attribution ------------------------------------------------------------


def attribute(events: Iterable[dict]) -> Dict[str, Any]:
    """Per-dispatch device-time breakdown from raw trace events.

    Returns a plain JSON-able dict: ``compute_secs`` / ``comm_secs`` /
    ``exposed_comm_secs`` / ``overlap_ratio`` / ``lanes`` totals, the
    per-``hlo_module`` breakdown, the top op classes by device time, and
    the host dispatch-anchor counts (``train_dispatches`` /
    ``exchange_dispatches``)."""
    # lane = (pid, tid); per lane the compute interval lists and the comm
    # EVENT lists (us; comm keeps the op class so async start/done pairs
    # can merge into one in-flight interval — see _merge_async_pairs)
    comm_ev: Dict[Tuple, List[Tuple[float, float, str]]] = {}
    comp_iv: Dict[Tuple, List[Tuple[float, float]]] = {}
    # module -> ("comm"|"compute") -> lane -> intervals/events: the
    # per-module breakdown keeps the lane split so device A's compute
    # can't masquerade as overlap for device B's collective
    per_module: Dict[str, Dict[str, Dict[Tuple, List]]] = {}
    op_totals: Dict[str, List[float]] = {}            # class -> [us, count]
    train_dispatches = 0
    exchange_dispatches = 0
    n_op_events = 0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
        if name == TRAIN_DISPATCH_SPAN:
            train_dispatches += 1
            continue
        if name == EXCHANGE_SPAN:
            exchange_dispatches += 1
            continue
        args = ev.get("args")
        if not isinstance(args, dict) or "hlo_op" not in args:
            continue                       # host python/runtime span
        try:
            ts = float(ev.get("ts", 0.0))
            dur = float(ev.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        if dur < 0:
            continue
        n_op_events += 1
        # _src disambiguates per-host capture files merged by profile_dir:
        # two hosts' device planes reuse the same small pid/tid integers,
        # and merging them into one lane would let host A's compute mask
        # host B's collective as overlap
        lane = (ev.get("_src"), ev.get("pid"), ev.get("tid"))
        cls = op_class(name)
        comm = is_comm_op(name)
        if comm:
            comm_ev.setdefault(lane, []).append((ts, ts + dur, cls))
        else:
            comp_iv.setdefault(lane, []).append((ts, ts + dur))
        tot = op_totals.setdefault(cls, [0.0, 0])
        tot[0] += dur
        tot[1] += 1
        mod = str(args.get("hlo_module", "?"))
        m = per_module.setdefault(mod, {"comm": {}, "compute": {}})
        if comm:
            m["comm"].setdefault(lane, []).append((ts, ts + dur, cls))
        else:
            m["compute"].setdefault(lane, []).append((ts, ts + dur))

    def _breakdown(comm_events, comp_by_lane):
        comm_by_lane = _merge_async_pairs(comm_events)
        comm_us = comp_us = exposed_us = 0.0
        for lane in set(comm_by_lane) | set(comp_by_lane):
            cu = _union(comm_by_lane.get(lane, []))
            pu = _union(comp_by_lane.get(lane, []))
            c = _measure(cu)
            comm_us += c
            comp_us += _measure(pu)
            exposed_us += c - _intersection_measure(cu, pu)
        return comm_us, comp_us, exposed_us

    comm_us, comp_us, exposed_us = _breakdown(comm_ev, comp_iv)
    # bubble fraction: per compute lane, the dispatch window is that
    # lane's first-compute-start → last-compute-end; everything inside
    # it with NO compute running on the lane is bubble (pipeline
    # fill/drain gaps, microbatch waits, exposed same-lane collectives).
    # Span-weighted across lanes so a short-lived lane can't swamp the
    # verdict; None when the trace carries no compute.
    bubble_span_us = bubble_idle_us = 0.0
    for lane, ivs in comp_iv.items():
        u = _union(ivs)
        if not u:
            continue
        span = u[-1][1] - u[0][0]
        if span <= 0:
            continue
        bubble_span_us += span
        bubble_idle_us += span - _measure(u)
    bubble_fraction = round(bubble_idle_us / bubble_span_us, 4) \
        if bubble_span_us > 0 else None
    modules: Dict[str, dict] = {}
    for mod, m in per_module.items():
        mc, mp, mx = _breakdown(m["comm"], m["compute"])
        modules[mod] = {
            "comm_secs": round(mc / 1e6, 6),
            "compute_secs": round(mp / 1e6, 6),
            "exposed_comm_secs": round(mx / 1e6, 6),
        }
    top_ops = sorted(
        ({"op": cls, "secs": round(us / 1e6, 6), "count": n,
          "comm": is_comm_op(cls)}
         for cls, (us, n) in op_totals.items()),
        key=lambda r: -r["secs"])[:15]
    comm_secs = comm_us / 1e6
    exposed = exposed_us / 1e6
    return {
        "compute_secs": round(comp_us / 1e6, 6),
        "comm_secs": round(comm_secs, 6),
        "exposed_comm_secs": round(exposed, 6),
        "overlap_ratio": (round(1.0 - exposed / comm_secs, 4)
                          if comm_secs > 0 else None),
        "bubble_fraction": bubble_fraction,
        "lanes": len(set(comm_ev) | set(comp_iv)),
        # lanes that actually carry compute — the denominator for
        # per-device compute-busy time (a dedicated async collective
        # stream is a lane, but averaging compute over it would halve it)
        "compute_lanes": len(comp_iv),
        "n_op_events": n_op_events,
        "train_dispatches": train_dispatches,
        "exchange_dispatches": exchange_dispatches,
        "modules": modules,
        "top_ops": top_ops,
    }


def load_dir_events(trace_dir: str) -> List[dict]:
    """Raw trace events of the newest capture session under ``trace_dir``,
    merged across per-host files and ``_src``-tagged per file (the lane
    disambiguator ``attribute()``/``schedule_occupancy()`` expect).  Empty
    when no capture is found."""
    events: List[dict] = []
    for src, p in enumerate(find_trace_files(trace_dir)):
        try:
            file_events = load_trace_events(p)
        except (OSError, ValueError):
            continue          # a truncated capture file is not fatal
        for ev in file_events:
            ev["_src"] = src  # lane disambiguator (see attribute())
        events.extend(file_events)
    return events


def profile_dir(trace_dir: str) -> Optional[Dict[str, Any]]:
    """Parse the newest capture session under ``trace_dir`` into one
    attribution dict (events merged across per-host files).  None when no
    capture is found."""
    paths = find_trace_files(trace_dir)
    events = load_dir_events(trace_dir)
    if not events:
        return None
    prof = attribute(events)
    prof["trace_files"] = [os.path.basename(p) for p in paths]
    return prof


# -- schedule occupancy ------------------------------------------------------


def schedule_occupancy(events: Iterable[dict], min_gap_us: float = 1.0,
                       strip_width: int = 96) -> Dict[str, Any]:
    """Per-lane schedule occupancy: classify each compute lane's dispatch
    window into compute / hop / other-comm / idle time, per schedule slot.

    The pipeline scan runs one chunk of layers per tick, so a lane's
    merged compute intervals ARE its schedule slots — their count
    estimates the tick count, and the gaps between them are the
    schedule's bubble (warm-up/drain ticks a device spends cond-gated
    out, plus exposed hop waits).  ``hop`` time is ``collective-permute``
    device time (the stage-boundary activation shift); other collectives
    (psums etc.) classify as ``comm``.  Each lane also gets a ``strip``:
    ``strip_width`` equal time bins over the lane span, each rendered as
    the class owning the most time in the bin (``C`` compute, ``H`` hop,
    ``c`` other comm, ``·`` idle) — a schedule regression is a SHAPE you
    can read, not just a worse scalar.

    Gaps shorter than ``min_gap_us`` merge into the neighboring busy time
    (sub-microsecond runtime jitter is not schedule structure)."""
    comp_iv: Dict[Tuple, List[Tuple[float, float]]] = {}
    hop_iv: Dict[Tuple, List[Tuple[float, float]]] = {}
    comm_iv: Dict[Tuple, List[Tuple[float, float]]] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args")
        if not isinstance(args, dict) or "hlo_op" not in args:
            continue
        try:
            ts = float(ev.get("ts", 0.0))
            dur = float(ev.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        if dur < 0:
            continue
        lane = (ev.get("_src"), ev.get("pid"), ev.get("tid"))
        cls = op_class(ev.get("name", ""))
        if cls.startswith("collective-permute"):
            hop_iv.setdefault(lane, []).append((ts, ts + dur))
        elif is_comm_op(cls):
            comm_iv.setdefault(lane, []).append((ts, ts + dur))
        else:
            comp_iv.setdefault(lane, []).append((ts, ts + dur))

    def _clip(ivs, lo, hi):
        return [(max(s, lo), min(e, hi)) for s, e in ivs
                if min(e, hi) > max(s, lo)]

    def _measure_in(ivs, lo, hi):
        return _measure(_clip(ivs, lo, hi))

    lanes = []
    for lane in sorted(comp_iv, key=str):
        cu = _union(comp_iv[lane])
        # merge sub-min_gap_us jitter between compute slots
        merged: List[Tuple[float, float]] = []
        for s, e in cu:
            if merged and s - merged[-1][1] <= min_gap_us:
                merged[-1] = (merged[-1][0], e)
            else:
                merged.append((s, e))
        lo, hi = merged[0][0], merged[-1][1]
        span = hi - lo
        if span <= 0:
            continue
        hu = _union(hop_iv.get(lane, []))
        mu = _union(comm_iv.get(lane, []))
        comp_us = _measure(merged)
        # busy precedence compute > hop > comm: overlapped (hidden) hop
        # time is not a stall, so it must not double-count against idle
        hop_us = _measure_in(hu, lo, hi)
        comm_us = _measure_in(mu, lo, hi)
        busy = _union(_clip(merged + hu + mu, lo, hi))
        idle_us = span - _measure(busy)
        strip_chars = []
        for b in range(strip_width):
            blo = lo + span * b / strip_width
            bhi = lo + span * (b + 1) / strip_width
            shares = (("C", _measure_in(merged, blo, bhi)),
                      ("H", _measure_in(hu, blo, bhi)),
                      ("c", _measure_in(mu, blo, bhi)))
            best, best_us = "·", 0.0
            covered = 0.0
            for ch, us in shares:
                covered += us
                if us > best_us:
                    best, best_us = ch, us
            if (bhi - blo) - covered > best_us:
                best = "·"
            strip_chars.append(best)
        lanes.append({
            "lane": f"{lane[0]}:{lane[1]}/{lane[2]}",
            "span_secs": round(span / 1e6, 6),
            "compute_secs": round(comp_us / 1e6, 6),
            "hop_secs": round(hop_us / 1e6, 6),
            "comm_secs": round(comm_us / 1e6, 6),
            "idle_secs": round(idle_us / 1e6, 6),
            "bubble_fraction": round(idle_us / span, 4),
            "n_slots": len(merged),
            "strip": "".join(strip_chars),
        })
    spans = sum(l["span_secs"] for l in lanes)
    idles = sum(l["idle_secs"] for l in lanes)
    return {
        "lanes": lanes,
        "n_lanes": len(lanes),
        # span-weighted like attribute()'s bubble_fraction, but gap-merged
        # at min_gap_us — the schedule-structure view of the same metric
        "bubble_fraction": round(idles / spans, 4) if spans > 0 else None,
    }


def _schedule_busy_counts(pp: int, v: int, m: int) -> List[int]:
    """Busy-device count per pipeline tick — the ``real`` column sums of
    ``parallel.pipeline.build_schedule`` (device ``r`` is busy at tick ``t``
    iff ``0 <= t - r < v·m``), replicated here in pure python so this
    module stays stdlib-only (pinned equal to the jax-side table in
    ``tests/test_pipeline_schedule.py``)."""
    pp, v, m = int(pp), int(v), int(m)
    total = v * m
    ticks = total + pp - 1
    return [sum(1 for r in range(pp) if 0 <= t - r < total)
            for t in range(ticks)]


def pipeline_schedule_report(events: Iterable[dict], pp: int, v: int,
                             m: int, passes: int = 2) -> Dict[str, Any]:
    """Measured pipeline-bubble report from a trace capture.

    CPU device lanes are a shared thread pool (one Eigen pool serves every
    simulated device), so per-lane gaps cannot read the SPMD schedule —
    but the schedule's tick structure survives in the ``collective-permute``
    events: every tick each of the ``pp`` devices hops once, so sorted hop
    timestamps group into ticks by COUNT (exactly ``pp`` per tick,
    ``T = v·m+pp−1`` ticks per pass).  A traced train step contains a
    whole number of passes over the schedule — forward plus its scan
    transpose, with XLA free to add replay passes (remat/recompute); the
    per-tick idle sequence is a PALINDROME (ramp-up ``pp−1``, plateau,
    ramp-down), so every consecutive block of ``T`` groups weights
    identically whichever direction it ran — the report never needs to
    know the pass structure.  Each group's start-to-start gap is that
    tick's measured wall time; the schedule table says how many devices
    idle that tick.  Returns:

    - ``schedule_verified``: the hop-event count divides exactly into
      whole ``T·pp`` passes — the compiled program demonstrably runs the
      expected tick count (``v·m+pp−1``, not ``m+pp−1``).
    - ``bubble_fraction``: duration-weighted measured bubble
      ``Σ idle_frac(tick)·dur(tick) / Σ dur(tick)`` — what the schedule's
      idle actually costs in wall time.
    - ``bubble_fraction_ticks``: the analytic ``1 − v·m/T`` over the
      VERIFIED tick structure (``passes`` — expected passes per train
      step, forward + transpose — only scales ``steps_detected``).
    """
    pp, v, m = int(pp), int(v), int(m)
    hops: List[Tuple[float, float]] = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args")
        if not isinstance(args, dict) or "hlo_op" not in args:
            continue
        cls = op_class(ev.get("name", ""))
        if not cls.startswith("collective-permute"):
            continue
        if cls.endswith("-done"):
            # async lowering emits start/done PAIRS per hop; count one
            # event per hop whichever form the backend lowered to
            continue
        try:
            ts = float(ev.get("ts", 0.0))
            dur = float(ev.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        hops.append((ts, ts + dur))
    hops.sort()
    busy = _schedule_busy_counts(pp, v, m)
    ticks_pass = len(busy)
    n_groups = len(hops) // pp
    report: Dict[str, Any] = {
        "pp": pp, "v": v, "m": m, "passes": passes,
        "n_hop_events": len(hops),
        "ticks_per_pass": ticks_pass,
        "measured_ticks": n_groups,
        "schedule_verified": bool(
            hops and len(hops) % (ticks_pass * pp) == 0),
    }
    if not n_groups:
        report.update(bubble_fraction=None, bubble_fraction_ticks=None,
                      passes_detected=0, steps_detected=0)
        return report
    report["passes_detected"] = round(n_groups / ticks_pass, 3)
    report["steps_detected"] = round(n_groups / ticks_pass / passes, 3)
    # idle fraction per tick position within one pass — a palindrome, so
    # the weighting is direction-agnostic and any replay passes XLA adds
    # (remat recompute of the forward under grad) align the same way
    idle_seq = [1.0 - b / pp for b in busy]
    starts = [hops[g * pp][0] for g in range(n_groups)]
    durs = [starts[g + 1] - starts[g] for g in range(n_groups - 1)]
    med = sorted(durs)[len(durs) // 2] if durs else 0.0
    durs.append(med)      # the capture's last tick has no successor
    wsum = dsum = 0.0
    for g, dur in enumerate(durs):
        # clip inter-pass host/dispatch gaps (a "tick" spanning a step
        # boundary) to the median so one gap can't swamp the weighting
        dur = min(dur, 10 * med) if med > 0 else dur
        wsum += idle_seq[g % ticks_pass] * dur
        dsum += dur
    report["bubble_fraction"] = round(wsum / dsum, 4) if dsum > 0 else None
    report["bubble_fraction_ticks"] = round(
        1.0 - (v * m) / ticks_pass, 4)
    return report


def format_schedule(occ: Dict[str, Any]) -> str:
    """Human-readable per-lane occupancy report (the ``--schedule`` view of
    ``scripts/profile_model.py``)."""
    lines = ["per-lane schedule occupancy "
             "(C compute · H hop · c comm · · idle):"]
    for l in occ.get("lanes", []):
        lines.append(
            f"  {l['lane']:<16} slots={l['n_slots']:<4} "
            f"span={l['span_secs'] * 1e3:8.2f}ms "
            f"compute={l['compute_secs'] * 1e3:8.2f}ms "
            f"hop={l['hop_secs'] * 1e3:7.2f}ms "
            f"idle={l['idle_secs'] * 1e3:7.2f}ms "
            f"bubble={l['bubble_fraction']:.4f}")
        lines.append(f"    |{l['strip']}|")
    bf = occ.get("bubble_fraction")
    lines.append(f"  span-weighted bubble_fraction: "
                 f"{bf if bf is not None else 'n/a'}")
    return "\n".join(lines)


# -- programmatic capture ---------------------------------------------------


class _Capture:
    """Result holder for :func:`capture` — ``.profile`` is populated when
    the context exits (None if the backend emitted no usable trace)."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.profile: Optional[Dict[str, Any]] = None


class capture:
    """Context manager driving one programmatic profiler window::

        with devprof.capture("/tmp/trace") as cap:
            for i in range(3):
                step(i)
            jax.block_until_ready(state)     # caller drains BEFORE exit
        cap.profile["overlap_ratio"]

    The caller must block on the traced work before the context exits —
    ``stop_trace`` only sees spans that have already executed."""

    def __init__(self, trace_dir: Optional[str] = None):
        self._own_dir = trace_dir is None
        if trace_dir is None:
            import tempfile
            trace_dir = tempfile.mkdtemp(prefix="devprof_")
        self._cap = _Capture(trace_dir)

    def __enter__(self) -> _Capture:
        import jax
        jax.profiler.start_trace(self._cap.trace_dir)
        return self._cap

    def __exit__(self, exc_type, exc, tb) -> None:
        import jax
        jax.profiler.stop_trace()
        if exc_type is None:
            try:
                self._cap.profile = profile_dir(self._cap.trace_dir)
            except Exception:
                self._cap.profile = None    # attribution must never raise
                                            # into the training loop
        if self._own_dir:
            # anonymous capture: the caller only wants the attribution, so
            # the multi-MB .trace.json.gz files must not accumulate under
            # /tmp across bench rows (pass trace_dir to keep the raw
            # capture for Perfetto)
            import shutil
            shutil.rmtree(self._cap.trace_dir, ignore_errors=True)


# -- consumers --------------------------------------------------------------


def feed_telemetry(profile: Dict[str, Any], tm=None) -> None:
    """Record one attribution result into the PR 4 registry: the
    :data:`DEVICE_GAUGES` gauges plus one :data:`PROFILE_EVENT` stream
    event (scalars + top 3 op classes — bounded, JSONL-friendly).  The
    schema-drift checker drives this live and pins the gauge set."""
    if tm is None:
        from . import telemetry
        tm = telemetry.active()
    if not tm.enabled:
        return
    for gname, key in zip(DEVICE_GAUGES,
                          ("compute_secs", "comm_secs", "exposed_comm_secs",
                           "overlap_ratio", "lanes")):
        v = profile.get(key)
        if v is not None:
            tm.gauge(gname, float(v))
    tm.event(PROFILE_EVENT,
             compute_secs=profile.get("compute_secs"),
             comm_secs=profile.get("comm_secs"),
             exposed_comm_secs=profile.get("exposed_comm_secs"),
             overlap_ratio=profile.get("overlap_ratio"),
             lanes=profile.get("lanes"),
             train_dispatches=profile.get("train_dispatches"),
             top_ops=[o["op"] for o in profile.get("top_ops", [])[:3]])


def profile_row_fields(profile: Dict[str, Any],
                       total_flops: Optional[float] = None,
                       peak_flops: Optional[float] = None) -> Dict[str, Any]:
    """The bench-row columns (:data:`TRACE_ROW_COLUMNS`, all keys always
    present).  ``device_mfu`` is the trace-derived cross-check of the
    ``cost_analysis`` MFU column: ``total_flops`` (per-device flops over
    the WHOLE traced window) against one lane's compute-busy time —
    None when flops/peak are unknown or the trace saw no compute."""
    lanes = profile.get("compute_lanes") or profile.get("lanes") or 0
    compute = profile.get("compute_secs") or 0.0
    mfu = None
    if total_flops and peak_flops and lanes and compute > 0:
        per_lane_secs = compute / lanes
        mfu = round(float(total_flops) / per_lane_secs / float(peak_flops), 4)
        if not math.isfinite(mfu):
            mfu = None
    return {
        "overlap_ratio": profile.get("overlap_ratio"),
        "exposed_comm_secs": profile.get("exposed_comm_secs"),
        "device_compute_secs": profile.get("compute_secs"),
        "device_comm_secs": profile.get("comm_secs"),
        "device_mfu": mfu,
        "bubble_fraction": profile.get("bubble_fraction"),
    }


def update_state_report(model) -> Dict[str, Any]:
    """Per-chip update-plane memory (:data:`USHARD_ROW_COLUMNS`): what a
    chip actually holds for the optimizer state + exchanger extra, against
    the replicated-equivalent layout.

    Measured, not modeled, on the live boxed state: every boxed leaf is
    ``[n_workers, ...]`` sharded ``P(workers)``, so per-chip bytes ARE
    boxed bytes over worker count — for a sharded leaf the rows are the
    partition (chunk each), for a replicated leaf each row is one full
    copy.  The replicated-equivalent prices ``model._replicated_opt``
    (the pre-chunking optimizer, EMA included) eval_shaped on the full
    params plus the rule's FULL extra template — per-worker divergent
    state (error feedback) appears identically on both sides, so the
    shrink ratio isolates exactly the redundancy sharding removes.
    ``scripts/predict_scaling.py`` joins its analytic model against these
    columns; bench.py folds them into sharded/control rows."""
    import jax
    import numpy as np
    from ..parallel.mesh import WORKER_AXIS

    def tree_bytes(t) -> int:
        return int(sum(
            int(np.prod(np.shape(x)) or 1) * np.dtype(x.dtype).itemsize
            for x in jax.tree.leaves(t)))

    n = int(model.mesh.shape[WORKER_AXIS])
    state = model.step_state
    assert state is not None, "update_state_report needs a compiled model"
    per_chip = (tree_bytes(state["opt_state"])
                + tree_bytes(state["extra"])) // n
    opt = getattr(model, "_replicated_opt", None) or model.opt
    full_opt = jax.eval_shape(opt.init, model.params)
    exch = model.exchanger
    full_extra = exch._extra_full_template() \
        if hasattr(exch, "_extra_full_template") \
        else exch.extra_state_template()
    replicated = tree_bytes(full_opt) + tree_bytes(full_extra)
    return {
        "update_state_bytes_per_chip": per_chip,
        "update_state_bytes_replicated": replicated,
        "update_state_shrink": (round(replicated / per_chip, 3)
                                if per_chip else None),
    }


def compress_traffic_model(strategy: str, n_elems: int, n_workers: int, *,
                           rank: int = 2, chunk: int = 8192,
                           k_c: Optional[int] = None,
                           leaf_shapes: Optional[list] = None
                           ) -> Optional[Dict[str, Any]]:
    """Analytic per-exchange HBM-traffic model for the compression
    pipelines — pure python, jax-free (scripts/predict_scaling.py joins it
    against measured rows without touching a backend).

    Accounting contract: XLA-op granularity with NO fusion credit — every
    jnp-level op in the strategy's exchange reads its operands and writes
    its result to HBM, fp32 = 4 bytes/elem.  That is an upper bound for
    what XLA's fuser actually emits from the unfused graph, and exact for
    the Pallas kernels (each kernel is one pass by construction), so the
    legacy/fused ratio is the *guaranteed-by-construction* shrink, not a
    measured one.  Stage lists name every counted op so the estimate is
    auditable.

    Returns ``None`` for strategies with no compression pipeline.
    """
    w = int(n_workers)

    def _total(stages):
        return float(sum(b for _, b in stages))

    if strategy == "onebit":
        # pad to the pack grid, like flatten_tree(pad_to_multiple_of=...)
        n = n_elems + (-n_elems) % 32768
        fn, pk = 4.0 * n, n / 8.0          # fp32 pass / packed buffer bytes
        legacy_enc = [
            ("add c = flat + state", 3 * fn),
            ("abs(c)", 2 * fn),
            ("mean reduce -> scale", fn),
            ("where(c==0, 1, c)", 2 * fn),
            ("sign", 2 * fn),
            ("scale * sign", 2 * fn),
            ("sub -> new_state", 3 * fn),
            ("pack_signs", fn + pk),
        ]
        legacy_dec = [
            ("unpack+weighted-sum", w * pk + fn),
            ("div /size -> mean", 2 * fn),
        ]
        fused_enc = [
            ("pack_signs_encode kernel", 2 * fn + pk + fn),
            ("mean reduce -> scale", fn),
            ("signed_residual kernel", fn + pk + fn),
        ]
        fused_dec = [
            ("unpack_signs_weighted_mean kernel", w * pk + fn),
        ]
    elif strategy == "topk":
        n = n_elems + (-n_elems) % chunk
        rows = n // chunk
        k = int(k_c or max(1, round(chunk * 0.01)))
        fn = 4.0 * n
        wire = 4.0 * rows * k              # bf16 val + int16 offset per slot
        legacy_enc = [
            ("add c = flat + state", 3 * fn),
            ("abs(c)", 2 * fn),
            ("top_k select", fn + 2 * wire),
            ("take_along_axis vals", fn + wire),
            ("bf16/int16 casts + residual", 3 * wire),
            ("scatter-set residual -> new_state", 3 * fn),
        ]
        legacy_dec = [
            ("zeros dense", fn),
            ("global-index arith", 3 * w * wire),
            ("serialized HBM scatter-add", 2 * fn + w * wire),
            ("div /size -> mean", 2 * fn),
        ]
        # no fused topk pipeline: its kernel pair was deleted after losing
        # to this op graph on the chip (PR 21, ROADMAP S6) — shrink 1.0
        fused_enc, fused_dec = legacy_enc, legacy_dec
    elif strategy.startswith("powersgd"):
        r = rank
        shapes = [s for s in (leaf_shapes or [])
                  if len(s) >= 2
                  and min(math.prod(s[:-1]), int(s[-1])) > 4 * r]
        if not shapes:
            return None
        fac = 4.0 * r * sum(math.prod(s[:-1]) + int(s[-1])
                            for s in shapes)   # both factors' fp32 bytes
        mats = 4.0 * sum(math.prod(s) for s in shapes)
        legacy_enc = [
            ("Mp = M + e (per leaf)", 3 * mats),
            ("factor matmuls", 2 * (mats + fac)),
            ("per-leaf staging pack (flatten/pad/concat)", 2 * fac),
            ("per-leaf psum staging copies", 2 * fac),
        ]
        legacy_dec = [
            ("qr + Mhat decode", mats + 2 * fac),
            ("residual e' = Mp - Mhat", 3 * mats),
        ]
        fused_enc = [
            ("Mp = M + e (per leaf)", 3 * mats),
            ("matmul_pack kernels (MXU -> staging)", 2 * (mats + fac)),
            ("stacked psum staging (one buffer)", 2 * fac),
        ]
        fused_dec = legacy_dec
    else:
        return None

    legacy = _total(legacy_enc) + _total(legacy_dec)
    fused = _total(fused_enc) + _total(fused_dec)
    return {
        "strategy": strategy,
        "n_workers": w,
        "stages": {"legacy_encode": legacy_enc, "legacy_decode": legacy_dec,
                   "fused_encode": fused_enc, "fused_decode": fused_dec},
        "compress_hbm_bytes_legacy": legacy,
        "compress_hbm_bytes_fused": fused,
        "compress_hbm_shrink": round(legacy / fused, 3),
        "compress_decode_shrink": round(_total(legacy_dec)
                                        / _total(fused_dec), 3),
    }


def compress_traffic_report(model) -> Optional[Dict[str, Any]]:
    """The :data:`COMPRESS_ROW_COLUMNS` bench columns for a live model —
    :func:`compress_traffic_model` fed from the model's actual strategy
    config and parameter count.  ``None`` when the exchange strategy has
    no compression pipeline; bench.py folds the columns into onebit/topk/
    powersgd rows next to the measured step time."""
    import jax
    strat = model.exchanger.strategy
    leaf_shapes = [tuple(getattr(l, "shape", ()) or ())
                   for l in jax.tree.leaves(model.params)]
    n_elems = sum(math.prod(s) if s else 1 for s in leaf_shapes)
    from ..parallel.mesh import WORKER_AXIS
    w = int(model.mesh.shape[WORKER_AXIS])
    kw: Dict[str, Any] = {}
    if strat.name == "topk":
        kw = {"chunk": strat.chunk, "k_c": strat._k_c()}
    elif strat.name.startswith("powersgd"):
        kw = {"rank": strat.rank, "leaf_shapes": leaf_shapes}
    m = compress_traffic_model(strat.name, n_elems, w, **kw)
    if m is None:
        return None
    return {c: m[c] for c in COMPRESS_ROW_COLUMNS}


def format_profile(profile: Dict[str, Any], top: int = 15) -> str:
    """Human-readable breakdown (profile_model.py / worker verbose)."""
    lines = [
        f"device time: compute {profile['compute_secs']:.4f}s  "
        f"comm {profile['comm_secs']:.4f}s  "
        f"exposed comm {profile['exposed_comm_secs']:.4f}s  "
        + (f"overlap {profile['overlap_ratio']:.1%}"
           if profile.get("overlap_ratio") is not None else "overlap n/a")
        + f"  ({profile['lanes']} lane(s), "
          f"{profile['n_op_events']} op events, "
          f"{profile['train_dispatches']} train dispatch(es))"]
    if profile.get("top_ops"):
        lines.append("top op classes by device time:")
        total = sum(o["secs"] for o in profile["top_ops"]) or 1.0
        for o in profile["top_ops"][:top]:
            tag = " [comm]" if o["comm"] else ""
            lines.append(f"  {o['secs'] * 1e3:9.2f} ms  "
                         f"{100 * o['secs'] / total:5.1f}%  x{o['count']:<5d} "
                         f"{o['op'][:90]}{tag}")
    return "\n".join(lines)
