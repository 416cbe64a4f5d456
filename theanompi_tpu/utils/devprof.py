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

This module is the program's ONE trace reader.  ``jax.profiler.stop_trace``
writes ``<dir>/plugins/profile/<session>/<host>.xplane.pb`` on every
backend (the TPU writes nothing else; the CPU backend of this container
also writes a Chrome ``.trace.json.gz`` with the same events, which nothing
reads any more), and ``jax.profiler.ProfileData`` reads it without
tensorboard or TensorFlow.  :func:`xplane_events` turns it into the event
dicts :func:`attribute` takes (``ph``/``pid``/``tid``/``ts``/``dur`` in
microseconds, ``name``, ``args.hlo_op`` / ``args.hlo_module``):

* **TPU** — one plane per chip, ``/device:TPU:<n>``; its line ``XLA Ops``
  holds one event per executed HLO instruction whose *name is the whole
  instruction text* (``%all-reduce.16 = (f32[96]{...}, ...) all-reduce(...)``,
  see ``tests/data/tpu_v5e_bsp4_trace_names.json``), so the opcode is
  parsed from the text after the result type; the line ``XLA Modules``
  (``jit_per_worker(<fingerprint>)``) says which program an instruction
  ran in.  The events' stats carry no op name or scope.
* **CPU** — the op events *are* host events: plane ``/host:CPU``, one line
  per executor thread, every op event marked by an ``hlo_op`` stat and
  named after the jax primitive it came from (``psum_invariant.7``), not
  its opcode.  The opcode is looked up in the compiled module's HloProto,
  which the profiler embeds in the plane ``/host:metadata``
  (:func:`_hlo_opcodes`, a forty-line protobuf walk: ``ProfileData`` does
  not expose event metadata).

A collective's event is named by its opcode (``all-reduce``,
``all-reduce-start``), every other event by its instruction, so
:func:`op_class` and :func:`is_comm_op` work on both.

**What is captured** — :func:`profile_options`: on a TPU, device planes
only.  With the host tracer on, PJRT's host-side layout transposition of
every staged batch emits 0.5–2.4 million events per runtime thread: a
1.1 GB trace, and 4 steps in 18 s where the untraced run makes 4.2 a second
(PERF.md §6, PR 23 finding 2).  At level 0 the trace is a few MB and the
pace untouched.  The host side of a capture is ``telemetry``'s always-on
span ring instead: :func:`write_host_spans` keeps the rows of the capture's
interval beside the trace as ``host_spans.jsonl``, and the session's
``profile_start_time`` (Unix ns, plane ``Task Environment``) puts them on
the trace's clock (``telemetry.on_trace_clock``).  :func:`idle_by_span`
then says, for chip 0, under which span of each thread class (main,
producer, pool) the device sat idle.  On the CPU backend the host tracer
stays on: there the op events are host events.

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

Dispatch counts come from the ring, not from the trace: one ``train.call``
row per dispatch of the step program (``train_iter``, or a script that wraps
its own ``train_fn`` call in ``telemetry.span("train.call")``), one
``exchange`` row per standalone exchange.

Consumers: the worker's ``trace_dir`` capture feeds the result into the
PR 4 telemetry registry as ``device.*`` gauges (:func:`feed_telemetry` —
names pinned by the tpulint schema-drift checker) and prints
:func:`format_profile`.

No jax at module scope (the lint CLI and stdlib scripts import this for
the schema constants); :func:`capture` imports it lazily.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
from bisect import bisect_right
from typing import Any, Dict, Iterable, List, Optional, Tuple

# Ring span names that count dispatches (telemetry.SPANS), and the file the
# worker leaves beside a capture.
TRAIN_DISPATCH_SPAN = "train.call"
EXCHANGE_SPAN = "exchange"
HOST_SPANS_FILE = "host_spans.jsonl"

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

# The report columns of a pipelined run (pp > 1) — the
# :func:`pipeline_schedule_report` measurement: the tick-count bubble read
# off the hop events (exact when the capture verifies), the wall-time
# weighted bubble, and the verification bit itself.  Declared HERE, the
# one jax-free schema home for the report vocabularies
# (``scripts/predict_scaling.py`` joins on them); disjointness from the
# other two is pinned in tests/test_pipeline_schedule.py.
PIPELINE_ROW_COLUMNS = (
    "pipeline_bubble_ticks",
    "pipeline_bubble_time",
    "pipeline_schedule_verified",
)

# The report columns of an update-plane-sharded run
# (parallel/update_sharding.py, docs/design.md §23) — the
# :func:`update_state_report` measurement: per-chip update-plane
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

# The report columns of a compressed wire (onebit/topk/powersgd
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


# -- capture options, trace file discovery / loading -------------------------

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
ENV_PLANE = "Task Environment"
START_STAT = "profile_start_time"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def profile_options():
    """``ProfileOptions`` for ``jax.profiler.start_trace``, the same for the
    worker's ``trace_dir`` capture and :class:`capture`: on a TPU the host
    and python tracers are off, so the trace holds device planes only (the
    module docstring and PERF.md §6 finding 2 say what host tracing costs
    while batches are staged); elsewhere the host tracer stays on, because
    the CPU backend's op events are host events."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    if jax.default_backend() == "tpu":
        opts.host_tracer_level = 0
    return opts


def find_xplane_files(trace_dir: str) -> List[str]:
    """The ``*.xplane.pb`` files of the NEWEST capture session under
    ``trace_dir`` (jax writes ``plugins/profile/<timestamp>/`` per
    ``stop_trace``; one file per host)."""
    sessions = [d for d in glob.glob(os.path.join(trace_dir, "plugins",
                                                  "profile", "*"))
                if os.path.isdir(d)]
    if not sessions:
        return []
    newest = max(sessions, key=os.path.getmtime)
    return sorted(glob.glob(os.path.join(newest, "*.xplane.pb")))


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _pb_fields(buf: bytes):
    """``(field number, value)`` pairs of one protobuf message: varints as
    ints, length-delimited fields as bytes, fixed ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield key >> 3, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire}")


def _hlo_opcodes(xspace: bytes) -> Dict[Tuple[str, str], str]:
    """``{(module, instruction name): opcode}`` from the HloProtos the
    profiler embeds in the plane ``/host:metadata``: XSpace.planes(1) ->
    XPlane.event_metadata(4, a map entry whose value is 2) ->
    XEventMetadata.stats(5) -> XStat.bytes_value(6) = HloProto ->
    hlo_module(1) -> name(1), computations(3) -> instructions(2) ->
    name(1), opcode(2).  Empty when the trace embeds none."""
    def sub(buf, field):
        return [v for f, v in _pb_fields(buf) if f == field
                and isinstance(v, bytes)]

    out: Dict[Tuple[str, str], str] = {}
    for plane in sub(xspace, 1):
        if b"/host:metadata" not in sub(plane, 2):
            continue
        for entry in sub(plane, 4):
            for meta in sub(entry, 2):
                for stat in sub(meta, 5):
                    for proto in sub(stat, 6):
                        try:
                            for mod in sub(proto, 1):
                                mname = sub(mod, 1)[0].decode()
                                for comp in sub(mod, 3):
                                    for ins in sub(comp, 2):
                                        out[mname, sub(ins, 1)[0].decode()] \
                                            = sub(ins, 2)[0].decode()
                        except (IndexError, ValueError,
                                UnicodeDecodeError):
                            continue    # a bytes stat that is no HloProto
    return out


def hlo_opcode(hlo_text: str) -> str:
    """The opcode of an instruction given as HLO text (a TPU ``XLA Ops``
    event's name): the token after the result type, which is one token
    without spaces or a parenthesised tuple.  Text that is no instruction
    (no `` = ``) names itself."""
    if " = " not in hlo_text:
        return hlo_text.strip()
    rest = hlo_text.split(" = ", 1)[1].lstrip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.split(" ", 1)[1].lstrip() if " " in rest else ""
    return re.split(r"[\s(]", rest, maxsplit=1)[0]


def _event_name(instruction: str, opcode: Optional[str]) -> str:
    """A collective is named by its opcode, anything else by itself."""
    return opcode if opcode and is_comm_op(opcode) else instruction


def xplane_events(xspace: bytes, src: int = 0
                  ) -> Tuple[List[dict], Optional[int]]:
    """One serialized XSpace -> (the op events as the dicts
    :func:`attribute` takes, the session's ``profile_start_time`` in Unix
    ns).  Times are microseconds on the trace's clock; a lane is
    ``(src, plane, line)``."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_serialized_xspace(xspace)
    opcodes: Optional[Dict[Tuple[str, str], str]] = None
    events: List[dict] = []
    start_ns = None
    for plane in profile.planes:
        if plane.name == ENV_PLANE:
            start_ns = dict(plane.stats).get(START_STAT)
            continue
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.split("(", 1)[0])
                          for e in getattr(lines.get(MODULES_LINE),
                                           "events", ()))
            starts = [m[0] for m in mods]
            for e in getattr(lines.get(OPS_LINE), "events", ()):
                instr = e.name.split(" = ", 1)[0].strip().lstrip("%")
                i = bisect_right(starts, e.start_ns) - 1
                module = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] \
                    else "?"
                events.append({
                    "ph": "X", "_src": src, "pid": plane.name,
                    "tid": OPS_LINE, "ts": e.start_ns / 1e3,
                    "dur": e.duration_ns / 1e3,
                    "name": _event_name(instr, hlo_opcode(e.name)),
                    "args": {"hlo_op": instr, "hlo_module": module}})
            continue
        for line in plane.lines:          # host planes: the CPU backend
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" not in stats:
                    continue              # host python/runtime span
                if opcodes is None:
                    opcodes = _hlo_opcodes(xspace)
                module = str(stats.get("hlo_module", "?"))
                events.append({
                    "ph": "X", "_src": src, "pid": plane.name,
                    "tid": line.name, "ts": e.start_ns / 1e3,
                    "dur": e.duration_ns / 1e3,
                    "name": _event_name(e.name,
                                        opcodes.get((module, e.name))),
                    "args": {"hlo_op": e.name, "hlo_module": module}})
    return events, start_ns


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


def attribute(events: Iterable[dict],
              host_rows: Iterable[tuple] = ()) -> Dict[str, Any]:
    """Per-dispatch device-time breakdown from raw trace events.

    Returns a plain JSON-able dict: ``compute_secs`` / ``comm_secs`` /
    ``exposed_comm_secs`` / ``overlap_ratio`` / ``lanes`` totals, the
    per-``hlo_module`` breakdown, the top op classes by device time, and
    the dispatch counts (``train_dispatches`` / ``exchange_dispatches``)
    read off ``host_rows``, the span ring's rows of the capture interval
    (``telemetry.spans``)."""
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
    host_names = [r[0] for r in host_rows]
    train_dispatches = host_names.count(TRAIN_DISPATCH_SPAN)
    exchange_dispatches = host_names.count(EXCHANGE_SPAN)
    n_op_events = 0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
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


def _load_dir(trace_dir: str) -> Tuple[List[dict], Optional[int], List[str]]:
    events: List[dict] = []
    start_ns = None
    paths = find_xplane_files(trace_dir)
    for src, p in enumerate(paths):
        try:
            with open(p, "rb") as f:
                file_events, file_start = xplane_events(f.read(), src)
        except Exception:
            continue          # a truncated capture file is not fatal
        events.extend(file_events)
        if start_ns is None:
            start_ns = file_start
    return events, start_ns, paths


def session_start_ns(trace_dir: str) -> Optional[int]:
    """The newest session's ``profile_start_time`` (Unix ns), read off the
    plane ``Task Environment`` without walking the events."""
    from jax.profiler import ProfileData
    for p in find_xplane_files(trace_dir):
        try:
            plane = ProfileData.from_file(p).find_plane_with_name(ENV_PLANE)
        except Exception:
            continue
        if plane is not None:
            return dict(plane.stats).get(START_STAT)
    return None


def load_dir_events(trace_dir: str) -> List[dict]:
    """Op events of the newest capture session under ``trace_dir``, merged
    across per-host files and ``_src``-tagged per file (the lane
    disambiguator ``attribute()``/``schedule_occupancy()`` expect).  Empty
    when no capture is found."""
    return _load_dir(trace_dir)[0]


def profile_dir(trace_dir: str, host_rows: Optional[List[tuple]] = None
                ) -> Optional[Dict[str, Any]]:
    """Parse the newest capture session under ``trace_dir`` into one
    attribution dict (events merged across per-host files).  The host side
    is ``host_rows`` (ring rows, Unix ns) or, failing that, the
    ``host_spans.jsonl`` the worker left in ``trace_dir``: dispatch counts,
    and with the session's start time the idle-by-span table.  None when
    no capture is found."""
    events, start_ns, paths = _load_dir(trace_dir)
    if not events:
        return None
    if host_rows is None:
        host_rows = read_host_spans(trace_dir)
    prof = attribute(events, host_rows)
    prof["trace_files"] = [os.path.basename(p) for p in paths]
    if host_rows and start_ns is not None:
        prof["idle_by_span"] = idle_by_span(events, host_rows, start_ns)
    return prof


# -- the host side of a capture: the span ring's rows ------------------------


def write_host_spans(trace_dir: str, t0_ns: int, t1_ns: int) -> str:
    """Keep the span ring's rows of ``[t0_ns, t1_ns]`` (Unix ns) beside a
    capture, one JSON object a line (``name``, ``tid``, ``t0``, ``t1``,
    ``parent``, ``batch``) under a header line with the interval and the
    session's ``profile_start_time``: subtract it (``on_trace_clock``) and
    a row lies on the trace's clock."""
    from . import telemetry
    start_ns = session_start_ns(trace_dir)
    path = os.path.join(trace_dir, HOST_SPANS_FILE)
    os.makedirs(trace_dir, exist_ok=True)
    with open(path, "w") as f:
        f.write(json.dumps({"t0": t0_ns, "t1": t1_ns,
                            "profile_start_time": start_ns}) + "\n")
        for name, tid, t0, t1, parent, batch in telemetry.spans(t0_ns,
                                                                 t1_ns):
            # a row that straddles an end is cut to the interval
            f.write(json.dumps({"name": name, "tid": tid,
                                "t0": max(t0, t0_ns), "t1": min(t1, t1_ns),
                                "parent": parent, "batch": batch}) + "\n")
    return path


def read_host_spans(trace_dir: str) -> List[tuple]:
    """The rows of ``host_spans.jsonl`` as ring tuples; empty if absent."""
    path = os.path.join(trace_dir, HOST_SPANS_FILE)
    if not os.path.exists(path):
        return []
    with open(path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    return [(r["name"], r["tid"], r["t0"], r["t1"], r["parent"],
             r["batch"]) for r in recs[1:]]


def thread_classes(rows: Iterable[tuple]) -> Dict[int, str]:
    """``{thread id: 'producer' | 'pool' | 'main'}`` by the spans a thread
    wrote: the one that plans is the loader's producer, one that only
    materializes or stages is a pool thread, any other is the consumer."""
    names: Dict[int, set] = {}
    for row in rows:
        names.setdefault(row[1], set()).add(row[0])
    return {tid: "producer" if "input.plan" in n or "input.enqueue" in n
            else "pool" if n & {"input.materialize", "input.device_put"}
            else "main" for tid, n in names.items()}


def innermost_by_piece(pieces: List[Tuple[float, float]],
                       rows: List[tuple]) -> Dict[str, float]:
    """Length of ``pieces`` (sorted, disjoint intervals) by the innermost
    (shortest) of one thread's ``rows`` ``(name, t0, t1)`` open at the
    time, ``(none)`` where none is."""
    cuts = sorted({t for _, a, b in rows for t in (a, b)})
    by: Dict[str, float] = {}
    for lo, hi in pieces:
        inside = cuts[bisect_right(cuts, lo):bisect_right(cuts, hi)]
        edges = [lo] + [c for c in inside if lo < c < hi] + [hi]
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            cover = [(t1 - t0, name) for name, t0, t1 in rows
                     if t0 <= mid < t1]
            label = min(cover)[1] if cover else "(none)"
            by[label] = by.get(label, 0.0) + b - a
    return by


def idle_by_span(events: Iterable[dict], host_rows: Iterable[tuple],
                 start_ns: int) -> Dict[str, Any]:
    """The first device lane's idle time inside the host rows' interval,
    by the innermost span open on each thread class — the operator's
    version of the benchmark's ``idle_gaps``, with the loader's threads in
    it.  A class with several threads (the pool) gets the mean over them,
    so every column sums to the idle time.  Seconds."""
    host_rows = list(host_rows)
    lanes: Dict[Tuple, List[Tuple[float, float]]] = {}
    for ev in events:
        lane = (ev.get("_src"), ev.get("pid"), ev.get("tid"))
        lanes.setdefault(lane, []).append(
            (ev["ts"] * 1e3, (ev["ts"] + ev["dur"]) * 1e3))
    if not lanes or not host_rows:
        return {}
    lane = min(lanes, key=str)
    # the rows' interval, cut to the session: the trace's clock starts at
    # 0, and before it the device's events are simply not there
    lo = max(0, min(r[2] for r in host_rows) - start_ns)
    hi = max(r[3] for r in host_rows) - start_ns
    idle, at = [], lo
    for s, e in _union(lanes[lane]):
        if e <= lo or s >= hi:
            continue
        if s > at:
            idle.append((at, s))
        at = max(at, e)
    if hi > at:
        idle.append((at, hi))
    classes = thread_classes(host_rows)
    per_thread: Dict[int, List[tuple]] = {}
    for name, tid, t0, t1, _parent, _batch in host_rows:
        per_thread.setdefault(tid, []).append(
            (name, t0 - start_ns, t1 - start_ns))
    out: Dict[str, Any] = {"lane": f"{lane[1]}/{lane[2]}",
                           "window_secs": (hi - lo) / 1e9,
                           "idle_secs": _measure(idle) / 1e9,
                           "threads": {}, "by_class": {}}
    for cls in ("main", "producer", "pool"):
        tids = [t for t, c in classes.items() if c == cls]
        if not tids:
            continue
        acc: Dict[str, float] = {}
        for tid in tids:
            for label, ns in innermost_by_piece(idle,
                                                per_thread[tid]).items():
                acc[label] = acc.get(label, 0.0) + ns / len(tids)
        out["threads"][cls] = len(tids)
        out["by_class"][cls] = sorted(
            ([label, ns / 1e9] for label, ns in acc.items()),
            key=lambda kv: -kv[1])
    return out


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
    """Human-readable per-lane occupancy report."""
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
        import time
        import jax
        self._t0_ns = time.time_ns()
        jax.profiler.start_trace(self._cap.trace_dir,
                                 profiler_options=profile_options())
        return self._cap

    def __exit__(self, exc_type, exc, tb) -> None:
        import time
        import jax
        from . import telemetry
        jax.profiler.stop_trace()
        if exc_type is None:
            try:
                self._cap.profile = profile_dir(
                    self._cap.trace_dir,
                    telemetry.spans(self._t0_ns, time.time_ns()))
            except Exception:
                self._cap.profile = None    # attribution must never raise
                                            # into the training loop
        if self._own_dir:
            # anonymous capture: the caller only wants the attribution, so
            # the multi-MB capture files must not accumulate under
            # /tmp across captures (pass trace_dir to keep the raw
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
    columns."""
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
    """The :data:`COMPRESS_ROW_COLUMNS` columns for a live model —
    :func:`compress_traffic_model` fed from the model's actual strategy
    config and parameter count.  ``None`` when the exchange strategy has
    no compression pipeline."""
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
    """Human-readable breakdown (worker verbose)."""
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
    idle = profile.get("idle_by_span")
    if idle:
        lines.append(
            f"device idle by host span ({idle['lane']}: idle "
            f"{idle['idle_secs'] * 1e3:.1f} ms of "
            f"{idle['window_secs'] * 1e3:.1f} ms; innermost open span per "
            f"thread class, a class's threads averaged):")
        for cls, rows in idle["by_class"].items():
            lines.append(f"  {cls} ({idle['threads'][cls]} thread(s)):")
            for label, secs in rows[:top]:
                share = 100 * secs / idle["idle_secs"] \
                    if idle["idle_secs"] else 0.0
                lines.append(f"    {secs * 1e3:9.2f} ms  {share:5.1f}%  "
                             f"{label}")
    return "\n".join(lines)
