"""From a ``jax.profiler`` trace to tables of intervals, and from those to
numbers.  The benchmark's own reduction: nothing here reads ``utils/devprof``.

What a TPU v5e trace looks like (``tests/data/tpu_v5e_bsp4_trace_names.json``,
PR 21): one plane per chip named ``/device:TPU:<n>``; on it the line
``XLA Modules`` holds one event per executed program
(``jit_per_worker(<fingerprint>)``) and the line ``XLA Ops`` one event per
executed HLO instruction, whose *name is the whole instruction text*
(``%all-reduce.16 = (f32[96]{...}, ...) all-reduce(...)``) and carries no
category, so the opcode is parsed from the text after the result type.

The host is traced at level 0, so the trace holds no host span: with host
tracing on, every chunk of PJRT's host-side layout transposition of a staged
batch is an event -- millions per second, a 1.1 GB trace and a host that
dispatches 40 times slower (my chip runs, PR 23).  Host intervals reach the
trace's clock another way: the profiler subtracts the session's start from
every timestamp and keeps that start, in Unix nanoseconds, as the stat
``profile_start_time`` of the plane ``Task Environment``; a host
``time.time_ns()`` minus it is a time on the trace's clock.

An event says nothing of the layer its instruction belongs to: that is in
the compiled program's HLO text, where an instruction's ``metadata`` holds
the ``op_name`` jax gave it, the path of transforms, named scopes and the
primitive (``jit(per_worker)/transpose(jvp(mla))/dot_general``).
:func:`scopes_from_hlo` makes the join PR 26 made by hand, instruction name
to ``op_name``; :func:`scope_busy_ns` reads a named scope's device time.

All times are integer nanoseconds on the trace's clock.  An interval is a
``(start, end)`` pair, a table row ``(name, start, end)``.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]
Row = Tuple[str, int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
ENV_PLANE = "Task Environment"
START_STAT = "profile_start_time"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

@dataclass
class DeviceTables:
    index: int
    modules: List[Row] = field(default_factory=list)
    ops: List[Row] = field(default_factory=list)


@dataclass
class TraceTables:
    devices: List[DeviceTables]
    start_unix_ns: Optional[int] = None   # the session's start, Unix clock

    def on_trace_clock(self, unix_ns: int) -> int:
        """A host ``time.time_ns()`` as a time on the trace's clock."""
        return unix_ns - self.start_unix_ns


def tables_from_profile(profile) -> TraceTables:
    """``jax.profiler.ProfileData`` -> tables: the two device lines of each
    chip's plane, and the session's start."""
    tables = TraceTables([])
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceTables(int(m.group(1)))
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dev.modules = _rows(line)
                elif line.name == OPS_LINE:
                    dev.ops = _rows(line)
            tables.devices.append(dev)
        elif plane.name == ENV_PLANE:
            tables.start_unix_ns = dict(plane.stats).get(START_STAT)
    tables.devices.sort(key=lambda d: d.index)
    return tables


def _rows(line) -> List[Row]:
    rows = [(e.name, int(round(e.start_ns)),
             int(round(e.start_ns + e.duration_ns))) for e in line.events]
    rows.sort(key=lambda r: r[1])
    return rows


def load_xplane(path: str) -> TraceTables:
    from jax.profiler import ProfileData
    return tables_from_profile(ProfileData.from_file(path))


def load_text_proto(path: str) -> TraceTables:
    """A recorded trace kept as an XSpace text proto (``testdata/``)."""
    from jax.profiler import ProfileData
    with open(path) as f:
        return tables_from_profile(ProfileData.from_text_proto(f.read()))


# ---------------------------------------------------------------------------
# HLO instruction text
# ---------------------------------------------------------------------------

def instruction_name(hlo_text: str) -> str:
    """``%all-reduce.16 = ...`` -> ``%all-reduce.16``."""
    return hlo_text.split(" = ", 1)[0].strip()


def opcode(hlo_text: str) -> str:
    """The opcode of an instruction given as HLO text: the token after the
    result type.  The type is either one token without spaces
    (``bf16[128,13,13]{...:T(8,128)(2,1)}``) or a parenthesised tuple that
    holds spaces, commas and ``/*index=5*/`` comments.  Text that is no
    instruction (no `` = ``) is returned whole: it names itself."""
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


def collective_base(op: str) -> Optional[str]:
    """``all-reduce-start`` -> ``all-reduce``; None if ``op`` is no
    collective."""
    for base in COLLECTIVES:
        if op in (base, base + "-start", base + "-done"):
            return base
    return None


# ---------------------------------------------------------------------------
# scopes: the compiled program's text joined to the trace's instructions
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?(%?[\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=(%?[\w.\-]+)")
_OPERAND = re.compile(r"%[\w.\-]+")
_OUTER = re.compile(r"^(p?jit\([^()]*\)|shard_map)$")   # the program's wrap
TAIL_CHARS = 64


def scopes_from_hlo(text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` over every computation of a compiled
    module's text (names are unique in a module; the ``%`` is dropped).  A
    fusion that carries no ``op_name`` of its own gets its root's, that is
    the one of the fused computation's ``ROOT`` or, where the root is a
    tuple or a bitcast the compiler made, of the nearest instruction behind
    it that has one.  An instruction with none is left out: unscoped."""
    own: Dict[str, str] = {}            # instruction -> its own op_name
    calls: Dict[str, str] = {}          # fusion -> computation it calls
    operands: Dict[str, List[str]] = {}
    roots: Dict[str, str] = {}          # computation -> its ROOT
    comp = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1).lstrip("%")
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2).lstrip("%")
        if m.group(1) and comp is not None:
            roots[comp] = name
        body = line[m.end():]
        op = _OP_NAME.search(body)
        if op:
            own[name] = op.group(1).replace("\\'", "'")
        called = _CALLS.search(body)
        if called:
            calls[name] = called.group(1).lstrip("%")
        operands[name] = [o.lstrip("%") for o in _OPERAND.findall(
            body.split(", metadata=")[0])]

    def behind(name: str) -> Optional[str]:
        """The op_name of ``name`` or of the nearest instruction behind it
        (breadth first over operands, a few hops)."""
        seen, front = {name}, [name]
        for _ in range(4):
            found = next((own[n] for n in front if n in own), None)
            if found is not None:
                return found
            front = list(dict.fromkeys(
                o for n in front for o in operands.get(n, ())
                if o not in seen))
            seen.update(front)
        return None

    scopes = dict(own)
    for name, comp in calls.items():
        if name not in scopes and comp in roots:
            found = behind(roots[comp])
            if found is not None:
                scopes[name] = found
    return scopes


def _split_path(op_name: str) -> List[str]:
    """The path cut at the ``/`` that stand outside parentheses."""
    parts, depth, at = [], 0, 0
    for i, ch in enumerate(op_name + "/"):
        depth += ch == "("
        depth -= ch == ")"
        if ch == "/" and depth == 0:
            parts.append(op_name[at:i])
            at = i + 1
    return parts


def path_parts(op_name: str) -> List[Tuple[Tuple[str, ...], str]]:
    """``jit(f)/transpose(jvp(mla))/dot_general`` -> ``[(("jit",), "f"),
    (("transpose", "jvp"), "mla"), ((), "dot_general")]``: the path cut at
    the ``/`` outside parentheses, each part with its transform wrappers
    stripped."""
    out = []
    for part in _split_path(op_name):
        wrappers = []
        while True:
            m = re.match(r"^(\w+)\((.*)\)$", part)
            if not m:
                break
            wrappers.append(m.group(1))
            part = m.group(2)
        out.append((tuple(wrappers), part))
    return out


def _way(parts) -> str:
    wrappers = {w for ws, _ in parts for w in ws}
    return "backward" if "transpose" in wrappers \
        else "forward" if "jvp" in wrappers else "other"


def direction(op_name: str) -> str:
    """``backward`` where a part of the path is under ``transpose`` (the
    transposed linearisation, rematerialised forward work included),
    ``forward`` where one is under ``jvp`` and none under ``transpose``,
    else ``other``: the update, the exchange, what no gradient passes."""
    return _way(path_parts(op_name))


def in_scope(op_name: str, component: str,
             way: Optional[str] = None) -> bool:
    """Whether ``component`` is one of the path's parts once the wrappers
    are stripped (``jvp(mla)`` and ``transpose(jvp(mla))`` both belong to
    ``mla``) and, where ``way`` is given, the path runs that direction."""
    parts = path_parts(op_name)
    return any(inner == component for _, inner in parts) \
        and (way is None or _way(parts) == way)


def scope_of(scopes: Dict[str, str], event_name: str) -> Optional[str]:
    """The ``op_name`` of a trace event (named by its instruction text)."""
    return scopes.get(instruction_name(event_name).lstrip("%"))


def scope_busy_ns(ops: Iterable[Row], window: Interval,
                  scopes: Dict[str, str], component: str,
                  direction: Optional[str] = None) -> int:
    """Nanoseconds of ``window`` in which an instruction of the named scope
    ran: the union of the intervals, not their sum."""
    members = {name for name, op_name in scopes.items()
               if in_scope(op_name, component, direction)}
    return busy_ns([r for r in ops
                    if instruction_name(r[0]).lstrip("%") in members],
                   window)


def unscoped_share(ops: Sequence[Row], window: Interval,
                   scopes: Dict[str, str]) -> Optional[float]:
    """The share of the busy time in which only instructions that the join
    found no ``op_name`` for ran."""
    busy = busy_ns(ops, window)
    if not busy:
        return None
    scoped = busy_ns([r for r in ops if scope_of(scopes, r[0])], window)
    return 1.0 - scoped / busy


def scope_tail(op_name: str, room: int) -> str:
    """The end of the path in at most ``room`` characters: the leading
    ``jit(...)`` and ``shard_map`` parts dropped (every instruction of the
    program has them), then whole parts between the first, where it
    carries a transform (it says forward from backward), and the last (the
    primitive), ``..`` in their place."""
    parts = _split_path(op_name)
    while len(parts) > 1 and _OUTER.match(parts[0]):
        parts.pop(0)
    tail = "/".join(parts)
    if len(tail) <= room:
        return tail
    head = [parts.pop(0)] if "(" in parts[0] and len(parts) > 1 else []
    while len(parts) > 1 and len("/".join(head + [".."] + parts)) > room:
        parts.pop(0)
    cut = "/".join(head + [".."] + parts)
    # nothing between to drop, or still too long: from the left, so that
    # the direction stays
    return cut if len(cut) <= room else "/".join(head + parts[-1:])[:room]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Length of the intersection of two *merged* interval lists."""
    i = j = acc = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        acc += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def gaps(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that the merged intervals leave free."""
    out, at = [], window[0]
    for s, e in clip(merged, window):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def busy_ns(ops: Iterable[Row], window: Interval) -> int:
    """Nanoseconds of ``window`` in which some instruction ran."""
    return total(union(clip(((s, e) for _, s, e in ops), window)))


def idle_share(ops: Iterable[Row], window: Interval) -> float:
    return 1.0 - busy_ns(ops, window) / (window[1] - window[0])


# ---------------------------------------------------------------------------
# programs and steps
# ---------------------------------------------------------------------------

def program_name(module_event: str) -> str:
    """``jit_per_worker(12109795050817197960)`` -> ``jit_per_worker``."""
    return module_event.split("(", 1)[0]


def train_program(modules: Iterable[Row]) -> Optional[str]:
    """The program that took most device time: the train step."""
    by: Dict[str, int] = {}
    for name, s, e in modules:
        by[program_name(name)] = by.get(program_name(name), 0) + e - s
    return max(by, key=by.get) if by else None


def step_intervals(dev: DeviceTables, window: Interval) -> List[Interval]:
    """Executions of the train program that lie wholly inside ``window``."""
    prog = train_program(dev.modules)
    return [(s, e) for name, s, e in dev.modules
            if program_name(name) == prog
            and s >= window[0] and e <= window[1]]


def ops_within(ops: Sequence[Row], spans: Sequence[Interval]) -> List[Row]:
    """The instructions that started inside one of the (sorted, disjoint)
    ``spans``."""
    starts = [s for s, _ in spans]
    out = []
    for row in ops:
        i = bisect_right(starts, row[1]) - 1
        if i >= 0 and row[1] < spans[i][1]:
            out.append(row)
    return out


def steps_and_ops(tables: Optional[TraceTables],
                  window: Optional[Interval]):
    """Chip 0: the train program's executions wholly inside the traced
    window, and the instructions that ran inside them."""
    if tables is None or window is None or not tables.devices:
        return [], []
    dev = tables.devices[0]
    steps = step_intervals(dev, window)
    return steps, ops_within(dev.ops, steps)


def mean_step_ns(tables: Optional[TraceTables],
                 window: Optional[Interval]) -> Optional[float]:
    """Device nanoseconds per execution of the train program on chip 0,
    over the executions wholly inside the traced window."""
    steps, _ = steps_and_ops(tables, window)
    return total(steps) / len(steps) if steps else None


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def collective_intervals(ops: Iterable[Row]) -> List[Interval]:
    """One interval per collective.  A synchronous collective is its own
    event.  An asynchronous one is a ``-start`` and a ``-done`` event: its
    interval runs from the start's begin to the done's end, the pair matched
    by the start's name among the done's operands, else first-in first-out
    per kind."""
    out: List[Interval] = []
    open_starts: Dict[str, List[Row]] = {}
    for row in sorted(ops, key=lambda r: r[1]):
        op = opcode(row[0])
        base = collective_base(op)
        if base is None:
            continue
        if op.endswith("-start"):
            open_starts.setdefault(base, []).append(row)
        elif op.endswith("-done"):
            pending = open_starts.get(base, [])
            match = next((p for p in pending
                          if instruction_name(p[0]) + ")" in row[0]
                          or instruction_name(p[0]) + "," in row[0]), None)
            if match is None and pending:
                match = pending[0]
            if match is not None:
                pending.remove(match)
                out.append((match[1], row[2]))
            else:                       # the start fell outside the trace
                out.append((row[1], row[2]))
        else:
            out.append((row[1], row[2]))
    for pending in open_starts.values():    # the done fell outside the trace
        out += [(s, e) for _, s, e in pending]
    return out


def collective_ns(ops: Sequence[Row]) -> int:
    """Device time under collectives: the union of their intervals."""
    return total(union(collective_intervals(ops)))


def exposed_collective_ns(ops: Sequence[Row]) -> int:
    """The part of the collectives' intervals during which no other
    instruction ran on this device."""
    coll = union(collective_intervals(ops))
    other = union((s, e) for name, s, e in ops
                  if collective_base(opcode(name)) is None)
    return total(coll) - overlap(coll, other)


# ---------------------------------------------------------------------------
# breakdown
# ---------------------------------------------------------------------------

def top_device_ops(ops: Iterable[Row], window: Interval, n: int = 10,
                   scopes: Optional[Dict[str, str]] = None) -> List[List]:
    """``[[name, seconds], ...]``: the instructions that took most device
    time, grouped by opcode and the instruction's own name; where the join
    knows the instruction, the name ends in the tail of its ``op_name``
    (``fusion %fusion.573 transpose(jvp())/conv_general_dilated``), the
    whole at most ``TAIL_CHARS`` characters."""
    by: Dict[str, int] = {}
    for name, s, e in ops:
        lo, hi = max(s, window[0]), min(e, window[1])
        if hi > lo:
            key = f"{opcode(name)} {instruction_name(name)}"
            by[key] = by.get(key, 0) + hi - lo

    def with_tail(key: str) -> str:
        scope = (scopes or {}).get(key.split(" %", 1)[-1])
        room = TAIL_CHARS - len(key) - 1
        return f"{key} {scope_tail(scope, room)}" if scope and room > 0 \
            else key

    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[with_tail(k), v / 1e9] for k, v in top]


def attribute_gaps(idle: Sequence[Interval], host: Sequence[Row],
                   n: int = 10) -> List[List]:
    """``[[label, seconds], ...]``: idle time on the device by what the host
    was doing.  ``host`` rows are labelled host intervals on the trace's
    clock; where several cover the same instant the shortest (innermost)
    wins, and time that none covers is ``between_calls``."""
    # cut the idle time at every host boundary, label each piece
    cuts = sorted({t for _, s, e in host for t in (s, e)})
    by: Dict[str, int] = {}
    for lo, hi in idle:
        inside = cuts[bisect_right(cuts, lo):bisect_right(cuts, hi - 1)]
        edges = [lo] + inside + [hi]
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            cover = [(e - s, name) for name, s, e in host if s <= mid < e]
            label = min(cover)[1] if cover else "between_calls"
            by[label] = by.get(label, 0) + b - a
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]
