"""schema-drift: recorder/telemetry phase vocabulary stays in sync.

Absorbs ``scripts/check_schema_drift.py`` (now a deprecation shim that
execs the lint CLI): three consumers must agree on the phase/section
vocabulary with ``telemetry.PHASES`` as the ONE source of truth —
``recorder.SECTIONS``, the ``print_train_info`` record keys
(``t_<section>``), and the telemetry phase-event names.  A bucket added
to one but not the others silently drops that phase from records,
plots, or reports.

Round 12 extends the probe to the device-attribution schema
(docs/design.md §13): ``devprof.feed_telemetry`` must emit exactly the
declared ``device.*`` gauge vocabulary (``devprof.DEVICE_GAUGES``), the
training sentry must emit the ``anomaly`` event with a ``kind`` from
``sentry.ANOMALY_KINDS``, and ``scripts/telemetry_report.py``'s
consumed-event vocabulary (``TRACKED_EVENTS``) must cover every emitter
— so a new emitter can't stream events the report and Perfetto export
silently drop.

Round 19 adds the protocol cross-check (docs/design.md §21): the
center op table the ``analysis/protocol.py`` extraction reads out of
``center_server.py`` must equal the ops a LIVE ``RemoteCenter``
actually sends against a stubbed wire — the static view the
wire-contract/retry-safety checkers rest on is pinned to the runtime
surface, so an extraction rule going stale fails the gate instead of
silently blinding the protocol pass.

PR 25 adds the always-on span ring's vocabulary (``telemetry.SPANS`` /
``telemetry.COUNTS``, docs/design.md §11): every ``telemetry.span`` /
``telemetry.count`` site in the package names a declared literal, and
every declared name has a site (:func:`span_vocabulary_errors`).

Unlike the AST checkers this is a PROJECT-level probe against LIVE
objects (a Recorder driven through one print, a Telemetry instance fed
one bracket per phase, a sentry pushed into an anomaly), so a
hand-rolled record dict drifting from the declared list is caught too.
All probed modules import without jax (``telemetry``/``devprof``/
``sentry`` are stdlib-only by contract, ``recorder`` needs numpy), so
the lint CLI stays backend-free.
"""

from __future__ import annotations

import os
import sys
from typing import List, Optional

from ..core import Checker, Finding, register
# endpoint files have ONE home — the §21 protocol model; re-declaring
# them here let the probe parse one path while anchoring findings to
# another if a module ever moved (review finding, round 19)
from ..protocol import (CENTER_PATH, FLEETMON_PATH, MEMBERSHIP_PATH,
                        TRACING_PATH, WIRE_PATH)

TELEMETRY_PATH = "theanompi_tpu/utils/telemetry.py"
RECORDER_PATH = "theanompi_tpu/utils/recorder.py"
DEVPROF_PATH = "theanompi_tpu/utils/devprof.py"
SENTRY_PATH = "theanompi_tpu/utils/sentry.py"
REPORT_PATH = "scripts/telemetry_report.py"
CHAOS_PATH = "theanompi_tpu/utils/chaos.py"
NUMERICS_PATH = "theanompi_tpu/utils/numerics.py"
# where the always-on ring's spans are written (span_vocabulary_errors)
SPAN_SITE_PATHS = ("theanompi_tpu/models/data/prefetch.py",
                   "theanompi_tpu/models/model_base.py",
                   "theanompi_tpu/parallel/exchanger.py", RECORDER_PATH)

# one lane, one module: a compute span [0,50]us and a comm span [40,60]us
# → compute 50us, comm 20us, exposed 10us, overlap 0.5 — a COMPLETE
# profile, so feed_telemetry must emit every declared gauge
_PROBE_EVENTS = [
    {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 50.0,
     "name": "fusion.1",
     "args": {"hlo_op": "fusion.1", "hlo_module": "jit_step"}},
    {"ph": "X", "pid": 1, "tid": 1, "ts": 40.0, "dur": 20.0,
     "name": "all-reduce.1",
     "args": {"hlo_op": "all-reduce.1", "hlo_module": "jit_step"}},
]


def live_drift_errors(recorder, telemetry) -> List[tuple]:
    """The live-object checks, parameterized on the two modules so tests
    can probe failure modes with monkeypatched stand-ins.  Returns
    ``(path, message)`` pairs; empty = in sync."""
    errors: List[tuple] = []

    # 1. recorder.SECTIONS must BE the canonical list
    if tuple(recorder.SECTIONS) != tuple(telemetry.PHASES):
        errors.append((RECORDER_PATH,
                       f"recorder.SECTIONS {tuple(recorder.SECTIONS)!r} != "
                       f"telemetry.PHASES {tuple(telemetry.PHASES)!r}"))

    # 2. the record keys a live print_train_info actually emits
    r = recorder.Recorder({"verbose": False, "printFreq": 1})
    r.start()
    r.end("train")
    r.train_error(1, 1.0, 0.5, 8)
    rec = r.print_train_info(1)
    if not rec:
        errors.append((RECORDER_PATH,
                       "print_train_info(1) did not fire at printFreq=1"))
    else:
        got = {k for k in rec if k.startswith("t_")}
        want = {"t_" + s for s in telemetry.PHASES if s != "val"}
        if got != want:
            errors.append((RECORDER_PATH,
                           f"print_train_info record keys {sorted(got)} != "
                           f"t_<PHASES except val> {sorted(want)}"))
    if tuple(recorder.RECORD_KEYS) != tuple(
            "t_" + s for s in telemetry.PHASES if s != "val"):
        errors.append((RECORDER_PATH,
                       f"recorder.RECORD_KEYS {tuple(recorder.RECORD_KEYS)!r}"
                       " drifted from telemetry.PHASES"))

    # 3. the phase-event names a live registry emits for each section
    tm = telemetry.Telemetry(rank=0, run_id="drift-check")
    for s in telemetry.PHASES:
        tm.phase(s, 0.0)
    evs = [e for e in tm.tail(len(telemetry.PHASES) + 1)
           if e["ev"] == "phase"]
    got_secs = {e.get("sec") for e in evs}
    if got_secs != set(telemetry.PHASES):
        errors.append((TELEMETRY_PATH,
                       f"telemetry phase-event names {sorted(got_secs)} != "
                       f"PHASES {sorted(telemetry.PHASES)}"))
    got_hists = {k for k in tm.hists if k.startswith("phase.")}
    if got_hists != {"phase." + s for s in telemetry.PHASES}:
        errors.append((TELEMETRY_PATH,
                       f"telemetry phase histograms {sorted(got_hists)} "
                       "drifted from PHASES"))
    return errors


def span_vocabulary_errors(telemetry, root: Optional[str] = None,
                           sources: Optional[dict] = None) -> List[tuple]:
    """The always-on span ring's vocabulary, guarded like ``PHASES``: every
    ``telemetry.span("...")`` / ``telemetry.count("...")`` literal in the
    package must be in ``telemetry.SPANS`` / ``telemetry.COUNTS``, every
    listed name must have a site (``compile.*`` spans are written by the
    ``jax.monitoring`` listener, named in ``COMPILE_EVENTS``), no span is
    called like a recorder phase, and a dotted name's head is a phase,
    ``input`` (the loader's threads) or ``model`` (what a model file counts
    of its own step).  ``sources`` ({path: text}) stands
    in for the package's files in tests."""
    import ast as _ast
    errors: List[tuple] = []
    declared = {"span": set(telemetry.SPANS), "count": set(telemetry.COUNTS)}
    clash = declared["span"] & set(telemetry.PHASES)
    if clash:
        errors.append((TELEMETRY_PATH,
                       f"telemetry.SPANS repeats recorder phases "
                       f"{sorted(clash)}: Recorder.end writes those rows"))
    heads = set(telemetry.PHASES) | {"input", "model"}
    for name in sorted(declared["span"] | declared["count"]):
        if "." in name and name.split(".", 1)[0] not in heads:
            errors.append((TELEMETRY_PATH,
                           f"span/counter {name!r}: the part before the "
                           f"dot is neither a phase nor 'input' nor "
                           f"'model'"))
    given = sources is not None
    if not given:
        if root is None:
            root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))))
        sources = {}
        for dirpath, _dirs, names in os.walk(
                os.path.join(root, "theanompi_tpu")):
            for n in names:
                if n.endswith(".py"):
                    full = os.path.join(dirpath, n)
                    with open(full, encoding="utf-8") as f:
                        sources[os.path.relpath(full, root)] = f.read()
    used = {"span": set(), "count": set()}
    for path, text in sources.items():
        if "telemetry.span(" not in text and "telemetry.count(" not in text:
            continue
        try:
            tree = _ast.parse(text)
        except SyntaxError:
            continue               # the parse step reports it already
        for node in _ast.walk(tree):
            if not (isinstance(node, _ast.Call)
                    and isinstance(node.func, _ast.Attribute)
                    and node.func.attr in used
                    and isinstance(node.func.value, _ast.Name)
                    and node.func.value.id == "telemetry"):
                continue
            arg = node.args[0] if node.args else None
            if not (isinstance(arg, _ast.Constant)
                    and isinstance(arg.value, str)):
                errors.append((path, f"line {node.lineno}: telemetry."
                               f"{node.func.attr}() needs a literal name "
                               f"from the declared vocabulary"))
                continue
            used[node.func.attr].add(arg.value)
            if arg.value not in declared[node.func.attr]:
                errors.append((path, f"line {node.lineno}: telemetry."
                               f"{node.func.attr}({arg.value!r}) is not in "
                               f"telemetry.{node.func.attr.upper()}S"))
    used["span"] |= set(telemetry.COMPILE_EVENTS.values())
    if not (given or all(p in sources for p in SPAN_SITE_PATHS)):
        return errors      # a partial tree (precommit_lint.sh): the sites'
                           # files are not in it, so none can be missing
    for kind in ("span", "count"):
        for name in sorted(declared[kind] - used[kind]):
            errors.append((TELEMETRY_PATH,
                           f"telemetry.{kind.upper()}S lists {name!r} but "
                           f"no telemetry.{kind}({name!r}) site exists"))
    return errors


def device_schema_errors(devprof, sentry, telemetry,
                         telemetry_report=None) -> List[tuple]:
    """The round-12 device-attribution probes, parameterized on the live
    modules.  ``telemetry_report`` may be None (script not in the linted
    tree — e.g. a restricted pre-commit checkout); its cross-checks are
    then skipped."""
    errors: List[tuple] = []

    # 1. feed_telemetry emits EXACTLY the declared device.* gauge set
    prof = devprof.attribute(_PROBE_EVENTS)
    tm = telemetry.Telemetry(rank=0, run_id="drift-check")
    devprof.feed_telemetry(prof, tm)
    if set(tm.gauges) != set(devprof.DEVICE_GAUGES):
        errors.append((DEVPROF_PATH,
                       f"feed_telemetry gauges {sorted(tm.gauges)} != "
                       f"DEVICE_GAUGES {sorted(devprof.DEVICE_GAUGES)}"))
    prof_evs = [e for e in tm.tail(4) if e["ev"] == devprof.PROFILE_EVENT]
    if not prof_evs:
        errors.append((DEVPROF_PATH,
                       f"feed_telemetry emitted no "
                       f"{devprof.PROFILE_EVENT!r} event"))
    if any(not g.startswith("device.") for g in devprof.DEVICE_GAUGES):
        errors.append((DEVPROF_PATH,
                       "DEVICE_GAUGES contains a non-'device.' name"))

    # 2. the sentry's anomaly event: a live instance pushed into a NaN
    # must emit ANOMALY_EVENT with a declared kind and an iter field
    tm2 = telemetry.Telemetry(rank=0, run_id="drift-check")
    s = sentry.TrainingSentry({"verbose": False, "sentry_min_records": 2},
                              telemetry=tm2)
    for i in range(3):
        s.observe_record({"iter": i, "cost": 1.0, "images_per_sec": 100.0})
    kind = s.observe_record({"iter": 3, "cost": float("nan"),
                             "images_per_sec": 100.0})
    anoms = [e for e in tm2.tail(8) if e["ev"] == sentry.ANOMALY_EVENT]
    if kind != "nan_loss" or not anoms:
        errors.append((SENTRY_PATH,
                       "a NaN cost did not raise a live "
                       f"{sentry.ANOMALY_EVENT!r} event (got kind "
                       f"{kind!r})"))
    else:
        ev = anoms[-1]
        if ev.get("kind") not in sentry.ANOMALY_KINDS:
            errors.append((SENTRY_PATH,
                           f"anomaly kind {ev.get('kind')!r} not in "
                           f"ANOMALY_KINDS {sentry.ANOMALY_KINDS}"))
        if "iter" not in ev:
            errors.append((SENTRY_PATH,
                           "anomaly event carries no 'iter' field"))

    # 3. the report/Perfetto converter consumes every emitter's vocabulary
    if telemetry_report is not None:
        tracked = set(getattr(telemetry_report, "TRACKED_EVENTS", ()))
        want = {"phase", "train_record", "gauges",
                sentry.ANOMALY_EVENT, devprof.PROFILE_EVENT}
        missing = sorted(want - tracked)
        if missing:
            errors.append((REPORT_PATH,
                           f"TRACKED_EVENTS is missing emitter event "
                           f"kind(s) {missing} — the report/trace export "
                           "would silently drop them"))
    return errors


def membership_schema_errors(membership, chaos, telemetry,
                             telemetry_report=None) -> List[tuple]:
    """Round-13 probes: the elastic-membership event vocabulary.  A LIVE
    controller driven through join → demote → leave must emit exactly the
    declared :data:`MEMBERSHIP_EVENTS` kinds (each tagged with the worker
    id), a live ``WorkerLease.beat`` must stream its declared heartbeat
    gauges, and the report/trace converter must consume all of it —
    otherwise the chaos gate's leave/join matching silently sees nothing.
    ``membership``/``chaos`` are the live modules (file-path loaded in the
    jax-free lint CLI); either may be None in a partial tree."""
    errors: List[tuple] = []
    if membership is not None:
        tm = telemetry.Telemetry(rank=0, run_id="drift-check")
        ctl = membership.MembershipController(telemetry_=tm)
        ctl.join(7, pid=123)
        ctl.demote(7)            # refused: would empty the active set
        ctl.join(8, pid=124)
        ctl.demote(7)
        ctl.leave(8, reason="probe")
        evs = [e for e in tm.tail(8) if e["ev"] != "run_start"]
        got = {e["ev"] for e in evs}
        if got != set(membership.MEMBERSHIP_EVENTS):
            errors.append((MEMBERSHIP_PATH,
                           f"a live controller's join/demote/leave emitted "
                           f"{sorted(got)} != MEMBERSHIP_EVENTS "
                           f"{sorted(membership.MEMBERSHIP_EVENTS)}"))
        if any("worker" not in e for e in evs):
            errors.append((MEMBERSHIP_PATH,
                           "a membership event carries no 'worker' field"))
        # heartbeat gauges: one live beat streams the declared keys
        import tempfile
        tm2 = telemetry.Telemetry(rank=0, run_id="drift-check")
        with tempfile.TemporaryDirectory() as d:
            lease = membership.WorkerLease(d, 0, telemetry_=tm2)
            lease.beat(5)
        beats = [e for e in tm2.tail(4) if e["ev"] == "gauges"]
        want_g = set(membership.HEARTBEAT_GAUGES)
        if not beats or not want_g <= set(beats[-1]):
            errors.append((MEMBERSHIP_PATH,
                           f"WorkerLease.beat streamed no gauges event "
                           f"carrying HEARTBEAT_GAUGES {sorted(want_g)}"))
        if set(tm2.gauges) != want_g:
            errors.append((MEMBERSHIP_PATH,
                           f"WorkerLease.beat gauges {sorted(tm2.gauges)} "
                           f"!= HEARTBEAT_GAUGES {sorted(want_g)}"))
    if telemetry_report is not None:
        tracked = set(getattr(telemetry_report, "TRACKED_EVENTS", ()))
        want = set(getattr(membership, "MEMBERSHIP_EVENTS", ())) if \
            membership is not None else set()
        if chaos is not None:
            want.add(chaos.FAULT_EVENT)
        missing = sorted(want - tracked)
        if missing:
            errors.append((REPORT_PATH,
                           f"TRACKED_EVENTS is missing membership/chaos "
                           f"event kind(s) {missing} — the chaos gate's "
                           "leave/join matching would silently drop them"))
        counters = set(getattr(telemetry_report, "TRACE_COUNTER_KEYS", ()))
        hb = set(getattr(membership, "HEARTBEAT_GAUGES", ())) if \
            membership is not None else set()
        if hb and not hb <= counters:
            errors.append((REPORT_PATH,
                           f"TRACE_COUNTER_KEYS is missing heartbeat "
                           f"gauge(s) {sorted(hb - counters)} — the "
                           "Perfetto export would not render liveness"))
    return errors


def wire_schema_errors(wire, membership, telemetry,
                       telemetry_report=None) -> List[tuple]:
    """Round-14 probes: the resilient-RPC telemetry vocabulary.  A LIVE
    wire client driven into a dead address must tick its declared
    counters and emit the declared ``wire`` give-up event; a live dedup
    window replaying a token must tick ``wire.dedup_hit``; a crafted
    version-mismatch frame must fail loudly with BOTH versions in the
    message; the controller's center-outage pair must emit exactly
    :data:`CENTER_EVENTS`; and the report/trace converter must consume
    all of it.  ``wire``/``membership`` are file-path-loaded live modules
    (jax-free); either may be None in a partial tree."""
    errors: List[tuple] = []
    if wire is None:
        return errors

    # 0. declared names are wire-namespaced (report renders by prefix)
    for name in (wire.WIRE_COUNTERS + wire.WIRE_HISTS + wire.WIRE_GAUGES):
        if not name.startswith("wire."):
            errors.append((WIRE_PATH,
                           f"declared wire metric {name!r} is outside the "
                           f"'wire.' namespace"))

    # 1. a live client against a dead address: retries, then a loud
    # give-up — declared counters tick, the declared event kind streams
    if membership is not None:
        tm = telemetry.Telemetry(rank=0, run_id="drift-check")
        client = wire.WireClient(
            "127.0.0.1:9", client_id="drift", op_timeout_s=0.2,
            connect_timeout_s=0.2, max_retries=1, deadline_s=1.0,
            backoff=membership.Backoff(base=0.01, cap=0.02),
            telemetry_=tm)
        gave_up = False
        try:
            client.request({"op": "stats"})
        except ConnectionError:
            gave_up = True
        if not gave_up:
            errors.append((WIRE_PATH,
                           "a WireClient against a dead address did not "
                           "raise WireGiveUp"))
        if tm.counters.get("wire.giveup", 0) < 1 or \
                tm.counters.get("wire.retry", 0) < 1:
            errors.append((WIRE_PATH,
                           f"give-up path ticked {sorted(tm.counters)} — "
                           f"expected wire.retry and wire.giveup counts"))
        evs = [e for e in tm.tail(8) if e["ev"] == wire.WIRE_EVENT]
        if not evs or evs[-1].get("kind") != "giveup":
            errors.append((WIRE_PATH,
                           f"give-up emitted no {wire.WIRE_EVENT!r} event "
                           f"with kind='giveup'"))

    # 2. dedup window: a replayed token must be a hit that ticks the
    # declared counter and does NOT read as fresh
    tm2 = telemetry.Telemetry(rank=0, run_id="drift-check")
    win = wire.DedupWindow(telemetry_=tm2)
    tok = {"w": "drift", "seq": 0}
    dup, _ = win.check(tok, "push")
    win.record(tok, "push", {"ok": True})
    dup2, _ = win.check(tok, "push")
    if dup or not dup2 or win.hits != 1 or \
            tm2.counters.get("wire.dedup_hit", 0) != 1:
        errors.append((WIRE_PATH,
                       "DedupWindow replay did not register exactly one "
                       f"wire.dedup_hit (fresh={dup}, dup={dup2}, "
                       f"hits={win.hits})"))

    # 3. version mismatch fails LOUDLY with both versions in the message
    import socket as _socket
    a, b = _socket.socketpair()
    try:
        a.sendall(wire.encode_frame({"ok": True, "v": 999999}))
        try:
            wire.recv_msg(b)
            errors.append((WIRE_PATH,
                           "a version-mismatched frame did not raise"))
        except wire.VersionMismatch as e:
            msg = str(e)
            if "999999" not in msg or str(wire.WIRE_VERSION) not in msg:
                errors.append((WIRE_PATH,
                               f"VersionMismatch message lacks both "
                               f"versions: {msg!r}"))
    finally:
        a.close()
        b.close()

    # 4. the center-outage pair: a live controller must emit exactly
    # CENTER_EVENTS, and the report must consume them + the wire schema
    if membership is not None:
        tm3 = telemetry.Telemetry(rank=0, run_id="drift-check")
        ctl = membership.MembershipController(telemetry_=tm3)
        ctl.center_down(reason="probe")
        ctl.center_restored(attempt=1)
        got = {e["ev"] for e in tm3.tail(4) if e["ev"] != "run_start"}
        if got != set(membership.CENTER_EVENTS):
            errors.append((MEMBERSHIP_PATH,
                           f"a live controller's center outage pair "
                           f"emitted {sorted(got)} != CENTER_EVENTS "
                           f"{sorted(membership.CENTER_EVENTS)}"))
    if telemetry_report is not None:
        tracked = set(getattr(telemetry_report, "TRACKED_EVENTS", ()))
        want = {wire.WIRE_EVENT}
        if membership is not None:
            want |= set(getattr(membership, "CENTER_EVENTS", ()))
        missing = sorted(want - tracked)
        if missing:
            errors.append((REPORT_PATH,
                           f"TRACKED_EVENTS is missing wire/center event "
                           f"kind(s) {missing} — the chaos gate's "
                           "center_down→center_restored matching and the "
                           "wire outage markers would be dropped"))
        counters = set(getattr(telemetry_report, "TRACE_COUNTER_KEYS", ()))
        missing_g = sorted(set(wire.WIRE_GAUGES) - counters)
        if missing_g:
            errors.append((REPORT_PATH,
                           f"TRACE_COUNTER_KEYS is missing wire gauge(s) "
                           f"{missing_g} — the Perfetto export would not "
                           "render outage durations"))
    return errors


def tracing_schema_errors(tracing, telemetry,
                          telemetry_report=None) -> List[tuple]:
    """Round-16 probes: the causal-tracing span/statusz vocabulary
    (docs/design.md §17).  LIVE checks, all jax-free:

    * a Tracer driven through a round must emit a ``span`` event carrying
      every declared :data:`SPAN_FIELDS` key;
    * the three span emitters (round via ``Tracer``, ``emit_wire_span``,
      ``emit_server_span``) fed into the REPORT's trace assembly must
      produce one joined round whose critical-path components sum to the
      round time, with a dedup twin counted but never joined — a span
      emitter the report cannot render fails the gate here;
    * a live :class:`StatuszServer` must answer a real socket ``health``
      query with every declared :data:`STATUSZ_FIELDS` key and register/
      deregister its discovery doc;
    * the report must track ``span``/``statusz`` and agree on the
      component vocabulary."""
    errors: List[tuple] = []
    if tracing is None:
        return errors

    # 1. a live round span carries the declared field set
    tm = telemetry.Telemetry(rank=0, run_id="drift-check")
    tr = tracing.Tracer(telemetry_=tm)
    rnd = tr.begin("round", island=0)
    ctx = rnd.ctx()
    rnd.end(outcome="exchanged")
    spans = [e for e in tm.tail(4) if e["ev"] == tracing.SPAN_EVENT]
    if not spans:
        errors.append((TRACING_PATH,
                       "a live Tracer round emitted no "
                       f"{tracing.SPAN_EVENT!r} event"))
    else:
        missing = [k for k in tracing.SPAN_FIELDS
                   if k not in spans[-1] and k != "parent"]
        if missing:                      # parent is None → omitted is fine
            errors.append((TRACING_PATH,
                           f"round span event lacks declared SPAN_FIELDS "
                           f"{missing}: {sorted(spans[-1])}"))
        if tr.spans != 1:
            errors.append((TRACING_PATH,
                           f"Tracer.spans counted {tr.spans} after one "
                           "emitted span"))

    # 2. the full client+server emitter set must assemble into ONE joined
    # round in the live report — with the dedup twin tagged, counted, and
    # never double-counted on the critical path
    import time as _time
    tm2 = telemetry.Telemetry(rank=1, run_id="drift-check")
    tr2 = tracing.Tracer(telemetry_=tm2)
    rnd2 = tr2.begin("round", island=1)
    wire_ctx = rnd2.ctx()
    sid = tracing.new_span_id()
    tracing.emit_wire_span(tm2, wire_ctx, "push", span=sid,
                           t0=rnd2.t0, dt=0.01, q=0.002, a=0.003)
    srv_ctx = {"t": rnd2.trace, "s": sid}
    tracing.emit_server_span(tm2, srv_ctx, "push", t0=rnd2.t0, dt=0.006,
                             q=0.002, a=0.003, island=1)
    tracing.emit_server_span(tm2, srv_ctx, "push", t0=rnd2.t0, dt=0.0001,
                             island=1, dedup=True)
    _time.sleep(0.015)          # round dt must cover its wire op's 10ms
    rnd2.end(outcome="exchanged")
    if telemetry_report is not None:
        assemble = getattr(telemetry_report, "assemble_traces", None)
        if assemble is None:
            errors.append((REPORT_PATH,
                           "telemetry_report has no assemble_traces — "
                           "span events would be emitted but never "
                           "joined/rendered"))
        else:
            traces = assemble(tm2.tail(8))
            if len(traces) != 1:
                errors.append((REPORT_PATH,
                               f"trace assembly built {len(traces)} "
                               "round(s) from one emitted round"))
            else:
                t = traces[0]
                if t["joined"] != 1 or t["dedup_twins"] != 1:
                    errors.append((REPORT_PATH,
                                   f"client span did not join exactly one "
                                   f"applied server span with one dedup "
                                   f"twin (joined={t['joined']}, "
                                   f"twins={t['dedup_twins']})"))
                total = sum(t["components"].values())
                if abs(total - t["dt"]) > max(0.05 * t["dt"], 1e-6):
                    errors.append((REPORT_PATH,
                                   f"critical-path components sum "
                                   f"{total:.6f} != round dt "
                                   f"{t['dt']:.6f}"))
                if set(t["components"]) != set(tracing.COMPONENTS):
                    errors.append((REPORT_PATH,
                                   f"component vocabulary "
                                   f"{sorted(t['components'])} != "
                                   f"tracing.COMPONENTS "
                                   f"{sorted(tracing.COMPONENTS)}"))
        comps = getattr(telemetry_report, "TRACE_COMPONENTS", ())
        if tuple(comps) != tuple(tracing.COMPONENTS):
            errors.append((REPORT_PATH,
                           f"TRACE_COMPONENTS {tuple(comps)!r} != "
                           f"tracing.COMPONENTS "
                           f"{tuple(tracing.COMPONENTS)!r}"))
        tracked = set(getattr(telemetry_report, "TRACKED_EVENTS", ()))
        missing = sorted({tracing.SPAN_EVENT,
                          tracing.STATUSZ_EVENT} - tracked)
        if missing:
            errors.append((REPORT_PATH,
                           f"TRACKED_EVENTS is missing tracing event "
                           f"kind(s) {missing} — spans/statusz would be "
                           "silently dropped from report and trace"))

    # 3. a live statusz endpoint answers with the declared field set and
    # registers/deregisters its discovery doc
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        tm3 = telemetry.Telemetry(rank=0, run_id="drift-check")
        sz = tracing.StatuszServer("probe", ident=0, run_dir=d,
                                   telemetry_=tm3, tracer_=tr)
        try:
            host, port = sz.start()
            docs = tracing.read_statusz_docs(d)
            if len(docs) != 1 or docs[0].get("port") != port:
                errors.append((TRACING_PATH,
                               f"statusz discovery doc missing/wrong "
                               f"under {d}: {docs}"))
            rep = tracing.statusz_query(f"{host}:{port}", "health")
            missing = [k for k in tracing.STATUSZ_FIELDS if k not in rep]
            if missing:
                errors.append((TRACING_PATH,
                               f"statusz health reply lacks declared "
                               f"STATUSZ_FIELDS {missing}: "
                               f"{sorted(rep)}"))
            evs = tracing.statusz_query(f"{host}:{port}", "events", n=4)
            if not evs.get("ok") or "events" not in evs:
                errors.append((TRACING_PATH,
                               "statusz events op returned no event "
                               "list"))
            sz_evs = [e for e in tm3.tail(4)
                      if e["ev"] == tracing.STATUSZ_EVENT]
            if not sz_evs or "addr" not in sz_evs[-1]:
                errors.append((TRACING_PATH,
                               f"statusz start emitted no "
                               f"{tracing.STATUSZ_EVENT!r} event with an "
                               f"addr"))
        except Exception as e:
            errors.append((TRACING_PATH,
                           f"live statusz probe failed: {e!r}"))
        finally:
            sz.stop()
        if tracing.read_statusz_docs(d):
            errors.append((TRACING_PATH,
                           "statusz stop() left its discovery doc "
                           "behind — fleetz would list a ghost"))
    return errors


def fleetmon_schema_errors(fleetmon, membership, telemetry,
                           telemetry_report=None) -> List[tuple]:
    """Round-18 probes: the fleet-health vocabulary (docs/design.md
    §20).  LIVE checks, all jax-free:

    * the stock rule sets pass their own grammar validator, and every
      rule name :data:`FAULT_ALERT_COVERAGE` promises the alert-audit
      exists in the full stock set — a renamed rule would silently
      vacate the audit;
    * a live collector fed a breaching sample fires EXACTLY ONE
      ``alert`` event carrying rule/series/worker/value/threshold, and
      does NOT re-fire while the breach persists (the no-flapping
      episode contract IS schema);
    * a demote-actioned alert driven through :func:`fleetmon.apply_alert`
      lands a ``worker_demote`` event CITING the firing rule by name,
      and that name exists in the rule set;
    * the text exposition covers every registered fleet series;
    * the report tracks the ``alert`` event kind."""
    errors: List[tuple] = []
    if fleetmon is None:
        return errors

    # 1. rule grammar: the stock sets must validate, and the audit's
    # coverage map must name real rules (the FULL set — step_time rules
    # are opt-in by threshold)
    try:
        fleetmon.validate_rules(fleetmon.DEFAULT_RULES)
        full = fleetmon.validate_rules(fleetmon.default_rules(
            step_p99_s=1.0, hbm_headroom_bytes=1.0, divergence=1.0))
    except ValueError as e:
        errors.append((FLEETMON_PATH,
                       f"the stock rule set fails its own validator: {e}"))
        full = []
    full_names = {r["name"] for r in full}
    for kind, names in fleetmon.FAULT_ALERT_COVERAGE.items():
        missing = sorted(set(names) - full_names)
        if missing:
            errors.append((FLEETMON_PATH,
                           f"FAULT_ALERT_COVERAGE[{kind!r}] names rule(s) "
                           f"{missing} absent from default_rules(...) — "
                           "the alert-audit for that fault kind is "
                           "vacuously uncovered"))

    # 2. a live breach fires exactly one schema-complete alert event,
    # and holds (no flapping) while the breach persists
    tm = telemetry.Telemetry(rank=0, run_id="drift-check")
    rules = [{"name": "probe_rule", "series": "step_p99",
              "predicate": "threshold", "op": ">", "value": 1.0,
              "scope": "rank"}]
    col = fleetmon.FleetCollector(rules=rules, telemetry_=tm)
    col.ingest({"step_p99": 5.0}, rank=3)
    first = col.evaluate()
    col.ingest({"step_p99": 6.0}, rank=3)
    second = col.evaluate()
    evs = [e for e in tm.tail(8) if e["ev"] == fleetmon.ALERT_EVENT]
    if len(first) != 1 or len(evs) != 1:
        errors.append((FLEETMON_PATH,
                       f"one breaching sample fired {len(first)} alert(s) "
                       f"/ {len(evs)} event(s) — expected exactly 1"))
    elif second:
        errors.append((FLEETMON_PATH,
                       "a persisting breach RE-fired on the next "
                       "evaluation — the no-flapping episode contract "
                       "is broken"))
    else:
        ev = evs[-1]
        missing = [k for k in ("rule", "series", "worker", "value",
                               "threshold") if k not in ev]
        if missing:
            errors.append((FLEETMON_PATH,
                           f"alert event lacks field(s) {missing}: "
                           f"{sorted(ev)}"))

    # 3. an alert-driven demotion cites a real rule name in the
    # worker_demote event (the §20 closed loop)
    if membership is not None:
        tm2 = telemetry.Telemetry(rank=0, run_id="drift-check")
        ctl = membership.MembershipController(telemetry_=tm2)
        ctl.join(1, pid=1)
        ctl.join(2, pid=2)
        alert = {"rule": "probe_rule", "series": "step_p99",
                 "rank": 1, "value": 5.0, "threshold": 1.0,
                 "action": "demote"}
        if not fleetmon.apply_alert(ctl, alert):
            errors.append((FLEETMON_PATH,
                           "apply_alert did not demote a live worker"))
        else:
            demotes = [e for e in tm2.tail(8)
                       if e["ev"] == "worker_demote"]
            if not demotes or demotes[-1].get("rule") != "probe_rule":
                errors.append((FLEETMON_PATH,
                               f"alert-driven worker_demote does not "
                               f"cite the firing rule: "
                               f"{demotes[-1] if demotes else None}"))
            elif demotes[-1]["rule"] not in {r["name"] for r in
                                             col.rules}:
                errors.append((FLEETMON_PATH,
                               f"demote cites rule "
                               f"{demotes[-1]['rule']!r} that exists in "
                               "no active rule set"))

    # 4. the exposition covers every registered fleet series
    col2 = fleetmon.FleetCollector(rules=[], telemetry_=telemetry.DISABLED)
    col2.ingest({k: 1.0 for k in fleetmon.METRIC_FIELDS}, rank=0)
    text = col2.expose_text()
    missing = [s for s in fleetmon.FLEET_SERIES
               if ("theanompi_" + s) not in text]
    if missing:
        errors.append((FLEETMON_PATH,
                       f"expose_text() omits registered fleet series "
                       f"{missing} — a scrape would silently miss them"))

    # 5. the report consumes the alert vocabulary
    if telemetry_report is not None:
        tracked = set(getattr(telemetry_report, "TRACKED_EVENTS", ()))
        missing = sorted(set(fleetmon.ALERT_EVENTS) - tracked)
        if missing:
            errors.append((REPORT_PATH,
                           f"TRACKED_EVENTS is missing fleet-health "
                           f"event kind(s) {missing} — alerts would be "
                           "dropped from report and Perfetto export"))
    return errors


def numerics_schema_errors(numerics, sentry, fleetmon, telemetry,
                           telemetry_report=None) -> List[tuple]:
    """Round-25 probes: the numerics health plane (docs/design.md §25).
    LIVE, jax-free (the host-plane half of ``utils/numerics`` is
    stdlib-only by contract):

    * the sentry kinds the plane raises are declared anomaly kinds;
    * a live ``record(example_report())`` emits EVERY declared
      ``NUMERICS_GAUGES`` gauge, every ``NUMERICS_HISTOGRAMS``
      distribution, and exactly one ``NUMERICS_EVENT`` event;
    * a live sentry fed an overflowing report raises ``grad_overflow``
      through the real anomaly event path;
    * fleetmon's snapshot schema carries the beacon series the
      ``replica_divergence`` rule reads;
    * the report/trace converter consumes the event kind and renders
      the divergence/grad-norm counter tracks."""
    errors: List[tuple] = []
    if numerics is None:
        return errors

    if sentry is not None:
        missing = sorted(set(numerics.SENTRY_KINDS) -
                         set(sentry.ANOMALY_KINDS))
        if missing:
            errors.append((NUMERICS_PATH,
                           f"numerics SENTRY_KINDS {missing} absent from "
                           f"sentry.ANOMALY_KINDS — the detectors would "
                           "raise undeclared anomalies"))

    # a live record() must cover the whole declared gauge/event surface
    tm = telemetry.Telemetry(rank=0, run_id="drift-check")
    rep = numerics.example_report()
    numerics.record(tm, rep, rank=0)
    missing = sorted(set(numerics.NUMERICS_GAUGES) - set(tm.gauges))
    if missing:
        errors.append((NUMERICS_PATH,
                       f"record(example_report()) never set declared "
                       f"gauge(s) {missing}"))
    missing = sorted(set(numerics.NUMERICS_HISTOGRAMS) - set(tm.hists))
    if missing:
        errors.append((NUMERICS_PATH,
                       f"record(example_report()) never observed declared "
                       f"histogram(s) {missing}"))
    evs = [e for e in tm.tail(4) if e["ev"] == numerics.NUMERICS_EVENT]
    if len(evs) != 1:
        errors.append((NUMERICS_PATH,
                       f"record(example_report()) emitted {len(evs)} "
                       f"{numerics.NUMERICS_EVENT!r} event(s) — "
                       "expected exactly 1"))

    # a live sentry fed an overflow raises through the real event path
    if sentry is not None:
        tm2 = telemetry.Telemetry(rank=0, run_id="drift-check")
        s = sentry.TrainingSentry({"verbose": False}, telemetry=tm2)
        bad = dict(numerics.example_report())
        bad["nonfinite"] = 4.0
        kind = s.observe_numerics(bad)
        anoms = [e for e in tm2.tail(4)
                 if e["ev"] == sentry.ANOMALY_EVENT]
        if kind != "grad_overflow" or not anoms:
            errors.append((SENTRY_PATH,
                           "an overflowing numerics report did not raise "
                           f"a live grad_overflow anomaly (got {kind!r})"))

    # fleetmon's snapshot schema must carry the beacon series
    if fleetmon is not None:
        missing = sorted({"grad_norm", "divergence"} -
                         set(fleetmon.METRIC_FIELDS))
        if missing:
            errors.append((FLEETMON_PATH,
                           f"METRIC_FIELDS is missing numerics series "
                           f"{missing} — the replica_divergence rule "
                           "would read an unregistered series"))

    # the report consumes the event + renders the counter tracks
    if telemetry_report is not None:
        tracked = set(getattr(telemetry_report, "TRACKED_EVENTS", ()))
        if numerics.NUMERICS_EVENT not in tracked:
            errors.append((REPORT_PATH,
                           f"TRACKED_EVENTS is missing "
                           f"{numerics.NUMERICS_EVENT!r} — numerics "
                           "reports would be dropped from the report"))
        counters = set(getattr(telemetry_report,
                               "TRACE_COUNTER_KEYS", ()))
        missing = sorted({"numerics.grad_norm", "numerics.divergence"} -
                         counters)
        if missing:
            errors.append((REPORT_PATH,
                           f"TRACE_COUNTER_KEYS is missing numerics "
                           f"key(s) {missing} — the Perfetto export "
                           "would drop the counter tracks"))
    return errors


def thread_role_coverage_errors(root: Optional[str] = None) -> List[tuple]:
    """Round-15 probe: the host-concurrency pass is only as good as its
    thread-role map, so every ``threading.Thread(...)``/``Timer(...)``
    construction in the thread-heaviest runtime modules
    (``membership.py``, ``chaos.py``) must (a) appear among
    ``engine.spawn_sites()`` and (b) RESOLVE to its entry function — a
    spawn whose target the engine cannot resolve silently escapes the
    shared-state-race/daemon-discipline analysis.  Built live on a mini
    ProgramIndex over just those files, so a new spawn idiom the
    resolver does not understand fails the gate the day it lands."""
    import ast as _ast

    from ..core import SourceFile
    from ..engine import ProgramIndex
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    files = []
    for rel in (MEMBERSHIP_PATH, CHAOS_PATH):
        full = os.path.join(root, rel)
        if os.path.exists(full):
            try:
                files.append(SourceFile(root, rel))
            except SyntaxError:
                continue           # the parse step reports it already
    if not files:
        return []
    index = ProgramIndex(files)
    sites = {}
    for s in index.spawn_sites():
        if s.kind in ("thread", "timer"):
            sites[(s.path, s.line)] = s
    errors: List[tuple] = []
    for sf in files:
        for node in _ast.walk(sf.tree):
            if not isinstance(node, _ast.Call):
                continue
            resolved = sf.resolver.resolve(node.func)
            if resolved not in ("threading.Thread", "threading.Timer"):
                continue
            site = sites.get((sf.path, node.lineno))
            if site is None:
                errors.append((sf.path,
                               f"thread spawn at line {node.lineno} is "
                               f"invisible to the thread-role map "
                               f"(engine.spawn_sites) — the "
                               f"host-concurrency pass cannot analyze "
                               f"it"))
            elif not site.entries:
                errors.append((sf.path,
                               f"thread spawn at line {node.lineno} "
                               f"(target `{site.target_desc}`) does not "
                               f"resolve to an entry function — its "
                               f"thread role is empty and its body "
                               f"escapes the race analysis"))
    return errors


def center_protocol_errors(center_server, root: Optional[str] = None
                           ) -> List[tuple]:
    """Round-19 probe: the §21 protocol model cross-checked against the
    RUNTIME client surface.  The op table statically extracted from the
    center dispatch ladder must be exactly (a) the op set the static
    client table sees RemoteCenter sending AND (b) the ops a LIVE
    RemoteCenter actually puts on the wire when every public op method
    is driven against a stubbed wire — so neither the extraction rules
    nor the client can drift from the runtime surface unnoticed.  The
    wire stub captures each request header and aborts before any
    network or jax work (``_leaves`` is stubbed too), keeping the probe
    socket-free and backend-free."""
    from .. import protocol
    from ..core import SourceFile
    from ..engine import ProgramIndex
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    if not os.path.exists(os.path.join(root, protocol.CENTER_PATH)):
        return []
    try:
        sf = SourceFile(root, protocol.CENTER_PATH)
    except (SyntaxError, OSError):
        return []               # the parse step reports it already
    index = ProgramIndex([sf])
    spec = next(s for s in protocol.ENDPOINTS if s.name == "center")
    table = protocol.server_op_table(index, spec)
    errors: List[tuple] = []
    if table is None:
        errors.append((CENTER_PATH,
                       "the §21 center op table could not be extracted "
                       "(dispatch function missing?) — the protocol "
                       "checkers are blind to this endpoint"))
        return errors
    static_server = set(table)
    static_client = set(protocol.client_op_table(index, spec))

    class _Captured(Exception):
        pass

    sent: set = set()

    class _WireStub:
        def request(self, header, body=b"", trace=None):
            sent.add(header.get("op"))
            raise _Captured()

        def close(self):
            pass

    rc = center_server.RemoteCenter("127.0.0.1:9")
    try:
        rc._wire.close()
    except OSError:
        pass
    rc._wire = _WireStub()
    rc._leaves = lambda tree: ([], None)    # instance stub: no jax flatten
    surface = (("ensure_init", (None,)), ("pull", ()),
               ("pull_leaves", ()), ("push_delta", (None, 0)),
               ("push_pull", (None, 0)), ("demote_island", (0,)),
               ("readmit_island", (0,)), ("stats", ()))
    for method, args in surface:
        try:
            getattr(rc, method)(*args)
        except _Captured:
            continue
        except Exception as e:
            errors.append((CENTER_PATH,
                           f"RemoteCenter.{method} failed before "
                           f"reaching the wire ({e!r}) — the runtime "
                           "surface probe cannot see its op"))
    if sent != static_server:
        errors.append((CENTER_PATH,
                       f"a live RemoteCenter sends ops {sorted(sent)} "
                       f"!= the extracted center dispatch table "
                       f"{sorted(static_server)} — static protocol "
                       "view drifted from the runtime surface (or this "
                       "probe's own hardcoded `surface` method list in "
                       "center_protocol_errors is stale: extend it "
                       "when adding an op)"))
    if static_client != static_server:
        errors.append((CENTER_PATH,
                       f"the static client op table "
                       f"{sorted(static_client)} != the extracted "
                       f"dispatch table {sorted(static_server)} — the "
                       "wire-contract checker should have caught this; "
                       "its extraction rules drifted"))
    return errors


def _load_parallel(name: str):
    """A ``theanompi_tpu.parallel`` submodule imported WITHOUT executing
    the jax-importing package ``__init__``: when the real package is not
    already loaded, a synthetic parent (the scripts/lint.py bootstrap
    pattern) is registered so the submodule's relative imports
    (``from . import wire``) resolve jax-free.  None when absent or
    broken (the probe skips its cross-checks)."""
    import importlib
    import importlib.machinery
    import types
    full = f"theanompi_tpu.parallel.{name}"
    if full in sys.modules:
        return sys.modules[full]
    if "theanompi_tpu.parallel" not in sys.modules:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        pkg_dir = os.path.join(root, "theanompi_tpu", "parallel")
        if not os.path.isdir(pkg_dir):
            return None
        pkg = types.ModuleType("theanompi_tpu.parallel")
        pkg.__path__ = [pkg_dir]
        spec = importlib.machinery.ModuleSpec(
            "theanompi_tpu.parallel", loader=None, is_package=True)
        spec.submodule_search_locations = [pkg_dir]
        pkg.__spec__ = spec
        sys.modules["theanompi_tpu.parallel"] = pkg
    try:
        return importlib.import_module(full)
    except Exception:
        return None


def _load_by_path(relpath: str, name: str):
    """A probed module loaded by FILE path — for modules that are not
    importable in the lint CLI's jax-free process through the synthetic
    package (scripts are not package modules; ``parallel/__init__``
    imports jax, so ``parallel/membership.py`` — itself stdlib-only at
    module scope by contract — loads this way too).  None when absent or
    broken (the parse step flags a syntax error as a normal finding; the
    probe just skips its cross-checks)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    path = os.path.join(root, relpath)
    if not os.path.exists(path):
        return None
    import importlib.util
    try:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    except Exception:
        return None
    return mod


def _load_telemetry_report():
    return _load_by_path(os.path.join("scripts", "telemetry_report.py"),
                         "_tpulint_telemetry_report")


@register
class SchemaDriftChecker(Checker):
    name = "schema-drift"
    description = ("recorder.SECTIONS / print_train_info record keys / "
                   "telemetry phase events must derive from telemetry."
                   "PHASES; device.* gauges and the sentry anomaly "
                   "schema must match their declared vocabularies "
                   "(live-object probe)")
    reads_files = False    # `--only schema-drift` skips the repo parse
    # every file the live probes load beyond the lint selection — the
    # runner folds these into partial runs' cache keys (core.Checker)
    disk_scoped = (RECORDER_PATH, TELEMETRY_PATH, DEVPROF_PATH,
                   SENTRY_PATH, REPORT_PATH, MEMBERSHIP_PATH,
                   CHAOS_PATH, WIRE_PATH, TRACING_PATH, FLEETMON_PATH,
                   CENTER_PATH, NUMERICS_PATH)

    def check_project(self, files):
        # normal import both under pytest (real package loaded) and under
        # the lint CLI (scripts/lint.py registers a synthetic
        # `theanompi_tpu` parent whose __path__ skips the jax-importing
        # package __init__)
        from theanompi_tpu.utils import recorder, telemetry
        errors = live_drift_errors(recorder, telemetry)
        # round 25 (PR 25): the always-on span ring's vocabulary against
        # every telemetry.span/count site in the package
        errors += span_vocabulary_errors(telemetry)
        try:
            # absent from a partial tree (precommit_lint.sh lints staged
            # blobs — a restricted checkout may omit them): the device
            # probes are skipped, the phase probes above still ran
            from theanompi_tpu.utils import devprof, sentry
        except ImportError:
            devprof = sentry = None
        report = _load_telemetry_report()
        if devprof is not None and sentry is not None:
            errors += device_schema_errors(devprof, sentry, telemetry,
                                           report)
        # membership/chaos by file path: parallel/__init__ imports jax,
        # which the lint CLI's no-backend contract forbids
        membership = _load_by_path(
            os.path.join("theanompi_tpu", "parallel", "membership.py"),
            "_tpulint_membership")
        chaos = _load_by_path(
            os.path.join("theanompi_tpu", "utils", "chaos.py"),
            "_tpulint_chaos")
        errors += membership_schema_errors(membership, chaos, telemetry,
                                           report)
        # round 14: the resilient-RPC wire layer (stdlib+numpy at module
        # scope by contract — file-path loads jax-free like membership)
        wire = _load_by_path(
            os.path.join("theanompi_tpu", "parallel", "wire.py"),
            "_tpulint_wire")
        errors += wire_schema_errors(wire, membership, telemetry, report)
        # round 16: the causal-tracing span/statusz vocabulary — live
        # emitters joined through the live report, statusz on a real
        # socket (utils/tracing is stdlib-only by contract, importable
        # through the synthetic package like telemetry)
        try:
            from theanompi_tpu.utils import tracing as tracing_mod
        except ImportError:
            tracing_mod = None
        errors += tracing_schema_errors(tracing_mod, telemetry, report)
        # round 18: the fleet-health plane — rule grammar, alert event
        # schema + no-flapping, rule-cited demotions, exposition
        # coverage (utils/fleetmon is stdlib-only by contract,
        # importable through the synthetic package like telemetry)
        try:
            from theanompi_tpu.utils import fleetmon as fleetmon_mod
        except ImportError:
            fleetmon_mod = None
        errors += fleetmon_schema_errors(fleetmon_mod, membership,
                                         telemetry, report)
        # round 25: the numerics health plane — sentry-kind vocabulary,
        # live record() gauge/event coverage, live grad_overflow raise,
        # beacon series in the fleetmon snapshot schema, report/trace
        # consumption (utils/numerics keeps jax out of module scope by
        # contract, importable through the synthetic package)
        try:
            from theanompi_tpu.utils import numerics as numerics_mod
        except ImportError:
            numerics_mod = None
        errors += numerics_schema_errors(numerics_mod, sentry,
                                         fleetmon_mod, telemetry, report)
        # round 19: the §21 protocol model cross-checked live — the
        # extracted center op table must equal the ops a real
        # RemoteCenter sends (static view vs runtime surface; the
        # parallel package parent is synthesized so the submodule
        # imports jax-free)
        center_server = _load_parallel("center_server")
        if center_server is not None:
            errors += center_protocol_errors(center_server)
        # round 15: the thread-role map must see and resolve every
        # Thread/Timer spawn in the thread-heaviest runtime modules
        errors += thread_role_coverage_errors()
        return [Finding(self.name, path, 1, 0, msg)
                for path, msg in errors]
