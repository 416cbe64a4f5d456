"""A step's device time by phase: forward, backward, recompute, exchange,
update.  Every instant of chip 0's traced stretch goes to **one** phase, that
of the innermost instruction running, so the phases sum to the busy time and
no instant is counted twice.

The phase is read from the instruction's ``op_name`` (``run.scopes``, the
join of ``trace.scopes_from_hlo``), in this order of precedence:

``exchange``   a path part ``exchange``: the scope the program writes around
               its wire (``BSP_Exchanger.step_update``, ``sync_bn``, the
               gathers of ``layers._dot_gathered``, ``fsdp.gather_params``),
               whatever opcode the compiler gives the instruction;
``update``     a path part ``update``: the optimizer's work
               (``Exchanger._update``, ``postprocess_update``, the boxing
               of the new state that roots its fusions);
``recompute``  a path part ``rematted_computation``, which jax writes in the
               transposition of a ``jax.checkpoint`` and nowhere else: the
               forward work the backward pass makes again;
``backward``   a part under ``transpose``;
``forward``    a part under ``jvp``;
``other``      none of these, or an instruction the join does not know: it
               takes the phase of the instruction it runs wholly inside (a
               copy in a ``while``'s body, a ``ragged-dot`` the compiler
               named without its path) and stays ``other`` at the top level.

"Innermost": among the ``XLA Ops`` events open at an instant, the one that
started last.  A ``while`` so gives its time to its body's instructions and
keeps what lies between them; an asynchronous ``-start`` ... ``-done`` pair
is two events, and what runs between them keeps its own phase.

The divisor is the device's, not the host's: the sub-window from the first to
the last *start* of the train program's events on ``XLA Modules`` inside the
stretch holds n - 1 whole periods, whatever those events' ends say (PERF.md
section 7 (j): the ends can be early, the starts are right), and the phase
time inside that sub-window is divided by n - 1.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, Iterable, List, Optional, Tuple

from benchmarks import trace

PHASES = ("forward", "backward", "recompute", "exchange", "update", "other")


def phase_of(op_name: Optional[str]) -> str:
    """The phase of an instruction by its ``op_name``; ``other`` for None."""
    if not op_name:
        return "other"
    inner = {part for _, part in trace.path_parts(op_name)}
    for scope in ("exchange", "update"):
        if scope in inner:
            return scope
    if "rematted_computation" in inner:
        return "recompute"
    return trace.direction(op_name)


def innermost_ns(rows: Iterable[Tuple[str, int, int]],
                 window: trace.Interval,
                 nameless: Optional[str] = None) -> Dict[str, int]:
    """``{key: nanoseconds}`` over ``(key, start, end)`` rows: every instant
    of ``window`` in which some row is open goes to the open row that
    started last (of two that start together, the shorter: the child).  A
    row whose key is ``nameless`` takes the key of the row it runs wholly
    inside, where there is one."""
    lo, hi = window
    acc: Dict[str, int] = {}
    stack: List[Tuple[int, str]] = []       # (end, key), in order of start

    def run_to(now: int, t: int) -> int:
        """Give the time from ``now`` to ``t`` to the rows on top of the
        stack, dropping those that end on the way."""
        while stack and now < t:
            end, key = stack[-1]
            if end > now:
                upto = min(end, t)
                acc[key] = acc.get(key, 0) + upto - now
                now = upto
            if end <= t:
                stack.pop()
        return t

    at = lo
    for key, s, e in sorted(rows, key=lambda r: (r[1], -r[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        at = run_to(at, s)
        if key == nameless:
            while stack and stack[-1][0] <= s:      # ended as this starts
                stack.pop()
            if stack and stack[-1][0] >= e:
                key = stack[-1][1]
        stack.append((e, key))
    run_to(at, hi)
    return acc


def phase_ns(ops: Iterable[trace.Row], window: trace.Interval,
             scopes: Dict[str, str]) -> Dict[str, int]:
    """Chip time of ``window`` by phase, every phase present: a partition of
    ``trace.busy_ns(ops, window)``."""
    known: Dict[str, str] = {}              # event text -> phase

    def phase(text: str) -> str:
        if text not in known:
            known[text] = phase_of(scopes.get(
                trace.instruction_name(text).lstrip("%")))
        return known[text]

    got = innermost_ns(((phase(name), s, e) for name, s, e in ops), window,
                       nameless="other")
    return {p: got.get(p, 0) for p in PHASES}


def whole_periods(modules: Iterable[trace.Row], window: trace.Interval
                  ) -> Optional[Tuple[trace.Interval, int]]:
    """``((first start, last start), n - 1)`` of the train program's n events
    on ``XLA Modules`` that start inside ``window``: a sub-window of n - 1
    whole periods.  None where n < 2."""
    modules = list(modules)
    prog = trace.train_program(modules)
    starts = sorted(s for name, s, _ in modules
                    if trace.program_name(name) == prog
                    and window[0] <= s < window[1])
    if len(starts) < 2:
        return None
    return (starts[0], starts[-1]), len(starts) - 1


def phases_ms_per_step(run) -> Optional[Dict[str, float]]:
    """``{phase: ms a step}`` on chip 0, ``period`` among them (the
    sub-window over its n - 1 periods).  One sweep a run, kept on the run.
    None where there is no trace, no join, or fewer than two executions."""
    if hasattr(run, "_phases_ms"):
        return run._phases_ms
    run._phases_ms = out = None
    t, window = run.tables, run.trace_window
    if t is None or window is None or not t.devices or not run.scopes:
        return None
    t0 = time.time()
    dev = t.devices[0]
    held = whole_periods(dev.modules, window)
    if held is not None:
        sub, periods = held
        steps = periods * max(1, int(run.steps_per_call))
        run._phases_ms = out = {
            p: ns / steps / 1e6
            for p, ns in phase_ns(dev.ops, sub, run.scopes).items()}
        out["period"] = (sub[1] - sub[0]) / steps / 1e6
    # after the window, in no metric: what the sweep cost, and the whole
    # partition (`other` and the period with it) for whoever reads the log
    run.after_window_s["phases"] = time.time() - t0
    print(f"benchmarks: phases over {len(dev.ops)} events, ms a step: "
          f"{json.dumps(out)}", file=sys.stderr)
    return out


def phase_ms(run, phase: str) -> Optional[float]:
    """Milliseconds a step in ``phase``; None where nothing can be read, or
    where the program has no instruction of that phase (the parent of the
    PR that brought a scope has none under it)."""
    got = phases_ms_per_step(run)
    return got[phase] if got and got[phase] > 0 else None
