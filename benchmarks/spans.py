"""What the span readers under ``layer_metrics/`` share: the program's
always-on span ring (``theanompi_tpu.utils.telemetry``: ``spans()``,
``totals()``, rows ``(name, thread, t0_ns, t1_ns, parent, batch)`` on the
Unix clock) over the traced stretch of a ``--trace 1`` run.

A program without the ring (a commit from before it) has nothing to read:
:func:`ring` is then None, every reader returns None and the line leaves the
metric out.  The stretch's bounds on the Unix clock are the trace's
``profile_start_time`` plus the traced window on the trace's clock
(``run.tables.start_unix_ns + run.trace_window``), so a run that was not
traced has none either.
"""

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks import trace

POOL_SPANS = ("input.materialize", "input.device_put")


def ring():
    """The program's ``telemetry`` module if it has the ring, else None."""
    try:
        from theanompi_tpu.utils import telemetry
    except ImportError:
        return None
    has = all(hasattr(telemetry, f) for f in ("spans", "totals"))
    return telemetry if has else None


def stretch(run) -> Optional[Tuple[int, int]]:
    """The traced stretch as Unix nanoseconds, or None."""
    t, w = run.tables, run.trace_window
    if t is None or w is None or t.start_unix_ns is None:
        return None
    return t.start_unix_ns + w[0], t.start_unix_ns + w[1]


def rows_in_stretch(run):
    """``(stretch, rows overlapping it)`` or None where there is no ring or
    no stretch."""
    tm, s = ring(), stretch(run)
    if tm is None or s is None:
        return None
    return s, tm.spans(*s)


def clipped_ns(rows: Iterable[tuple], names: Sequence[str],
               window: Tuple[int, int]) -> int:
    """Nanoseconds the rows called one of ``names`` spend inside
    ``window`` (summed over threads, not a union)."""
    lo, hi = window
    return sum(max(0, min(r[3], hi) - max(r[2], lo))
               for r in rows if r[0] in names)


def per_step_ms(run, names: Sequence[str]) -> Optional[float]:
    """Host milliseconds per step under spans called ``names`` over the
    traced stretch (steps: the harness's own count)."""
    got = rows_in_stretch(run)
    if got is None or run.traced is None or not run.traced.steps:
        return None
    s, rows = got
    if not any(r[0] in names for r in rows):
        return None
    return clipped_ns(rows, names, s) / run.traced.steps / 1e6


def dequeued(rows: Iterable[tuple], window: Tuple[int, int]) -> List[tuple]:
    """The ``load.dequeue`` rows that ended inside ``window``: the batches
    the consumer took in it."""
    return [r for r in rows if r[0] == "load.dequeue"
            and window[0] <= r[3] <= window[1]]


def per_batch_ms(run, names: Sequence[str]) -> Optional[float]:
    """Milliseconds of spans called ``names`` (any thread) inside the
    stretch per batch dequeued in it."""
    got = rows_in_stretch(run)
    if got is None:
        return None
    s, rows = got
    n = len(dequeued(rows, s))
    if not n or not any(r[0] in names for r in rows):
        return None
    return clipped_ns(rows, names, s) / n / 1e6


def intervals_on_trace_clock(run, rows: Iterable[tuple],
                             name: str) -> List[trace.Interval]:
    start = run.tables.start_unix_ns
    return [(r[2] - start, r[3] - start) for r in rows if r[0] == name]


def totals() -> Optional[Dict[str, tuple]]:
    tm = ring()
    return None if tm is None else tm.totals()
