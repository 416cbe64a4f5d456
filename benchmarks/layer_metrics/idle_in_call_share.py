"""Layer: device.  Percent of the traced stretch in which chip 0 runs no
instruction *and* the main thread is inside ``train.call``, the span mapped
onto the trace's clock through ``profile_start_time``: the part of
``device_idle_share`` in which the step call itself is held back (by the
runtime waiting for the batch's transfer, say), as opposed to the small
programs, the dequeue or the print."""

from benchmarks import spans, trace


def read(run):
    got = spans.rows_in_stretch(run)
    t, w = run.tables, run.trace_window
    if got is None or not t.devices:
        return None
    _, rows = got
    calls = trace.union(spans.intervals_on_trace_clock(run, rows,
                                                       "train.call"))
    if not calls:
        return None
    busy = trace.union((s, e) for _, s, e in t.devices[0].ops)
    idle = trace.gaps(busy, w)
    return 100.0 * trace.overlap(idle, trace.clip(calls, w)) / (w[1] - w[0])
