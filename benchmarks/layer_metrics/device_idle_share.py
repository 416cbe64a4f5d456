"""Layer: device.  Percent of the traced window in which no instruction ran
on chip 0: 1 - union of the ``XLA Ops`` intervals / window."""

from benchmarks import trace


def read(run):
    t, w = run.tables, run.trace_window
    if t is None or w is None or not t.devices:
        return None
    return 100.0 * trace.idle_share(t.devices[0].ops, w)
