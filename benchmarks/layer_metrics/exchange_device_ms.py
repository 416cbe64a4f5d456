"""Layer: exchange.  Device milliseconds per step under collectives
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all;
an asynchronous one counts from its ``-start`` to its ``-done``), chip 0,
inside the train program's executions in the traced window.  A trace with no
collective (one chip) gives nothing."""

from benchmarks import trace


def read(run):
    steps, ops = trace.steps_and_ops(run.tables, run.trace_window)
    if not steps or not trace.collective_intervals(ops):
        return None
    return trace.collective_ns(ops) / len(steps) / run.steps_per_call / 1e6
