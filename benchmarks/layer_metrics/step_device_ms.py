"""Layer: step program.  Device milliseconds per step of the train program
(the program with most device time on the ``XLA Modules`` line,
``jit_per_worker``), chip 0, over the executions that lie wholly inside the
traced window.  Applies to every cell."""

from benchmarks import trace


def read(run):
    ns = trace.mean_step_ns(run.tables, run.trace_window)
    return None if ns is None else ns / run.steps_per_call / 1e6
