"""Layer: exit head.  Device milliseconds per step under the scope
``exit_head``: the whole-vocabulary head at every loop step, the exit gate,
the exit distribution and the objective, forward and backward."""

from benchmarks import scopes


def read(run):
    return scopes.scope_ms_per_step(run, "exit_head")
