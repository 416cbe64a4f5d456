"""Layer: looped stack.  Device milliseconds per step under the scope
``ut_loop``: the stack of layers, every loop step, forward and backward
with the layer applications the backward pass makes again."""

from benchmarks import scopes


def read(run):
    return scopes.scope_ms_per_step(run, "ut_loop")
