"""Layer: expert layer.  Device milliseconds per step under the scope
``moe``: router, sort, gather, the held experts' grouped products,
weighted scatter-add and the shared expert of every routed layer, forward
and backward with what the backward pass makes again."""

from benchmarks import scopes


def read(run):
    return scopes.scope_ms_per_step(run, "moe")
