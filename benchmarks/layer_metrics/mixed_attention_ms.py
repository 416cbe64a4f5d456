"""Layer: mixed attention.  Device milliseconds per step under the scope
``attn``: projections, rotary turns, the core (full-attention and window
layers alike), the per-head gate and the output projection of every
layer, forward and backward with what the backward pass makes again."""

from benchmarks import scopes


def read(run):
    return scopes.scope_ms_per_step(run, "attn")
