"""Layer: latent attention.  Device milliseconds per step under the scope
``mla``: the latent attention operators of every ``mla`` layer (the
projections, the latent's norm and expansion, the splash kernel's core),
forward and backward with what the backward pass makes again, over the
device's own count of steps (``short_conv_ms.scope_ms_per_period``)."""

from benchmarks.layer_metrics.short_conv_ms import scope_ms_per_period


def read(run):
    return scope_ms_per_period(run, "mla")
