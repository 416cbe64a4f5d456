"""Layer: linear attention.  Device milliseconds per step under the scope
``kda``: the Kimi Delta Attention operators of every ``kda`` layer (the
projections, the convolutions, the decays and gates, the chunked delta
rule, the output norm), forward and backward with what the backward pass
makes again.  The divisor is the device's: the scope's time inside the
sub-window of whole periods (``phases.whole_periods``) over the steps it
holds."""

from benchmarks.layer_metrics.short_conv_ms import scope_ms_per_period


def read(run):
    return scope_ms_per_period(run, "kda")
