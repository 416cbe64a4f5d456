"""Layer: input pipeline.  Host milliseconds the loader's threads spend
making one batch: ``input.materialize`` (crop, mirror, the native augment
to float32) plus ``input.device_put`` (the host call that hands the batch
to the runtime) inside the traced stretch, summed over threads, per batch
dequeued in it.  Divided by the pool's size it is the pace the pool can
sustain."""

from benchmarks import spans


def read(run):
    return spans.per_batch_ms(run, spans.POOL_SPANS)
