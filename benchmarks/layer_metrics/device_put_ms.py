"""Layer: input pipeline.  ``input.device_put`` alone, per batch dequeued
in the traced stretch: the host call that hands a batch to the runtime.  It
returns before PJRT's threads have transposed and transferred the batch, so
it is not the transfer (``unready_dequeue_share`` says whether that was done
in time)."""

from benchmarks import spans


def read(run):
    return spans.per_batch_ms(run, ("input.device_put",))
