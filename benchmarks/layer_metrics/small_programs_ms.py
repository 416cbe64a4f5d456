"""Layer: worker loop.  Host milliseconds per step in the four small
programs around the step program: ``train.args`` (the ``jnp.float32`` and
``jnp.int32`` conversions of the learning rate and the count) and
``train.reduce`` (the two ``jnp.mean`` of cost and error), over the traced
stretch.  What a step call that took and returned host scalars would
save."""

from benchmarks import spans


def read(run):
    return spans.per_step_ms(run, ("train.args", "train.reduce"))
