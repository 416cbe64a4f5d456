"""Layer: worker loop.  Host milliseconds per step inside the call of the
step program alone (``train.call``: ``train_fn(...)`` in ``train_iter``),
over the traced stretch.  ``host_dispatch_ms`` less this and
``small_programs_ms`` is the bracket's own bookkeeping.  Where it approaches
the step's period the runtime holds the caller back in this call."""

from benchmarks import spans


def read(run):
    return spans.per_step_ms(run, ("train.call",))
