"""Layer: worker loop.  The recorder's ``train`` bucket per step over the
traced stretch: host time inside the dispatch bracket of ``train_iter`` (the
step program and the four small programs around it).  It is not device time,
and it is not only enqueue cost: the runtime makes the host wait in this
bracket once its queue is full, so in a cell the device or the transfers
bound it approaches the step's period (my chip runs, PR 23).  Applies to
every cell."""


def read(run):
    s = run.traced
    if s is None or not s.steps:
        return None
    return 1e3 * s.buckets["train"] / s.steps
