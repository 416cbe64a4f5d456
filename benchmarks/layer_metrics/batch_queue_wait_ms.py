"""Layer: input pipeline.  How long a finished batch waited for the
consumer: per batch dequeued in the traced stretch, the start of its
``load.dequeue`` less the end of its ``input.device_put`` (the two joined on
the batch id the queue item carries), floored at 0; the mean, in
milliseconds.  Of the order of the queue's depth times the step's period
where the loader runs ahead; near 0 where the consumer is fed just in time
or starves."""

from benchmarks import spans


def read(run):
    got = spans.rows_in_stretch(run)
    if got is None:
        return None
    s, rows = got
    takes = [r for r in spans.dequeued(rows, s) if r[5] is not None]
    if not takes:
        return None
    # a batch taken in the stretch may have been staged before it began
    put_end = {}
    for r in spans.ring().spans(t1_ns=s[1]):
        if r[0] == "input.device_put" and r[5] is not None:
            put_end[r[5]] = max(put_end.get(r[5], 0), r[3])
    waits = [max(0, r[2] - put_end[r[5]]) for r in takes if r[5] in put_end]
    return sum(waits) / len(waits) / 1e6 if waits else None
