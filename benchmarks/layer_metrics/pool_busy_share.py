"""Layer: input pipeline.  Percent of the traced stretch that the threads
which materialize and stage batches spend doing so: ``input.materialize`` +
``input.device_put`` time inside the stretch over the stretch times the
number of threads seen writing those spans in it.  100% is a pool with no
headroom."""

from benchmarks import spans


def read(run):
    got = spans.rows_in_stretch(run)
    if got is None:
        return None
    s, rows = got
    threads = {r[1] for r in rows if r[0] in spans.POOL_SPANS}
    if not threads:
        return None
    return 100.0 * spans.clipped_ns(rows, spans.POOL_SPANS, s) \
        / ((s[1] - s[0]) * len(threads))
