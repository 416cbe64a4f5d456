"""Layer: input pipeline.  Percent of the batches the consumer took whose
device buffers the runtime had not finished transposing and transferring
(``jax.Array.is_ready()`` false at the dequeue, a non-blocking look): the
counter ``input.unready_dequeues`` over ``input.dequeues``.  The counters
are the ring's running totals, so the five warm-up steps and the lead-in are
in them; read in a traced run only, like every per-layer metric."""

from benchmarks import spans


def read(run):
    totals = spans.totals()
    if totals is None or run.traced is None \
            or not totals.get("input.dequeues", (0, 0))[0]:
        return None
    return 100.0 * totals.get("input.unready_dequeues", (0, 0))[0] \
        / totals["input.dequeues"][0]
