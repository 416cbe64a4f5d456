"""Layer: compile.  Seconds inside XLA's backend compile before the traced
stretch began: the ring's running total of ``compile.xla`` (one span per
``/jax/core/compile/backend_compile_duration`` event, which jax wraps
around ``compile_or_get_cached``, so a load from the persistent cache counts
like a compile; ``compile.cache_load`` is nested in it and not added) less
whatever of it ended after the stretch began.  Every program of the run,
small ones included.  Beside ``first_step_s`` it says how much of the first
step is the compiler's."""

from benchmarks import spans


def read(run):
    got, totals = spans.rows_in_stretch(run), spans.totals()
    if got is None or "compile.xla" not in totals:
        return None
    (lo, _), _ = got
    late = sum(r[3] - r[2] for r in spans.ring().spans(t0_ns=lo)
               if r[0] == "compile.xla" and r[3] > lo)
    return (totals["compile.xla"][1] - late) / 1e9
