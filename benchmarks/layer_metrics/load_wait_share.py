"""Layer: input pipeline.  Percent of the traced stretch that the consumer
spent in the recorder's ``load`` (blocked on the producer's queue) and
``stage`` (staging itself) brackets.  Applies to every cell; large where the
host sets the pace."""


def read(run):
    s = run.traced
    if s is None or not s.seconds:
        return None
    return 100.0 * (s.buckets["load"] + s.buckets["stage"]) / s.seconds
