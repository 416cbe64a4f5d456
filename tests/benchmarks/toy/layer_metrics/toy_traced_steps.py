"""Layer: worker loop.  Steps issued inside the traced stretch: a metric
added by files alone, to show that one can be."""


def read(run):
    return None if run.traced is None else float(run.traced.steps)
