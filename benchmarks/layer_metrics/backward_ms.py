"""Layer: step program.  Device milliseconds per step in the phase
``backward`` (``benchmarks/phases.py``): chip 0's time whose innermost
running instruction is under ``transpose``, the transposed linearisation
alone.  What the backward pass recomputes (``recompute_ms``) and the gathers
it waits for (``exchange_scope_ms``) are taken out.  Applies to every cell."""

from benchmarks import phases


def read(run):
    return phases.phase_ms(run, "backward")
