"""Layer: step program.  Device milliseconds per step in the phase
``recompute`` (``benchmarks/phases.py``): chip 0's time whose innermost
running instruction has the path part ``rematted_computation``, the forward
work a ``jax.checkpoint`` makes again inside the backward pass, which ``mfu``
does not count.  A program without ``jax.checkpoint`` gives nothing."""

from benchmarks import phases


def read(run):
    return phases.phase_ms(run, "recompute")
