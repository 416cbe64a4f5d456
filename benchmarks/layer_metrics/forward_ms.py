"""Layer: step program.  Device milliseconds per step in the phase
``forward`` (``benchmarks/phases.py``): chip 0's time whose innermost
running instruction is under ``jvp`` and no ``transpose``, the loss's forward
pass.  The exchange, the update and what the backward pass makes again are
not in it.  Applies to every cell."""

from benchmarks import phases


def read(run):
    return phases.phase_ms(run, "forward")
