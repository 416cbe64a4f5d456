"""Layer: optimizer.  Device milliseconds per step in the phase ``update``
(``benchmarks/phases.py``): chip 0's time whose innermost running instruction
is under the scope ``update`` that the step program writes around gradient
clipping, ``opt.update`` and ``postprocess_update``.  A program without the
scope gives nothing."""

from benchmarks import phases


def read(run):
    return phases.phase_ms(run, "update")
