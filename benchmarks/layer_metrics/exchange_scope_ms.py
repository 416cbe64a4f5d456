"""Layer: exchange.  Device milliseconds per step in the phase ``exchange``
(``benchmarks/phases.py``): chip 0's time whose innermost running instruction
is under the scope ``exchange``, the span the program writes around its wire
(the strategy's call, ``sync_bn``, the gathers of ``_dot_gathered``),
whatever opcode the compiler gives it; ``exchange_device_ms`` beside it reads
opcodes.  One chip, or a program without the scope, gives nothing."""

from benchmarks import phases


def read(run):
    return phases.phase_ms(run, "exchange")
