"""Set-up the program and the benchmark do before the clock starts: the
imports, model and data, compile or cache load, the reference check, the
warm-up steps.  It is the time from process start to the start of the clock
less the phases in ``harness.OUTSIDE_SETUP`` (the chip's runtime coming up)."""


def read(run):
    return run.setup_s
