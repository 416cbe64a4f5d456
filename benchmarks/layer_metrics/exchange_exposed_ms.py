"""Layer: exchange.  The part of ``exchange_device_ms`` during which no
other instruction ran on the chip: what overlap does not hide."""

from benchmarks import trace


def read(run):
    steps, ops = trace.steps_and_ops(run.tables, run.trace_window)
    if not steps or not trace.collective_intervals(ops):
        return None
    return trace.exposed_collective_ns(ops) / len(steps) \
        / run.steps_per_call / 1e6
