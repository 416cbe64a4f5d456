"""Layer: expert layer.  Device milliseconds per step in the held experts'
grouped products themselves, forward and backward with what the backward
pass makes again: the kernels the compiler makes of ``lax.ragged_dot`` and
``lax.ragged_dot_general``, taken by their instruction name
(``ragged-dot...``), wherever they run.  The compiler writes such a
kernel's ``op_name`` as ``ragged-dot-none``, the path dropped, so no reader
of a scope holds one: ``moe_ms`` and the phases count a product only where
a ``while`` around it carries it, and ``expert_roofline_share``'s scope
``experts`` has never held one (PERF.md section 7).  The divisor is the
device's, as the phases': the kernels' time inside the sub-window of whole
periods (``phases.whole_periods``) over the steps it holds."""

from benchmarks import phases, trace

KERNEL = "ragged-dot"


def read(run):
    t, window = run.tables, run.trace_window
    if t is None or window is None or not t.devices:
        return None
    dev = t.devices[0]
    held = phases.whole_periods(dev.modules, window)
    if held is None:
        return None
    sub, periods = held
    ns = trace.busy_ns(
        [row for row in dev.ops
         if trace.instruction_name(row[0]).lstrip("%").startswith(KERNEL)],
        sub)
    steps = periods * max(1, int(run.steps_per_call))
    return ns / steps / 1e6 if ns else None
