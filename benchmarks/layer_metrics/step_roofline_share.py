"""Layer: step program.  The least time the chip could take for one step's
required FLOPs (compute-bound: FLOPs / bf16 peak; at these batch sizes the
parameter and activation bytes over 819 GB/s come to less) as a percentage
of the step's device time (``step_device_ms``).  Applies to every cell."""

from benchmarks import trace


def read(run):
    ns = trace.mean_step_ns(run.tables, run.trace_window)
    if ns is None or run.peaks is None:
        return None
    flops = run.flops_per_sample * run.global_batch / run.cell.chips \
        * run.steps_per_call
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / (ns / 1e9)
