"""Layer: expert layer.  The least time the chip could take for the held
experts' grouped products (the configuration's counter under ``flops/``,
``routed_experts_train_flops_per_sample``: three products an application,
forward and backward, at the **expected** number of routed (token, expert)
pairs under uniform routing; compute-bound, FLOPs / bf16 peak) as a
percentage of the grouped-product kernels' own device time
(``ragged_dot_ms``: the kernels by name, not a scope).  The time holds the
products the backward pass makes again and the rows of a stretch that are
no pair; the FLOPs do not.  At the routed cell's 320 rows an expert a
forward product moves 76 MB for 16.1 GFLOP, 0.093 ms at 819 GB/s against
0.082 ms at the peak: a product is as much the memory's as the units', and
the share cannot reach 100 there."""

from benchmarks import harness
from benchmarks.layer_metrics import ragged_dot_ms


def read(run):
    ms = ragged_dot_ms.read(run)
    if ms is None or run.peaks is None:
        return None
    count = getattr(harness.load_module(harness.load_manifest(), "flops",
                                        run.cell.config["flops"]),
                    "routed_experts_train_flops_per_sample", None)
    if count is None:
        return None
    flops = count(run.cell.config) * run.global_batch / run.cell.chips
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / (ms / 1e3)
