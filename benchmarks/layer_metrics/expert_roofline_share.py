"""Layer: expert layer.  The least time the chip could take for the held
experts' grouped products (the configuration's counter under ``flops/``,
``routed_experts_train_flops_per_sample``: three products an application,
forward and backward, at the **expected** number of routed (token, expert)
pairs under uniform routing; the cell's traffic gave 2,309 to 2,741 pairs a
layer against the expected 2,560 and 10,242 and 10,256 a step against
10,240 at the initial parameters of two seeds, PERF.md section 5;
compute-bound, FLOPs / bf16 peak) as a percentage of the
device time under the scope ``experts``, whatever implements the products.
The time holds the products the backward pass makes again; the FLOPs do
not."""

from benchmarks import harness, scopes


def read(run):
    ms = scopes.scope_ms_per_step(run, "experts")
    count = getattr(harness.load_module(harness.load_manifest(), "flops",
                                        run.cell.config["flops"]),
                    "routed_experts_train_flops_per_sample", None)
    if ms is None or count is None or run.peaks is None:
        return None
    flops = count(run.cell.config) * run.global_batch / run.cell.chips
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / (ms / 1e3)
