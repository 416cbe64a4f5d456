"""Layer: looped stack.  The least time the chip could take for the
attention core's required FLOPs (the causal half of the scores and weighted
sums, forward and backward, every layer application: the configuration's
counter under ``flops/``; compute-bound, FLOPs / bf16 peak) as a percentage
of the device time under the scope ``attn_core``, whatever computes it.
The time holds the forward pass the backward makes again; the FLOPs do
not."""

from benchmarks import harness, scopes


def read(run):
    ms = scopes.scope_ms_per_step(run, "attn_core")
    count = getattr(harness.load_module(harness.load_manifest(), "flops",
                                        run.cell.config["flops"]),
                    "attn_core_train_flops_per_sample", None)
    if ms is None or count is None or run.peaks is None:
        return None
    flops = count(run.cell.config) * run.global_batch / run.cell.chips
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / (ms / 1e3)
