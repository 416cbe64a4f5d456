"""Layer: linear attention.  The least time the chip could take for the
chunked delta rule of every ``kda`` layer, the larger of its required
FLOPs over the bf16 peak and its required bytes over the HBM bandwidth
(the configuration's counter under ``flops/``:
``kda_core_train_flops_per_sample``, ``kda_core_train_bytes_per_sample``),
as a percentage of the device time under the scope ``kda_core``, whatever
computes it, over the device's own count of steps
(``short_conv_ms.scope_ms_per_period``).  The time holds the forward pass
the backward makes again; the work does not."""

from benchmarks import harness
from benchmarks.layer_metrics.short_conv_ms import scope_ms_per_period


def read(run):
    ms = scope_ms_per_period(run, "kda_core")
    counter = harness.load_module(harness.load_manifest(), "flops",
                                  run.cell.config["flops"])
    flops = getattr(counter, "kda_core_train_flops_per_sample", None)
    moved = getattr(counter, "kda_core_train_bytes_per_sample", None)
    if ms is None or flops is None or moved is None or run.peaks is None:
        return None
    per_step = run.global_batch / run.cell.chips
    least_s = max(
        flops(run.cell.config) * per_step / run.peaks["bf16_flops_per_s"],
        moved(run.cell.config) * per_step / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
