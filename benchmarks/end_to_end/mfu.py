"""Model FLOP/s utilisation, in percent: the FLOPs forward and backward
require per sample (the configuration's own counter under ``flops/``) x
samples/s/chip / the chip's bf16 peak (``peaks.json``).  It is
``train_throughput`` in the currency that compares one model with another."""


def read(run):
    w = run.window
    if not w.steps or not w.seconds or run.peaks is None:
        return None
    per_chip = w.steps * run.global_batch / w.seconds / run.cell.chips
    return 100.0 * run.flops_per_sample * per_chip \
        / run.peaks["bf16_flops_per_s"]
