#!/usr/bin/env python
"""Elastic resume: train on N workers, stop, resume on M — a capability
the reference could not offer (`mpirun -np N` was fixed for a job's life;
its checkpoints were per-rank).

Checkpoints are worker-count portable for the BSP / ZeRO-1 / FSDP
layouts: BSP grads-mode state dedups to one replica; ZeRO-1 optimizer
chunks and FSDP parameter chunks re-partition on load (the chunk layout
is recorded in the checkpoint meta).  Per-worker exchange-strategy state
(onebit/topk/powersgd error-feedback buffers, async diverged replicas)
has NO refit path — resuming such a run on a different worker count
raises a targeted error from ``load()`` (round-4 review).  This
script trains 1 epoch on 8 workers with FSDP + adam, checkpoints,
rebuilds on 4 workers, resumes, and shows the val accuracy carrying
over.
"""

import os
import shutil
import tempfile

from _common import setup

setup()

from theanompi_tpu import BSP  # noqa: E402


def run(devices, epochs, ckpt_dir, resume):
    rule = BSP()
    rule.init(devices=devices,
              modelfile="theanompi_tpu.models.cifar10",
              modelclass="Cifar10_model",
              fsdp=True, optimizer="adam", learning_rate=1e-3,
              synthetic_train=2048, synthetic_val=512, batch_size=16,
              epochs=epochs, printFreq=32,
              ckpt_dir=ckpt_dir, resume=resume,
              compute_dtype="float32", scale_lr=False)
    rec = rule.wait()
    print(f"[{devices} workers] last val:", rec.epoch_records[-1])
    return rec


if __name__ == "__main__":
    d = os.environ.get("CKPT_DIR") or tempfile.mkdtemp(prefix="elastic_")
    try:
        print("== phase 1: 8 workers, FSDP chunks = 1/8 of the params each")
        run(8, epochs=1, ckpt_dir=d, resume=False)
        print("== phase 2: resume the SAME training on 4 workers "
              "(chunks re-partition on load)")
        run(4, epochs=2, ckpt_dir=d, resume=True)
    finally:
        if not os.environ.get("CKPT_DIR"):
            shutil.rmtree(d, ignore_errors=True)
