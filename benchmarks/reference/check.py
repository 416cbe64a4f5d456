"""The comparison between the system and its plain reference.

The system computes in bfloat16 (8 bits of significand: each rounding is up
to 2**-9 = 0.2% of the value) with float32 accumulation and float32
parameters; the reference is float32 at ``Precision.HIGHEST``.  Through 8 to
16 layers the roundings of the activations add up roughly as a random walk
and arrive at the logits as a few parts in a thousand of the logits' range.
The numbers the chip gave are in PERF.md (Findings, PR 23).

The limits are therefore set on the error relative to the largest reference
logit, at 2%: several times what bf16 gives and far under what a narrower
format would (an 8-bit float with 3 bits of significand rounds by up to
2**-4 = 6% per value; ``tests/benchmarks`` shows a float8 forward pass
failing the limit).  The loss is a mean over the batch of differences of
logits, so its limit is the logit limit times the logits' scale, with a
floor for logits near zero.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference.plain_ops import softmax_loss as plain_softmax_loss  # noqa: F401,E501

LOGIT_REL_TOL = 0.02
LOSS_ABS_FLOOR = 2e-3


def image_batch(config: dict, rng: np.random.RandomState, n: int):
    """``n`` seeded crops in the value range the data object delivers
    (uint8 pixels minus the synthetic source's scalar mean 122) and labels."""
    hw, ch = int(config["input_hw"]), int(config["input_channels"])
    x = rng.randint(0, 256, (n, hw, hw, ch)).astype(np.float32) - 122.0
    y = rng.randint(0, int(config["n_class"]), n).astype(np.int32)
    return x, y


def compare(ref_logits, sys_logits, ref_loss: float, sys_loss: float) -> dict:
    scale = float(np.max(np.abs(ref_logits)))
    logit_err = float(np.max(np.abs(sys_logits - ref_logits))) / scale \
        if scale > 0 else float("inf")
    loss_err = abs(sys_loss - ref_loss)
    loss_tol = max(LOSS_ABS_FLOOR, LOGIT_REL_TOL * scale)
    ok = bool(np.isfinite(sys_logits).all() and logit_err <= LOGIT_REL_TOL
              and loss_err <= loss_tol)
    return {"ok": ok, "logit_rel_err": logit_err, "logit_scale": scale,
            "loss_err": loss_err, "loss_tol": loss_tol,
            "ref_loss": ref_loss, "sys_loss": sys_loss}
