"""The Kimi Linear model's plain reference (``benchmarks/reference/
kimi_linear.py``) at the toy cell's sizes: heads of 12, q and k 8 + 4,
v 12, 4 experts a token.  Nothing of its own."""

import functools

from benchmarks.reference import kimi_linear

SIZES = {"head_dim": 12, "nope": 8, "rope": 4, "v_dim": 12, "top_k": 4}

forward = functools.partial(kimi_linear.forward, **SIZES)
train_loss = functools.partial(kimi_linear.train_loss, **SIZES)
batch = kimi_linear.batch
