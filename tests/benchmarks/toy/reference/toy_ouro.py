"""The looped model's plain reference (``benchmarks/reference/ouro.py``) at
the toy cell's sizes: 2 heads, 3 loop steps.  Nothing of its own."""

import functools

from benchmarks.reference import ouro

SIZES = {"n_head": 2, "loops": 3}

forward = functools.partial(ouro.forward, **SIZES)
train_loss = functools.partial(ouro.train_loss, **SIZES)
batch = ouro.batch
