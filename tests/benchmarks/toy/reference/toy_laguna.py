"""The routed model's plain reference (``benchmarks/reference/laguna.py``)
at the toy cell's sizes: heads of 16, a window of 8, 4 of 16 experts a
token, four layers (full, window, window, full), YaRN over half the
head in the full layers.  Nothing of its own."""

import functools

from benchmarks.reference import laguna

SIZES = {
    "head_dim": 16, "window": 8, "top_k": 4,
    "layer_types": ("full_attention", "sliding_attention",
                    "sliding_attention", "full_attention"),
    "rope": {"full_attention": {"rope_type": "yarn", "rope_theta": 5e5,
                                "factor": 4, "beta_fast": 32, "beta_slow": 1,
                                "original_max_position_embeddings": 16,
                                "attention_factor": 1.2,
                                "partial_rotary_factor": 0.5},
             "sliding_attention": {"rope_type": "default",
                                   "rope_theta": 1e4,
                                   "partial_rotary_factor": 1}}}

forward = functools.partial(laguna.forward, **SIZES)
train_loss = functools.partial(laguna.train_loss, **SIZES)
batch = laguna.batch
