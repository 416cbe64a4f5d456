"""Required FLOPs of a looped decoder-only language model, from the
configuration file's published keys (``hidden_size``, ``intermediate_size``,
``num_hidden_layers``, ``vocab_size``, ``total_ut_steps``) and its
``seq_len``.

What is counted, per token and in multiply-accumulates: for every loop step
and layer the four attention projections ``4 d^2``, the gated feed-forward
``3 d ff`` and the causal half of the scores and weighted sums ``T d``
(``2 T d`` unmasked); for every loop step the whole-vocabulary head ``d V``,
because the training objective reads all of them.  Times 2 FLOPs, times 3
for forward, gradient to the inputs and gradient to the weights.  Not
counted: norms, rotary turns, softmax, the gate, the embedding lookup, the
optimizer update, and anything recomputed in the backward pass.
"""

from __future__ import annotations


def _sizes(config: dict):
    return (int(config["hidden_size"]), int(config["intermediate_size"]),
            int(config["num_hidden_layers"]), int(config["vocab_size"]),
            int(config["total_ut_steps"]), int(config["seq_len"]))


def layer_macs_per_token(config: dict) -> int:
    """One layer application: projections, feed-forward, causal attention."""
    d, ff, _, _, _, t = _sizes(config)
    return 4 * d * d + 3 * d * ff + t * d


def forward_macs_per_token(config: dict) -> int:
    d, _, layers, v, loops, _ = _sizes(config)
    return loops * (layers * layer_macs_per_token(config) + d * v)


def train_flops_per_sample(config: dict) -> int:
    """FLOPs one training step requires per sequence."""
    return 2 * 3 * forward_macs_per_token(config) * int(config["seq_len"])


def attn_core_train_flops_per_sample(config: dict) -> int:
    """The part of it under scores-to-weighted-sum, all loop steps and
    layers: what ``attention_roofline_share`` holds the kernel to."""
    d, _, layers, _, loops, t = _sizes(config)
    return 2 * 3 * loops * layers * t * d * t


def n_params(config: dict) -> int:
    """A layer is four projections, three feed-forward matrices and four
    norm scales; beside the layers the embedding, the head, the final norm
    and the exit gate (a vector and a scalar)."""
    d, ff, layers, v, _, _ = _sizes(config)
    return layers * (4 * d * d + 3 * d * ff + 4 * d) + 2 * v * d + d + d + 1
