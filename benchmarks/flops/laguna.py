"""Required FLOPs of a routed decoder whose layers differ (poolside's
Laguna), held as one chip's share, from the configuration file's published
keys and its ``seq_len``: ``layer_types``, ``mlp_layer_types`` and
``num_attention_heads_per_layer`` are read as far as ``num_hidden_layers``;
``num_experts`` is what the chip holds and ``published.num_experts`` what
the router scores.

What is counted, per sequence and in multiply-accumulates: in every layer
the projections at the heads held (q and o over the layer's query heads, k
and v over the key/value heads, the per-head gate); the attention core as
required, scores and weighted sums over the keys a query may see (a
full_attention layer: the causal half, ``(T + 1) / 2`` keys a query; a
sliding_attention layer: ``512 - 512 * 511 / (2 T)``); in a sparse layer
the router over every expert, the shared expert, and the held experts at
the **expected** ``top_k * held / routed`` applications a token, which is
what uniform routing gives; in a dense layer its feed-forward; the head
over the vocabulary held.  Times 2 FLOPs, times 3 for forward, gradient to
the inputs and gradient to the weights.  Not counted: norms, rotary turns,
softmax, sigmoids, the top-k, sort, gather and scatter of the routed
layer, the embedding lookup, the optimizer, anything recomputed.
"""

from __future__ import annotations


def _layers(config: dict):
    """``(attention kind, query heads, feed-forward kind)`` of each layer
    held."""
    n = int(config["num_hidden_layers"])
    return list(zip(config["layer_types"][:n],
                    config["num_attention_heads_per_layer"][:n],
                    config["mlp_layer_types"][:n]))


def keys_seen(kind: str, t: int, window: int) -> int:
    """Sum over a sequence's ``t`` queries of the keys each may see."""
    if kind == "sliding_attention" and window < t:
        return window * (window + 1) // 2 + (t - window) * window
    return t * (t + 1) // 2


def attn_core_macs_per_sample(config: dict) -> int:
    t, hd = int(config["seq_len"]), int(config["head_dim"])
    return sum(2 * hd * heads * keys_seen(kind, t, int(
        config["sliding_window"])) for kind, heads, _ in _layers(config))


def routed_experts_macs_per_sample(config: dict) -> int:
    """The held experts' three products at the expected number of routed
    (token, expert) pairs under uniform routing."""
    d, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    pairs = int(config["seq_len"]) * int(config["num_experts_per_tok"]) \
        * int(config["num_experts"])
    routed = int(config["published"]["num_experts"])
    assert pairs % routed == 0, (pairs, routed)
    sparse = sum(ff == "sparse" for _, _, ff in _layers(config))
    return sparse * 3 * d * f * (pairs // routed)


def forward_macs_per_sample(config: dict) -> int:
    t, d = int(config["seq_len"]), int(config["hidden_size"])
    hd, kv = int(config["head_dim"]), int(config["num_key_value_heads"])
    per_token = d * int(config["vocab_size"])
    for _, heads, ff in _layers(config):
        per_token += d * hd * 2 * (heads + kv) + d * heads
        per_token += 3 * d * int(config["intermediate_size"]) \
            if ff == "dense" else \
            d * int(config["published"]["num_experts"]) \
            + 3 * d * int(config["shared_expert_intermediate_size"])
    return t * per_token + attn_core_macs_per_sample(config) \
        + routed_experts_macs_per_sample(config)


def train_flops_per_sample(config: dict) -> int:
    """FLOPs one training step requires per sequence."""
    return 2 * 3 * forward_macs_per_sample(config)


def attn_core_train_flops_per_sample(config: dict) -> int:
    """The part of it under scores-to-weighted-sum, every layer: what
    ``window_attention_roofline_share`` holds the kernel to."""
    return 2 * 3 * attn_core_macs_per_sample(config)


def routed_experts_train_flops_per_sample(config: dict) -> int:
    """The part of it in the held experts' grouped products, expected under
    uniform routing: what ``expert_roofline_share`` holds them to."""
    return 2 * 3 * routed_experts_macs_per_sample(config)


def n_params(config: dict) -> int:
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    kv, v = int(config["num_key_value_heads"]), int(config["vocab_size"])
    expert = 3 * d * int(config["moe_intermediate_size"])
    total = 2 * v * d + d
    for _, heads, ff in _layers(config):
        total += d * hd * 2 * (heads + kv) + d * heads + 2 * d
        total += 3 * d * int(config["intermediate_size"]) if ff == "dense" \
            else d * int(config["published"]["num_experts"]) \
            + 3 * d * int(config["shared_expert_intermediate_size"]) \
            + int(config["num_experts"]) * expert
    return total
