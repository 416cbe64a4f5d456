"""Required FLOPs, and the delta rule's required bytes, of a routed decoder
of Kimi Delta Attention and latent attention layers (Moonshot's Kimi
Linear), held as one chip's share, from the configuration file's published
keys and its ``seq_len``: ``linear_attn_config`` says which layers are
``kda`` (``kda_layers``, counted from 1) and the rest are ``mla``, read as
far as ``num_hidden_layers``; the first ``first_k_dense_replace`` layers
have the dense feed-forward and the rest the routed one; ``num_experts`` is
what the chip holds and ``published.num_experts`` what the router scores.

What is counted, per sequence and in multiply-accumulates:

- a ``kda`` layer: its projections (q, k, v and o over ``num_heads`` heads
  of ``head_dim``, the two low-rank pairs of the decay and the output
  gate, ``beta``), its convolutions' taps (``short_conv_kernel_size`` a
  channel, three of them) and its core in the chunked form of chunks of
  ``CHUNK`` (:func:`kda_core_macs_per_chunk_head`);
- an ``mla`` layer: its projections (q over the heads at ``nope + rope``,
  the latent and the shared rope part, the latent's expansion to keys and
  values, o) and its core as required, scores at ``nope + rope`` and
  weighted sums at ``v_head_dim`` over the causal half, ``(T + 1) / 2``
  keys a query;
- a dense layer: its feed-forward; a routed layer: the router over every
  expert, the shared experts and the held experts at the **expected**
  ``top_k * held / routed`` applications a token, which uniform routing
  gives; the head over the vocabulary held.

Times 2 FLOPs, times 3 for forward, gradient to the inputs and gradient to
the weights.  Not counted: norms, gates' sigmoids, the decays' softplus and
exponentials, softmax, the top-k, sort, gather and scatter of the routed
layer, the embedding lookup, the optimizer, anything recomputed.
"""

from __future__ import annotations

CHUNK = 64           # tokens of the delta rule's chunked form
CD_BYTES, F32_BYTES = 2, 4


def _kinds(config: dict):
    """``(operator, feed-forward)`` of each layer held."""
    lin = config["linear_attn_config"]
    n, dense = int(config["num_hidden_layers"]), int(
        config["first_k_dense_replace"])
    kda = {int(i) - 1 for i in lin["kda_layers"]}
    return [("kda" if i in kda else "mla", "dense" if i < dense else "sparse")
            for i in range(n)]


def kda_layers(config: dict) -> int:
    return sum(op == "kda" for op, _ in _kinds(config))


def kda_core_macs_per_chunk_head(c: int, dk: int, dv: int) -> int:
    """The chunked delta rule's products in one chunk of ``c`` tokens and
    one head: the causal ``q k`` pairs and the strictly lower ``k k``
    pairs (``dk`` each), ``T = (I + L)^-1`` by substitution, ``T``'s two
    applications (``W`` at ``dk``, ``U`` at ``dv``), the causal pairs'
    sums of ``U`` and three ``c x dk x dv`` products with the state."""
    causal, strict = c * (c + 1) // 2, c * (c - 1) // 2
    return causal * (dk + dk + dv + dv) + strict * dk \
        + c * (c - 1) * (c - 2) // 6 + 3 * c * dk * dv


def _sizes(config: dict):
    lin = config["linear_attn_config"]
    return (int(config["hidden_size"]), int(lin["num_heads"]),
            int(lin["head_dim"]), int(lin["short_conv_kernel_size"]))


def forward_macs_per_sample(config: dict) -> int:
    t = int(config["seq_len"])
    d, h, hd, taps = _sizes(config)
    heads = int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), int(
        config["qk_rope_head_dim"])
    vd, latent = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    routed = int(config["published"]["num_experts"])
    expert = 3 * d * int(config["moe_intermediate_size"])
    pairs = int(config["num_experts_per_token"]) * int(config["num_experts"])
    assert t * pairs % routed == 0 and t % CHUNK == 0, (t, pairs, routed)
    total = t * d * int(config["vocab_size"])
    for op, ff in _kinds(config):
        if op == "kda":
            total += t * (4 * d * h * hd + 2 * (d * hd + hd * h * hd) + d * h
                          + 3 * taps * h * hd) \
                + (t // CHUNK) * h * kda_core_macs_per_chunk_head(CHUNK, hd,
                                                                  hd)
        else:
            total += t * (d * heads * (nope + rope) + d * (latent + rope)
                          + latent * heads * (nope + vd) + heads * vd * d) \
                + heads * (nope + rope + vd) * t * (t + 1) // 2
        total += t * 3 * d * int(config["intermediate_size"]) \
            if ff == "dense" else t * (d * routed + expert * int(
                config["num_shared_experts"])) + expert * t * pairs // routed
    return total


def train_flops_per_sample(config: dict) -> int:
    """FLOPs one training step requires per sequence."""
    return 2 * 3 * forward_macs_per_sample(config)


def kda_core_train_flops_per_sample(config: dict) -> int:
    """The chunked delta rule's share of :func:`train_flops_per_sample`:
    what ``kda_core_roofline_share`` holds the core's time to, beside its
    bytes."""
    t = int(config["seq_len"])
    _, h, hd, _ = _sizes(config)
    return 2 * 3 * kda_layers(config) * (t // CHUNK) * h \
        * kda_core_macs_per_chunk_head(CHUNK, hd, hd)


def kda_core_train_bytes_per_sample(config: dict) -> int:
    """The HBM bytes the delta rule of every ``kda`` layer requires per
    sequence, forward and backward: its inputs read and its output written
    once each way, q, k, v and their cotangents in ``compute_dtype``, the
    log decay, the output and their cotangents in float32: forward q, k,
    v, a read and o written, backward the same four and o's cotangent read
    and the four cotangents written, 38 bytes a token and channel; and
    ``beta``, one float32 a token and head, read twice and its cotangent
    written.  The states stay on the chip's side of the count."""
    t = int(config["seq_len"])
    _, h, hd, _ = _sizes(config)
    per_channel = (3 * CD_BYTES + 2 * F32_BYTES) \
        + (3 * CD_BYTES + 2 * F32_BYTES + 3 * CD_BYTES + F32_BYTES)
    return kda_layers(config) * t * h * (hd * per_channel + 3 * F32_BYTES)


def n_params(config: dict) -> int:
    d, h, hd, taps = _sizes(config)
    heads = int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), int(
        config["qk_rope_head_dim"])
    vd, latent = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    expert = 3 * d * int(config["moe_intermediate_size"])
    total = 2 * int(config["vocab_size"]) * d + d       # embedding, head, N_f
    for op, ff in _kinds(config):
        total += 2 * d
        if op == "kda":
            total += 4 * d * h * hd + 2 * (d * hd + hd * h * hd) + d * h \
                + 3 * taps * h * hd + h * hd + h + hd   # b_dt, A_log, o_norm
        else:
            total += d * heads * (nope + rope) + d * (latent + rope) \
                + latent + latent * heads * (nope + vd) + heads * vd * d
        total += 3 * d * int(config["intermediate_size"]) if ff == "dense" \
            else d * int(config["published"]["num_experts"]) + expert * (
                int(config["num_shared_experts"]) + int(config["num_experts"]))
    return total
