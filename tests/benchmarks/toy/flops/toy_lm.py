"""Required FLOPs of the toy token model, from the sizes in its
configuration file: per token the attention projections (4 d^2), the causal
scores and their weighted sum (T d: two products over half the positions),
the router (d E), the experts a token is sent to (k times two products of
d by mlp_ratio d) and the head (d V); 2 FLOPs a multiply-accumulate; forward,
gradient to the weights and gradient to the inputs.  Not counted: the
embedding lookups, LayerNorm, softmax, the optimizer, anything recomputed."""


def forward_macs_per_token(c: dict) -> int:
    d, t = c["d_model"], c["seq_len"]
    layer = (4 * d * d + t * d + d * c["moe_experts"]
             + c["moe_topk"] * 2 * d * c["mlp_ratio"] * d)
    return c["n_layer"] * layer + d * c["vocab"]


def train_flops_per_sample(c: dict) -> int:
    """A sample is a sequence of ``seq_len`` tokens."""
    return 2 * 3 * c["seq_len"] * forward_macs_per_token(c)
