"""Required FLOPs of a convolution / fully-connected stack, from the layer
table in the configuration file.

What is counted: the multiply-accumulates of ``conv`` and ``fc`` layers,
2 FLOPs each.  Training = forward + gradient to the weights + gradient to
the inputs, each the forward's MACs again, with no input gradient for the
first layer (its input is data).  Not counted: LRN, pooling, ReLU, dropout,
softmax, the optimizer update (all O(activations) or O(parameters), under
1% of the matmul work at these sizes), and anything the compiler recomputes.
"""

from __future__ import annotations


def layer_macs(layer: dict) -> int:
    """Forward multiply-accumulates of one layer for one sample."""
    if layer["kind"] == "conv":
        return (layer["out_hw"] ** 2 * layer["out"]
                * (layer["in"] // layer["groups"]) * layer["kernel"] ** 2)
    if layer["kind"] == "fc":
        return layer["in"] * layer["out"]
    return 0


def forward_macs_per_sample(config: dict) -> int:
    return sum(layer_macs(l) for l in config["layers"])


def train_flops_per_sample(config: dict) -> int:
    """FLOPs one training step requires per sample (forward and backward)."""
    macs = [layer_macs(l) for l in config["layers"]]
    first = next(m for m in macs if m)
    return 2 * (3 * sum(macs) - first)


def n_params(config: dict) -> int:
    """Weights and biases the layer table implies (checked against the
    configuration's published ``n_params``)."""
    total = 0
    for l in config["layers"]:
        if l["kind"] == "conv":
            total += (l["kernel"] ** 2 * (l["in"] // l["groups"]) * l["out"]
                      + l["out"])
        elif l["kind"] == "fc":
            total += l["in"] * l["out"] + l["out"]
    return total
