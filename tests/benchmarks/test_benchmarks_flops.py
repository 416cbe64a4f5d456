"""The FLOP counter against the published totals, and the peaks table."""

import json
import os

import pytest

from benchmarks import peaks
from benchmarks.flops import conv_stack

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,gmac", [("alexnet", 0.72), ("vgg16", 15.5)])
def test_forward_macs_match_the_published_totals(name, gmac):
    macs = conv_stack.forward_macs_per_sample(_config(name))
    assert macs / 1e9 == pytest.approx(gmac, rel=0.01)


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_layer_table_gives_the_published_parameter_count(name):
    cfg = _config(name)
    assert conv_stack.n_params(cfg) == cfg["n_params"]
    with open(os.path.join(ROOT, "model_param_counts.json")) as f:
        assert json.load(f)[name]["params"] == cfg["n_params"]


def test_alexnet_groups_halve_the_grouped_layers():
    cfg = _config("alexnet")
    by = {l["name"]: conv_stack.layer_macs(l) for l in cfg["layers"]}
    assert by["conv1"] == 55 * 55 * 96 * 3 * 11 * 11
    assert by["conv2"] == 27 * 27 * 256 * 48 * 5 * 5       # two groups
    assert by["conv3"] == 13 * 13 * 384 * 256 * 3 * 3      # one group
    assert by["pool1"] == 0 and by["lrn1"] == 0


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_training_is_three_forwards_less_the_first_input_gradient(name):
    cfg = _config(name)
    fwd = conv_stack.forward_macs_per_sample(cfg)
    first = conv_stack.layer_macs(cfg["layers"][0])
    assert conv_stack.train_flops_per_sample(cfg) == 2 * (3 * fwd - first)


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_layer_table_chains(name):
    """Each conv's input channels are the previous output's, each spatial
    size follows from kernel, stride and padding, and the first FC layer
    takes the flattened last feature map."""
    cfg = _config(name)
    hw, ch = cfg["input_hw"], cfg["input_channels"]
    for l in cfg["layers"]:
        if l["kind"] == "conv":
            assert l["in"] == ch
            assert l["out_hw"] == (hw + 2 * l["pad"] - l["kernel"]) \
                // l["stride"] + 1
            hw, ch = l["out_hw"], l["out"]
        elif l["kind"] == "maxpool":
            assert l["channels"] == ch
            assert l["out_hw"] == (hw - l["kernel"]) // l["stride"] + 1
            hw = l["out_hw"]
        elif l["kind"] == "fc":
            assert l["in"] == (hw * hw * ch if hw else ch)
            hw, ch = 0, l["out"]
    assert ch == cfg["n_class"]


def test_peaks_know_the_v5e_and_refuse_the_unknown():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
