"""ResNet-50.

Reference: ``theanompi/models/resnet50.py`` / ``lasagne_model_zoo/resnet50.py``
(SURVEY.md §2.7) — the He et al. 2015 bottleneck architecture wrapped in the
Theano-MPI model contract.  BASELINE.json config #4 trains it under the GoSGD
gossip exchanger.

The residual graph is built from a composite :class:`Bottleneck` layer that
threads BatchNorm running statistics through the ``state`` pytree (the
framework's BN-state convention, models/layers.py)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layers as L
from .data.imagenet import ImageNet_data
from .model_base import ModelBase


class ConvBN(L.Layer):
    """conv → BN → (relu) — the ResNet primitive."""

    has_state = True

    def __init__(self, in_ch, out_ch, kernel, stride=1, padding="SAME",
                 relu=True, cd=jnp.bfloat16, bn_nd=None, name="convbn"):
        self.name = name
        self.conv = L.Conv(in_ch, out_ch, kernel, stride=stride,
                           padding=padding, w_init="he", activation=None,
                           compute_dtype=cd, name="conv")
        self.bn = L.BatchNorm(out_ch, norm_dtype=bn_nd, name="bn")
        self.relu = relu

    def init(self, key):
        return {"conv": self.conv.init(key), "bn": self.bn.init(key)}

    def init_state(self):
        return {"bn": self.bn.init_state()}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        y = self.conv.apply(params["conv"], x, train=train)
        y, bn_new = self.bn.apply(params["bn"], y, train=train,
                                  state=state["bn"])
        if self.relu:
            y = jax.nn.relu(y)
        return y, ({"bn": bn_new} if bn_new is not None else None)


class Bottleneck(L.Layer):
    """1×1 → 3×3 → 1×1 bottleneck with identity or projection shortcut."""

    has_state = True

    def __init__(self, in_ch, mid_ch, out_ch, stride=1, project=False,
                 cd=jnp.bfloat16, bn_nd=None, name="block"):
        self.name = name
        self.a = ConvBN(in_ch, mid_ch, 1, cd=cd, bn_nd=bn_nd, name="a")
        self.b = ConvBN(mid_ch, mid_ch, 3, stride=stride, cd=cd, bn_nd=bn_nd,
                        name="b")
        self.c = ConvBN(mid_ch, out_ch, 1, relu=False, cd=cd, bn_nd=bn_nd,
                        name="c")
        self.project = project
        if project:
            self.proj = ConvBN(in_ch, out_ch, 1, stride=stride, relu=False,
                               cd=cd, bn_nd=bn_nd, name="proj")

    def _subs(self):
        subs = {"a": self.a, "b": self.b, "c": self.c}
        if self.project:
            subs["proj"] = self.proj
        return subs

    def init(self, key):
        subs = self._subs()
        keys = jax.random.split(key, len(subs))
        return {n: m.init(k) for (n, m), k in zip(subs.items(), keys)}

    def init_state(self):
        return {n: m.init_state() for n, m in self._subs().items()}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        new_state = {}

        def run(name, mod, inp):
            y, st = mod.apply(params[name], inp, train=train,
                              state=state[name])
            if st is not None:
                new_state[name] = st
            return y

        y = run("a", self.a, x)
        y = run("b", self.b, y)
        y = run("c", self.c, y)
        sc = run("proj", self.proj, x) if self.project else x
        out = jax.nn.relu(y + sc)
        return out, (new_state or None)


class ResNet50(ModelBase):
    batch_size = 32
    epochs = 90
    n_subb = 1
    learning_rate = 0.1
    momentum = 0.9
    weight_decay = 0.0001
    lr_adjust_epochs = (30, 60, 80)
    n_class = 1000

    # (mid_ch, out_ch, n_blocks, first_stride) per stage
    stages = ((64, 256, 3, 1), (128, 512, 4, 2),
              (256, 1024, 6, 2), (512, 2048, 3, 2))

    def build_model(self) -> None:
        cd = self.config.get("compute_dtype", jnp.bfloat16)
        # bn_norm_dtype='bfloat16': normalize in bf16 with fp32 stats —
        # perf A/B lever (ROADMAP S8); default fp32-exact
        bn_nd = self.config.get("bn_norm_dtype")
        if isinstance(bn_nd, str):
            bn_nd = jnp.dtype(bn_nd).type if bn_nd != "none" else None
        nc = self.config.get("n_class", self.n_class)
        layers = [
            ConvBN(3, 64, 7, stride=2, padding=3, cd=cd, bn_nd=bn_nd,
                   name="conv1"),
            L.Pool(3, 2, mode="max", padding="SAME", name="pool1"),
        ]
        in_ch = 64
        for si, (mid, out, reps, stride) in enumerate(self.stages, start=2):
            for bi in range(reps):
                layers.append(Bottleneck(
                    in_ch, mid, out,
                    stride=stride if bi == 0 else 1,
                    project=(bi == 0), cd=cd, bn_nd=bn_nd,
                    name=f"res{si}_{bi + 1}"))
                in_ch = out
        self.trunk = L.Sequential(layers)
        self.fc = L.FC(2048, nc, w_init=("normal", 0.01), activation=None,
                       compute_dtype=cd, name="softmax")
        self.data = ImageNet_data(self.config, self.batch_size, crop=224)

    def init_params(self, key):
        k1, k2 = jax.random.split(key)
        return {"trunk": self.trunk.init(k1), "fc": self.fc.init(k2)}

    def init_bn_state(self):
        return {"trunk": self.trunk.init_state()}

    def apply_model(self, params, x, *, train, rng, state):
        y, trunk_state = self.trunk.apply(params["trunk"], x, train=train,
                                          rng=rng, state=state["trunk"])
        y = jnp.mean(y, axis=(1, 2))      # global average pool
        logits = self.fc.apply(params["fc"], y, train=train)
        return logits, {"trunk": trunk_state}
