"""GoogLeNet (Inception v1) with auxiliary classifiers.

Reference: ``theanompi/models/googlenet.py`` (SURVEY.md §2.7): ImageNet,
batch 32 in the paper's benchmarks, the full Szegedy et al. 2014 graph —
stem, nine inception modules, two auxiliary softmax heads (weighted 0.3 into
the training loss, dropped at eval), global average pooling, dropout 0.4.

The branch-parallel inception module is a composite :class:`Inception`
layer; the aux taps make the trunk a staged pipeline rather than one
Sequential, so this model overrides the ``init_params``/``apply_model`` hooks.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import layers as L
from .data.imagenet import ImageNet_data
from .model_base import ModelBase


class Inception(L.Layer):
    """Four-branch inception module: 1×1 / 1×1→3×3 / 1×1→5×5 / pool→1×1,
    channel-concatenated."""

    def __init__(self, in_ch, c1, c3r, c3, c5r, c5, pp, cd, name):
        self.name = name
        self.out_ch = c1 + c3 + c5 + pp
        k = dict(w_init="he", compute_dtype=cd)
        self.b1 = L.Sequential([L.Conv(in_ch, c1, 1, name="1x1", **k)])
        self.b2 = L.Sequential([
            L.Conv(in_ch, c3r, 1, name="3x3r", **k),
            L.Conv(c3r, c3, 3, padding="SAME", name="3x3", **k)])
        self.b3 = L.Sequential([
            L.Conv(in_ch, c5r, 1, name="5x5r", **k),
            L.Conv(c5r, c5, 5, padding="SAME", name="5x5", **k)])
        self.b4_pool = L.Pool(3, 1, mode="max", padding="SAME", name="pool")
        self.b4 = L.Sequential([L.Conv(in_ch, pp, 1, name="poolproj", **k)])

    def init(self, key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {"b1": self.b1.init(k1), "b2": self.b2.init(k2),
                "b3": self.b3.init(k3), "b4": self.b4.init(k4)}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        y1, _ = self.b1.apply(params["b1"], x, train=train)
        y2, _ = self.b2.apply(params["b2"], x, train=train)
        y3, _ = self.b3.apply(params["b3"], x, train=train)
        yp = self.b4_pool.apply(None, x)
        y4, _ = self.b4.apply(params["b4"], yp, train=train)
        return jnp.concatenate([y1, y2, y3, y4], axis=-1)


class GoogLeNet(ModelBase):
    batch_size = 32
    epochs = 70
    n_subb = 1
    learning_rate = 0.01
    momentum = 0.9
    weight_decay = 0.0002
    lr_adjust_epochs = (20, 40, 60)
    n_class = 1000
    aux_weight = 0.3

    def build_model(self) -> None:
        cd = self.config.get("compute_dtype", jnp.bfloat16)
        nc = self.config.get("n_class", self.n_class)
        self._nc = nc
        k = dict(w_init="he", compute_dtype=cd)

        self.stem = L.Sequential([
            L.Conv(3, 64, 7, stride=2, padding=3, name="conv1", **k),
            L.Pool(3, 2, mode="max", padding="SAME", name="pool1"),
            L.LRN(name="lrn1"),
            L.Conv(64, 64, 1, name="conv2r", **k),
            L.Conv(64, 192, 3, padding="SAME", name="conv2", **k),
            L.LRN(name="lrn2"),
            L.Pool(3, 2, mode="max", padding="SAME", name="pool2"),
        ])
        self.stage3 = L.Sequential([
            Inception(192, 64, 96, 128, 16, 32, 32, cd, "3a"),
            Inception(256, 128, 128, 192, 32, 96, 64, cd, "3b"),
            L.Pool(3, 2, mode="max", padding="SAME", name="pool3"),
        ])
        self.stage4a = L.Sequential([
            Inception(480, 192, 96, 208, 16, 48, 64, cd, "4a")])
        self.stage4bcd = L.Sequential([
            Inception(512, 160, 112, 224, 24, 64, 64, cd, "4b"),
            Inception(512, 128, 128, 256, 24, 64, 64, cd, "4c"),
            Inception(512, 112, 144, 288, 32, 64, 64, cd, "4d"),
        ])
        self.stage4e = L.Sequential([
            Inception(528, 256, 160, 320, 32, 128, 128, cd, "4e"),
            L.Pool(3, 2, mode="max", padding="SAME", name="pool4"),
        ])
        self.stage5 = L.Sequential([
            Inception(832, 256, 160, 320, 32, 128, 128, cd, "5a"),
            Inception(832, 384, 192, 384, 48, 128, 128, cd, "5b"),
        ])
        self.head = L.Sequential([
            L.Dropout(0.4, name="drop"),
            L.FC(1024, nc, w_init=("normal", 0.01), activation=None,
                 compute_dtype=cd, name="softmax"),
        ])

        # the aux taps sit after four stride-2 stages (conv1, pool1, pool2,
        # pool3 — all ceil-mode), so their spatial side is crop/16 rounded
        # up, and the aux 5×5/3 VALID avg-pool shrinks it again; 224 → 14 → 4
        crop = int(self.config.get("crop_size", 224))
        s = crop
        for _ in range(4):
            s = (s + 1) // 2
        aux_sp = (s - 5) // 3 + 1
        assert aux_sp >= 1, f"crop {crop} too small for the aux heads"

        def aux_head(in_ch, name):
            # avgpool 5×5/3 → 1×1 conv 128 → FC 1024 → dropout .7 → FC nc
            return L.Sequential([
                L.Pool(5, 3, mode="avg", name=f"{name}_pool"),
                L.Conv(in_ch, 128, 1, name=f"{name}_conv", **k),
                L.Flatten(name=f"{name}_flat"),
                L.FC(128 * aux_sp * aux_sp, 1024, w_init="he",
                     compute_dtype=cd, name=f"{name}_fc"),
                L.Dropout(0.7, name=f"{name}_drop"),
                L.FC(1024, nc, w_init=("normal", 0.01), activation=None,
                     compute_dtype=cd, name=f"{name}_out"),
            ])

        self.aux1 = aux_head(512, "aux1")   # taps output of 4a
        self.aux2 = aux_head(528, "aux2")   # taps output of 4d
        self._parts = {
            "stem": self.stem, "stage3": self.stage3,
            "stage4a": self.stage4a,
            "stage4bcd": self.stage4bcd, "stage4e": self.stage4e,
            "stage5": self.stage5, "head": self.head,
            "aux1": self.aux1, "aux2": self.aux2,
        }
        self.data = ImageNet_data(self.config, self.batch_size, crop=224)

    # -- composite-model hooks --------------------------------------------

    def init_params(self, key):
        keys = jax.random.split(key, len(self._parts))
        return {name: part.init(k)
                for (name, part), k in zip(self._parts.items(), keys)}

    def init_bn_state(self):
        return {}

    def _trunk(self, params, x, train, rng):
        def r():
            nonlocal rng
            if rng is None:
                return None
            rng, sub = jax.random.split(rng)
            return sub

        x, _ = self.stem.apply(params["stem"], x, train=train, rng=r())
        x, _ = self.stage3.apply(params["stage3"], x, train=train, rng=r())
        x, _ = self.stage4a.apply(params["stage4a"], x, train=train, rng=r())
        t4a = x
        x, _ = self.stage4bcd.apply(params["stage4bcd"], x, train=train,
                                    rng=r())
        t4d = x
        x, _ = self.stage4e.apply(params["stage4e"], x, train=train, rng=r())
        x, _ = self.stage5.apply(params["stage5"], x, train=train, rng=r())
        x = jnp.mean(x, axis=(1, 2))            # global average pool 7×7
        logits, _ = self.head.apply(params["head"], x, train=train, rng=r())
        return logits, t4a, t4d, rng

    def apply_model(self, params, x, *, train, rng, state):
        logits, _, _, _ = self._trunk(params, x, train, rng)
        return logits, state

    def loss_and_metrics(self, params, bn_state, batch, rng, train):
        logits, t4a, t4d, rng = self._trunk(
            params, self.stage_input(batch["x"], batch.get("crop_off")),
            train, rng)
        ls = self._label_smoothing(train)
        cost = L.softmax_cross_entropy(logits, batch["y"], ls)
        if train:
            r1, r2 = (jax.random.split(rng) if rng is not None
                      else (None, None))
            a1, _ = self.aux1.apply(params["aux1"], t4a, train=True, rng=r1)
            a2, _ = self.aux2.apply(params["aux2"], t4d, train=True, rng=r2)
            cost = cost + self.aux_weight * (
                L.softmax_cross_entropy(a1, batch["y"], ls) +
                L.softmax_cross_entropy(a2, batch["y"], ls))
        err = L.errors(logits, batch["y"])
        return cost, (err, bn_state)
