"""Routed decoder-only language model whose layers differ along the stack:
full-attention and causal-window layers with their own query-head counts
and rotary forms, a dense gated feed-forward in some layers and a routed
one in the others, held as one chip's share of an expert- and
head-parallel deployment.

The architecture of poolside's Laguna models
(https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json),
through the same duck-typed model contract as the rest of the zoo --
``rule.init(modelfile='theanompi_tpu.models.routed_lm',
modelclass='RoutedLM', ...)``:

    h^0 = E[x];   u = h + Attn_l(N1(h));   h' = u + FF_l(N2(u))
    logits = W_head N_f(h^L);   objective = mean next-token cross-entropy

``Attn_l`` is :class:`layers.GroupedQueryAttention` (fewer key/value heads
than query heads, a per-head sigmoid output gate, rotary positions over a
part of the head), causal in a ``full_attention`` layer and a causal
window in a ``sliding_attention`` layer; ``FF_l`` is
:class:`layers.GatedMLP` in a ``dense`` layer and
:class:`parallel.moe.HeldExperts` in a ``sparse`` one (a router over all
the model's experts, the held ones' part computed, a shared expert).

**The stack is a pattern**: ``layer_types``, ``mlp_layer_types`` and
``n_head_per_layer`` say, layer by layer, which attention, how many query
heads and which feed-forward; ``rope`` says each attention kind's rotary
form (``default`` or ``yarn``, how much of the head turns).  One loop
builds every layer from its row of the pattern.

The share of a deployment is stated in sizes alone: the query heads of
each layer and the key/value heads held here, ``experts_held`` (a range
of the ``n_experts`` the router scores), the rows of the vocabulary.
Nothing stands in for the absent chips: an expert that is chosen and not
held adds nothing.

The residual stream, the norms, the router and the loss are float32;
matmul operands are ``compute_dtype``.  Each layer is rematerialised in
the backward pass (``jax.checkpoint``); the head's loss and its gradients
come from one pass over the logits, ``head_block`` tokens at a time
(:func:`layers.weighted_cross_entropy` with every token's weight ``1/N``).
Scopes: ``block<i>`` (inside ``attn``, ``attn_core``, ``mlp`` or ``moe``
with ``router``, ``experts``, ``shared_expert``) and ``head``.  Counters,
at the step's first trace: ``model.experts_held``,
``model.experts_routed``, ``model.routed_pairs``,
``model.routed_rows_at_once`` (the rows the routed layers' first stretches
hold: where ``routed_pairs * held / experts`` fits them, the expected
routing never enters a walk's loop), ``model.window_layers``,
``model.full_layers``, ``model.attn_kernel_applications``,
``model.head_logit_products``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.moe import HeldExperts
from ..utils import telemetry
from . import layers as L
from .looped_lm import UniformTokens
from .model_base import ModelBase


def rotary_frequencies(rope: dict, head_dim: int) -> tuple:
    """``(frequencies, factor)`` of one attention kind's entry of the
    source's ``rope_parameters``: ``partial_rotary_factor`` of the head
    turns; ``rope_type`` ``default`` turns pair ``i`` by ``rope_theta **
    (-2i / rot)``, ``yarn`` by :func:`layers.yarn_frequencies` with cos
    and sin times ``attention_factor``."""
    rot = int(head_dim * float(rope.get("partial_rotary_factor", 1)))
    theta = float(rope["rope_theta"])
    if rope["rope_type"] == "yarn":
        return L.yarn_frequencies(
            rot, theta, float(rope["factor"]),
            int(rope["original_max_position_embeddings"]),
            float(rope["beta_fast"]), float(rope["beta_slow"])), \
            float(rope["attention_factor"])
    assert rope["rope_type"] == "default", rope["rope_type"]
    return (theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
            ).astype(np.float32), 1.0


class PreNormBlock(L.Layer):
    """``u = h + Attn(N1(h))``, ``h' = u + FF(N2(u))`` around whatever
    attention and feed-forward the pattern gave this layer."""

    def __init__(self, dim, attn, ff, eps=1e-6, name="block"):
        self.name, self.attn, self.ff = name, attn, ff
        self.norm1 = L.RMSNorm(dim, eps, name="norm1")
        self.norm2 = L.RMSNorm(dim, eps, name="norm2")

    def init(self, key):
        ka, kf = jax.random.split(key)
        return {"norm1": self.norm1.init(None), "norm2": self.norm2.init(None),
                self.attn.name: self.attn.init(ka),
                self.ff.name: self.ff.init(kf)}

    def apply(self, params, h, *, train=False, rng=None, state=None):
        a = self.attn.apply(params[self.attn.name],
                            self.norm1.apply(params["norm1"], h), train=train)
        u = h + a.astype(h.dtype)
        f = self.ff.apply(params[self.ff.name],
                          self.norm2.apply(params["norm2"], u), train=train)
        return u + f.astype(h.dtype)


class RoutedLM(ModelBase):
    batch_size = 1
    epochs = 1
    n_subb = 1
    learning_rate = 3e-4
    optimizer = "adam"
    weight_decay = 0.0
    vocab = 128             # rows of the vocabulary held
    d_model = 64
    head_dim = 16
    n_kv_head = 1           # key/value heads held
    n_layer = 4
    d_ff = 128              # a dense layer's feed-forward
    n_experts = 16          # the router's width: all the model's experts
    experts_held = (0, 4)   # the range of them this chip holds
    top_k = 4
    expert_width = 32
    shared_width = 32
    routed_scale = 2.5
    window = 8
    seq_len = 32
    norm_eps = 1e-6
    head_block = 2048       # tokens whose logits exist at once
    head_std = 0.005        # as LoopedLM's: the first cost starts within
                            # a few hundredths of ln(vocab)
    # the pattern, layer by layer; a longer list is read as far as n_layer
    layer_types = ("full_attention", "sliding_attention",
                   "sliding_attention", "full_attention")
    mlp_layer_types = ("dense", "sparse", "sparse", "sparse")
    n_head_per_layer = (2, 3, 3, 2)     # query heads held
    rope = {"full_attention": {"rope_type": "yarn", "rope_theta": 5e5,
                               "factor": 4, "beta_fast": 32, "beta_slow": 1,
                               "original_max_position_embeddings": 16,
                               "attention_factor": 1.2,
                               "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 1e4,
                                  "partial_rotary_factor": 1}}

    def build_model(self) -> None:
        cfg = self.config
        self.cd = cd = cfg.get("compute_dtype", jnp.bfloat16)
        for k in ("vocab", "d_model", "head_dim", "n_kv_head", "n_layer",
                  "d_ff", "n_experts", "top_k", "expert_width",
                  "shared_width", "window", "seq_len", "head_block"):
            if k in cfg:
                setattr(self, k, int(cfg[k]))
        for k in ("norm_eps", "routed_scale"):
            if k in cfg:
                setattr(self, k, float(cfg[k]))
        for k in ("layer_types", "mlp_layer_types", "n_head_per_layer",
                  "experts_held", "rope"):
            if k in cfg:
                setattr(self, k, cfg[k])
        impl = str(cfg.get("attn_impl", "reference"))
        # what each kind of layer is made with; the pattern picks a row
        attention = {}
        for kind, window in (("full_attention", None),
                             ("sliding_attention", self.window)):
            freq, factor = rotary_frequencies(self.rope[kind], self.head_dim)
            attention[kind] = dict(freq=freq, rope_factor=factor,
                                   window=window)
        feed_forward = {
            "dense": lambda: L.GatedMLP(self.d_model, self.d_ff,
                                        compute_dtype=cd, name="mlp"),
            "sparse": lambda: HeldExperts(
                self.d_model, self.n_experts, self.experts_held, self.top_k,
                self.expert_width, self.shared_width, self.routed_scale,
                compute_dtype=cd, name="moe")}
        self.blocks = [PreNormBlock(
            self.d_model,
            L.GroupedQueryAttention(self.d_model, int(heads), self.n_kv_head,
                                    self.head_dim, compute_dtype=cd,
                                    attn_impl=impl, name="attn",
                                    **attention[kind]),
            feed_forward[ff](), self.norm_eps, name=f"block{i}")
            for i, (kind, ff, heads) in enumerate(zip(
                self.layer_types[:self.n_layer],
                self.mlp_layer_types[:self.n_layer],
                self.n_head_per_layer[:self.n_layer]))]
        assert len(self.blocks) == self.n_layer, (
            f"the pattern names {len(self.blocks)} layers of {self.n_layer}")
        # the table is read in float32: the residual stream starts there
        self.embed = L.Embedding(self.vocab, self.d_model,
                                 compute_dtype=jnp.float32)
        self.norm_f = L.RMSNorm(self.d_model, self.norm_eps, name="norm_f")
        self.data = UniformTokens(cfg, self.batch_size)
        self._counted = False

    def init_params(self, key):
        ks = jax.random.split(key, len(self.blocks) + 2)
        p = {"embed": self.embed.init(ks[0]),
             "norm_f": self.norm_f.init(None),
             "head": {"w": L.init_weight(ks[1], (self.d_model, self.vocab),
                                         ("normal", self.head_std))}}
        for blk, k in zip(self.blocks, ks[2:]):
            p[blk.name] = blk.init(k)
        return p

    def init_bn_state(self):
        return {}

    def hidden_state(self, params, x, train: bool):
        """Ids ``[B, T]`` -> ``[B, T, d]``: the normed state the head reads."""
        h = self.embed.apply(params["embed"], x)
        for blk in self.blocks:
            run = lambda p, h, _b=blk: _b.apply(p, h, train=train)  # noqa: E731,E501
            if train:           # one saved residual a layer
                run = jax.checkpoint(run)
            with jax.named_scope(blk.name):
                h = run(params[blk.name], h)
        return self.norm_f.apply(params["norm_f"], h)

    def _logits(self, params, h):
        cd = self.cd
        return jnp.dot(h.astype(cd), params["head"]["w"].astype(cd),
                       preferred_element_type=jnp.float32)

    def apply_model(self, params, x, *, train, rng, state):
        h = self.hidden_state(params, x, train)
        with jax.named_scope("head"):
            return self._logits(params, h), state

    def _count_once(self, rows: int) -> None:
        if not self._counted:
            self._counted = True
            sparse = [b.ff for b in self.blocks
                      if isinstance(b.ff, HeldExperts)]
            windows = sum(b.attn.window is not None for b in self.blocks)
            telemetry.count("model.experts_held",
                            sum(ff.n_held for ff in sparse))
            telemetry.count("model.experts_routed",
                            sum(ff.n_experts for ff in sparse))
            telemetry.count("model.routed_pairs", sum(
                rows * self.seq_len * ff.top_k for ff in sparse))
            telemetry.count("model.routed_rows_at_once", sum(
                ff.rows_at_once(rows * self.seq_len) for ff in sparse))
            telemetry.count("model.window_layers", windows)
            telemetry.count("model.full_layers", len(self.blocks) - windows)
            telemetry.count("model.attn_kernel_applications", sum(
                b.attn.attn_impl == "flash" for b in self.blocks))
            telemetry.count("model.head_logit_products", L.head_logit_products(
                rows * self.seq_len, self.head_block))

    def loss_and_metrics(self, params, bn_state, batch, rng, train):
        x, y = batch["x"], batch["y"]
        if train:
            self._count_once(x.shape[0])
        h = self.hidden_state(params, x, train)
        n = y.size
        with jax.named_scope("head"):
            cost, _, miss = L.weighted_cross_entropy(
                params["head"]["w"], h.reshape(n, self.d_model),
                y.reshape(n), jnp.full((n,), 1.0 / n), block=self.head_block,
                compute_dtype=self.cd)
            return cost, (jnp.mean(miss), bn_state)

    def val_metrics(self, params, bn_state, batch):
        logits, _ = self.apply_model(params, batch["x"], train=False,
                                     rng=None, state=bn_state)
        flat, y = logits.reshape(-1, self.vocab), batch["y"].reshape(-1)
        return L.softmax_cross_entropy(flat, y), (
            L.errors(flat, y), L.errors_top_x(flat, y, 5))
