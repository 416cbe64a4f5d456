"""Looped decoder-only language model: one stack of layers applied several
times over the same weights, with a learned exit gate.

The architecture of "Scaling Latent Reasoning via Looped Language Models"
(arXiv:2510.25741), through the same duck-typed model contract as the rest
of the zoo -- ``rule.init(modelfile='theanompi_tpu.models.looped_lm',
modelclass='LoopedLM', ...)``:

    h^0 = E[x];   h^t = Norm_f(Stack(h^{t-1})),  t = 1..R, one set of weights
    layer:  u = h + N2(Attn(N1(h)));  h' = u + N4(MLP(N3(u)))
    logits^t = W_head h^t;   lambda_t = sigmoid(w . h^t + b)
    p_t = lambda_t prod_{j<t}(1 - lambda_j)  (t < R),  p_R = prod_{j<R}(1 - lambda_j)
    objective = mean over tokens of  sum_t p_t CE(logits^t, y) - beta H(p)

``Attn`` is causal softmax attention with rotary positions and no bias,
``MLP`` the gated feed-forward, every norm an RMSNorm (``layers.py``).
Evaluation reads ``logits^R``.  The residual stream, the norms, the gate,
the exit distribution and the loss are float32; matmul operands are
``compute_dtype``.

What a step holds in memory is set here: each layer application is
rematerialised in the backward pass (``jax.checkpoint``; ``R x n_layer``
saved residuals and nothing else of the stack), and the heads' loss and
its gradients come from one pass over the logits, ``head_block`` tokens at
a time (:func:`layers.weighted_cross_entropy`: the exit distribution is
made first and handed to it as the tokens' weights), so that no
``[tokens, vocab]`` tensor of a whole step exists and none is made twice.
Scopes: ``ut_loop`` (the stack, all loop steps; inside it ``attn``,
``attn_core``, ``mlp``) and ``exit_head`` (heads, gate, objective).
Counters, at the step's first trace:
``model.loop_steps``, ``model.layer_applications``, ``model.head_tokens``,
``model.head_logit_products``, ``model.attn_kernel_applications``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils import telemetry
from . import layers as L
from .model_base import ModelBase
from .transformer_lm import LMData


class UniformTokens(LMData):
    """Token ids drawn uniformly over the whole vocabulary from the run's
    seed: fixed-length sequences, no padding, nothing to learn."""

    def _draw(self, n, seed, seq_len, vocab, noise):
        r = np.random.RandomState(
            (seed + int(self.config.get("seed", 0))) % 2 ** 32)
        return r.randint(0, vocab, (n, seq_len + 1)).astype(np.int32)


class SandwichBlock(L.Layer):
    """A layer with an RMSNorm before and after each sub-layer:
    ``u = h + N2(Attn(N1(h)))``, ``h' = u + N4(MLP(N3(u)))``."""

    def __init__(self, dim, n_head, d_ff, theta=10000.0, eps=1e-6,
                 cd=jnp.bfloat16, attn_impl="reference", name="block"):
        self.name = name
        self.norms = [L.RMSNorm(dim, eps, name=f"norm{i}")
                      for i in (1, 2, 3, 4)]
        self.attn = L.RotaryAttention(dim, n_head, theta, compute_dtype=cd,
                                      attn_impl=attn_impl, name="attn")
        self.mlp = L.GatedMLP(dim, d_ff, compute_dtype=cd, name="mlp")

    def init(self, key):
        ka, km = jax.random.split(key)
        p = {n.name: n.init(None) for n in self.norms}
        p.update(attn=self.attn.init(ka), mlp=self.mlp.init(km))
        return p

    def apply(self, params, h, *, train=False, rng=None, state=None):
        n1, n2, n3, n4 = (lambda x, n=n: n.apply(params[n.name], x)
                          for n in self.norms)
        # a sub-layer gives compute_dtype; its norm is taken in the residual
        # stream's own
        a = self.attn.apply(params["attn"], n1(h), train=train)
        u = h + n2(a.astype(h.dtype))
        m = self.mlp.apply(params["mlp"], n3(u), train=train)
        return u + n4(m.astype(h.dtype))


def exit_distribution(z):
    """``[R, ...]`` gate logits -> ``(p, log p)``, the distribution over the
    loop step a token leaves at: ``p_t = lambda_t prod_{j<t}(1 - lambda_j)``
    for ``t < R`` and what is left for ``t = R`` (the last gate is not
    asked).  In logs, so that a saturated gate stays finite."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)     # log prod(1-l)
    before = jnp.concatenate([jnp.zeros_like(z[:1]), stay[:-1]])
    logp = jnp.concatenate([jax.nn.log_sigmoid(z[:-1]) + before, stay[-1:]])
    return jnp.exp(logp), logp


class LoopedLM(ModelBase):
    batch_size = 2
    epochs = 1
    n_subb = 1
    learning_rate = 3e-4
    optimizer = "adam"
    weight_decay = 0.0
    vocab = 128
    d_model = 64
    n_head = 2
    n_layer = 2
    d_ff = 128
    seq_len = 32
    loop_steps = 4          # R: times the stack is applied
    rope_theta = 1e6
    norm_eps = 1e-6
    exit_beta = 0.1         # weight of the exit distribution's entropy
    head_block = 2048       # tokens whose logits exist at once
    head_std = 0.005        # the head's initial scale: logits of 0.005 *
                            # sqrt(d) on unit-RMS states start the cost
                            # within a few hundredths of ln(vocab)

    def build_model(self) -> None:
        cfg = self.config
        self.cd = cd = cfg.get("compute_dtype", jnp.bfloat16)
        for k in ("vocab", "d_model", "n_head", "n_layer", "d_ff", "seq_len",
                  "loop_steps"):
            if k in cfg:
                setattr(self, k, int(cfg[k]))
        for k in ("rope_theta", "norm_eps", "exit_beta"):
            if k in cfg:
                setattr(self, k, float(cfg[k]))
        # the table is read in float32: the residual stream starts there
        self.embed = L.Embedding(self.vocab, self.d_model,
                                 compute_dtype=jnp.float32)
        self.blocks = [SandwichBlock(
            self.d_model, self.n_head, self.d_ff, self.rope_theta,
            self.norm_eps, cd, str(cfg.get("attn_impl", "reference")),
            name=f"block{i}")
            for i in range(self.n_layer)]
        self.norm_f = L.RMSNorm(self.d_model, self.norm_eps, name="norm_f")
        self.data = UniformTokens(cfg, self.batch_size)
        self._counted = False

    def init_params(self, key):
        ks = jax.random.split(key, len(self.blocks) + 3)
        d, v = self.d_model, self.vocab
        p = {"embed": self.embed.init(ks[0]),
             "norm_f": self.norm_f.init(None),
             "head": {"w": L.init_weight(ks[1], (d, v),
                                         ("normal", self.head_std))},
             "gate": {"w": L.init_weight(ks[2], (d,), ("normal", 0.02)),
                      "b": jnp.zeros((1,))}}
        for blk, k in zip(self.blocks, ks[3:]):
            p[blk.name] = blk.init(k)
        return p

    def init_bn_state(self):
        return {}

    # -- the looped stack ------------------------------------------------------

    def hidden_states(self, params, x, train: bool):
        """``[R, B, T, d]``: the normed state after each loop step."""

        def stack(h):
            for blk in self.blocks:
                run = lambda p, h, _b=blk: _b.apply(p, h, train=train)  # noqa: E731,E501
                if train:       # one saved residual a layer application
                    run = jax.checkpoint(run)
                with jax.named_scope(blk.name):
                    h = run(params[blk.name], h)
            return self.norm_f.apply(params["norm_f"], h)

        h0 = self.embed.apply(params["embed"], x)
        with jax.named_scope("ut_loop"):

            def body(h, _):
                h = stack(h)
                return h, h

            _, hs = lax.scan(body, h0, None, length=self.loop_steps)
        return hs

    def _logits(self, params, h):
        cd = self.cd
        return jnp.dot(h.astype(cd), params["head"]["w"].astype(cd),
                       preferred_element_type=jnp.float32)

    def apply_model(self, params, x, *, train, rng, state):
        """Evaluation reads the last loop step: ``logits^R``."""
        hs = self.hidden_states(params, x, train)
        with jax.named_scope("exit_head"):
            return self._logits(params, hs[-1]), state

    # -- heads, gate, objective ---------------------------------------------------

    def exit_losses(self, params, hs, y):
        """``(objective, cross-entropy of each loop step [R], top-1 error of
        the last, exit distribution [R, N])`` over ``N`` tokens.  The exit
        distribution comes from the gate alone, so it is made first and
        handed to the head as each token's weight at each loop step."""
        r, n, d = hs.shape
        with jax.named_scope("exit_head"):
            z = jnp.einsum("rnd,d->rn", hs, params["gate"]["w"]) \
                + params["gate"]["b"]
            p, logp = exit_distribution(z)
            expected, ce, miss = L.weighted_cross_entropy(
                params["head"]["w"], hs.reshape(r * n, d), jnp.tile(y, r),
                (p / n).reshape(r * n), block=self.head_block,
                compute_dtype=self.cd)
            entropy = -jnp.sum(p * logp, axis=0)
            cost = expected - self.exit_beta * jnp.mean(entropy)
            return cost, jnp.mean(ce.reshape(r, n), axis=1), \
                jnp.mean(miss[-n:]), p

    def _count_once(self, rows: int) -> None:
        if not self._counted:
            self._counted = True
            r = self.loop_steps
            telemetry.count("model.loop_steps", r)
            telemetry.count("model.layer_applications", r * self.n_layer)
            tokens = r * rows * self.seq_len
            telemetry.count("model.head_tokens", tokens)
            telemetry.count("model.head_logit_products",
                            L.head_logit_products(tokens, self.head_block))
            telemetry.count("model.attn_kernel_applications", r * sum(
                b.attn.attn_impl == "flash" for b in self.blocks))

    def loss_and_metrics(self, params, bn_state, batch, rng, train):
        x, y = batch["x"], batch["y"]
        if train:
            self._count_once(x.shape[0])
        hs = self.hidden_states(params, x, train)
        r = hs.shape[0]
        cost, _, err, _ = self.exit_losses(
            params, hs.reshape(r, -1, self.d_model), y.reshape(-1))
        return cost, (err, bn_state)

    def val_metrics(self, params, bn_state, batch):
        logits, _ = self.apply_model(params, batch["x"], train=False,
                                     rng=None, state=bn_state)
        flat, y = logits.reshape(-1, self.vocab), batch["y"].reshape(-1)
        return L.softmax_cross_entropy(flat, y), (
            L.errors(flat, y), L.errors_top_x(flat, y, 5))
