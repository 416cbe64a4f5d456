"""Plain float32 reference of the looped language model of "Scaling Latent
Reasoning via Looped Language Models" (arXiv:2510.25741; sizes from
https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json).

Written from the equations, ``jax.numpy`` only, every contraction at
``Precision.HIGHEST``, no import from the program:

    h^0 = E[x];   h^t = Norm_f(Stack(h^{t-1})),  t = 1..R, one set of weights
    layer:  u = h + N2(Attn(N1(h)));  h' = u + N4(MLP(N3(u)))
    Norm(x) = x / sqrt(mean(x^2) + eps) * scale
    Attn: heads of 128, rotary on q and k (half-split pairs), causal softmax
    MLP(x) = W_d(silu(W_g x) * W_u x)
    logits^t = W_head h^t;   lambda_t = sigmoid(w . h^t + b)
    p_t = lambda_t prod_{j<t}(1 - lambda_j)  (t < R),  p_R = prod_{j<R}(1 - lambda_j)
    train_loss = mean over tokens of  sum_t p_t CE(logits^t, y) - beta H(p)

``forward`` gives ``logits^R``, what evaluation reads.  What the source does
not settle is stated in the configuration file under ``assumed``.

The sizes the equations need beside the parameters' shapes (heads, loop
steps, rotary base, epsilon, beta) are keyword arguments whose defaults are
the published ones; a test at another size passes its own.

So that float32 at ``HIGHEST`` fits beside the optimizer's state on one
chip, attention is taken a head at a time and the heads 512 tokens at a
time, and each of these and each layer application is made again in the
backward pass (``jax.checkpoint``).  None of that changes a number.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PUBLISHED = {"n_head": 16, "loops": 4, "theta": 1e6, "eps": 1e-6}
BETA = 0.1
AT_ONCE = 512         # tokens whose logits exist at once in train_loss


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(scale)


def rotate(x, theta):
    """``[..., T, hd]``: pair ``(x[i], x[i + hd/2])`` turned by ``t * theta
    ** (-2i / hd)`` at position ``t``."""
    t, hd = x.shape[-2:]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)[None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)],
                           axis=-1)


def attention(p, x, n_head, theta):
    """Causal softmax attention of ``[B, T, d]``, a head at a time."""
    b, t, d = x.shape
    hd = d // n_head

    def heads(name):        # [H, B, T, hd]
        y = jnp.dot(x, _f32(p[name]), precision=HIGHEST)
        return y.reshape(b, t, n_head, hd).transpose(2, 0, 1, 3)

    @jax.checkpoint
    def one_head(qkv):
        q, k, v = qkv
        s = jnp.einsum("bqd,bkd->bqk", rotate(q, theta), rotate(k, theta),
                       precision=HIGHEST) / np.sqrt(hd)
        s = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None], s,
                      -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    o = lax.map(one_head, (heads("wq"), heads("wk"), heads("wv")))
    return jnp.dot(o.transpose(1, 2, 0, 3).reshape(b, t, d), _f32(p["wo"]),
                   precision=HIGHEST)


def gated_mlp(p, x):
    g = jnp.dot(x, _f32(p["wg"]), precision=HIGHEST)
    u = jnp.dot(x, _f32(p["wu"]), precision=HIGHEST)
    return jnp.dot(g * jax.nn.sigmoid(g) * u, _f32(p["wd"]),
                   precision=HIGHEST)


def layer(p, h, n_head, theta, eps):
    u = h + rms_norm(attention(p["attn"], rms_norm(h, p["norm1"]["scale"],
                                                   eps), n_head, theta),
                     p["norm2"]["scale"], eps)
    return u + rms_norm(gated_mlp(p["mlp"], rms_norm(u, p["norm3"]["scale"],
                                                     eps)),
                        p["norm4"]["scale"], eps)


def loop_states(params, x, n_head, loops, theta, eps):
    """Ids ``[B, T]`` -> ``[R, B, T, d]``: h^1 .. h^R."""
    n_layer = sum(k.startswith("block") for k in params)
    run = jax.checkpoint(functools.partial(layer, n_head=n_head, theta=theta,
                                           eps=eps))

    def loop_step(h, _):
        for i in range(n_layer):
            h = run(params[f"block{i}"], h)
        h = rms_norm(h, params["norm_f"]["scale"], eps)
        return h, h

    return lax.scan(loop_step, _f32(params["embed"]["w"])[x], None,
                    length=loops)[1]


def head(params, h):
    return jnp.dot(h, _f32(params["head"]["w"]), precision=HIGHEST)


def loop_logits(params, x, **sizes):
    """``[B, T]`` ids -> ``[R, B, T, V]``: the logits after every loop step."""
    return head(params, loop_states(params, x, **{**PUBLISHED, **sizes}))


def forward(params, x, **sizes):
    """Evaluation (``early_exit_threshold`` 1: no early exit): ``logits^R``."""
    return head(params, loop_states(params, x, **{**PUBLISHED, **sizes})[-1])


def exit_distribution(lam):
    """``[R, N]`` gate values -> ``p``: leave at step ``t < R`` with
    ``lambda_t`` if still there, at step ``R`` otherwise."""
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)
    still = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]])
    return jnp.concatenate([lam[:-1] * still, stay[-1:]])


def cross_entropy(logits, y):
    """Per token: ``log sum exp(logits) - logits[y]``."""
    top = logits.max(-1)
    return jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1)) + top \
        - logits[jnp.arange(logits.shape[0]), y]


def train_loss(params, x, y, beta=BETA, **sizes):
    """The training objective (the paper's first stage): the mean over all
    tokens of ``sum_t p_t CE_t - beta H(p)``."""
    states = loop_states(params, x, **{**PUBLISHED, **sizes})
    r, d = states.shape[0], states.shape[-1]
    y = y.reshape(-1)
    rows = AT_ONCE if y.size % AT_ONCE == 0 else y.size

    @jax.checkpoint
    def some_tokens(hy):
        return cross_entropy(head(params, hy[0]), hy[1])

    def every_token(h):     # [N, d] -> [N]
        return lax.map(some_tokens, (h.reshape(-1, rows, d),
                                     y.reshape(-1, rows))).reshape(-1)

    states = states.reshape(r, -1, d)
    ce = jnp.stack([every_token(h) for h in states])
    lam = jax.nn.sigmoid(jnp.dot(states, _f32(params["gate"]["w"]),
                                 precision=HIGHEST)
                         + _f32(params["gate"]["b"]))
    p = exit_distribution(lam)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy)


def batch(config: dict, rng: np.random.RandomState):
    """Ids uniform over the vocabulary, two sequences of the cell's length,
    next-token targets."""
    wc = config["worker_config"]
    seq = rng.randint(0, int(wc["vocab"]),
                      (2, int(wc["seq_len"]) + 1)).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]
