"""Plain float32 reference of Moonshot's Kimi Linear decoder (sizes from
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json,
the family described in arXiv:2510.26692), held as one chip's share: some
of each routed layer's experts, a slice of the vocabulary.

Written from the equations, ``jax.numpy`` only, every contraction at
``Precision.HIGHEST``, no import from the program:

    h^0 = E[x];   u = h + Op_l(N1(h));   h' = u + FF_l(N2(u))
    N(x) = x / sqrt(mean(x^2) + eps) * scale
    Op_l where the layer holds `kda` (Kimi Delta Attention), per head of 128:
      q, k, v = SiLU(Conv1d(x W_q)), SiLU(Conv1d(x W_k)), SiLU(Conv1d(x W_v)),
          depthwise, 4 taps, padding 3 on both sides, the first T outputs
      q <- q / |q| / sqrt(128);  k <- k / |k|
      a_t = -exp(A_log) softplus(x W_fa W_fb + b_dt);  beta_t = sigmoid(x W_b)
      S_t = (I - beta_t k_t k_t^T) Diag(exp a_t) S_{t-1} + beta_t k_t v_t^T,
      S_0 = 0, token by token;  o_t = S_t^T q_t
      Op = (N_128(o) * sigmoid(x W_ga W_gb)) W_o
    Op_l where it holds `mla` (latent attention, no rotary turn):
      q_h = (x W_q)_h (192 = 128 + 64);  [c | k^r] = x W_kva;  c <- N_512(c)
      [k^n_h | v_h] = (c W_kvb)_h;  k_h = [k^n_h | k^r]  (k^r shared)
      o_h = softmax_causal(q_h k_h^T / sqrt(192)) v_h;  Op = concat(o) W_o
    FF_l = W_d(silu(W_g x) * W_u x)   where the layer holds `mlp`
    FF_l = Shared(x) + sum_{e chosen and held} w_e Expert_e(x)   where `moe`:
      s = sigmoid(x W_r) over every expert; the top_k largest of s + b
      chosen (b the expert bias, nought); w_e = scale * s_e / (sum of the
      chosen s + 1e-6); an expert chosen and not held adds nothing and its
      weight stays in the normalisation
    logits = W_head N_f(h^L);  train_loss = mean next-token cross-entropy

What the source does not settle is stated in the configuration file under
``assumed``.  The sizes the equations need beside the parameters' shapes
are keyword arguments whose defaults are the published ones and the cell's
share (the first experts); a test at another size passes its own.

So that float32 at ``HIGHEST`` fits beside the optimizer's state on one
chip it is blocked as ``reference/laguna.py`` is, whose plain pieces it
takes (norm, SwiGLU, blocks of tokens, cross-entropy, the batch of uniform
ids), and ``reference/lfm2.py``'s sigmoid routing: the recurrence is a
``lax.scan`` over tokens made again in the backward pass a block of
``BLOCK`` tokens at a time, both operators a group of heads at a time
(``HEAD_GROUPS``), attention a head and ``AT_ONCE`` queries at a time,
the convolutions made again from their inputs, the feed-forwards and the
head ``AT_ONCE`` tokens at a time, the experts one at a time, each layer
made again in the backward pass.  None of that changes a number.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.laguna import (AT_ONCE, batch,  # noqa: F401
                                         cross_entropy, gated_mlp, rms_norm)
from benchmarks.reference.lfm2 import routed_part

HIGHEST = lax.Precision.HIGHEST
PUBLISHED = {"head_dim": 128, "nope": 128, "rope": 64, "v_dim": 128,
             "top_k": 8, "scale": 2.446, "eps": 1e-5,
             "first_held": 0}   # the held experts are first_held, ...+1..
BLOCK = 64            # tokens of the recurrence whose states exist at once
HEAD_GROUPS = 4       # of an operator's heads, taken one group at a time


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _dot(x, w):
    return jnp.dot(x, _f32(w), precision=HIGHEST)


@jax.checkpoint
def conv_silu(x, taps):
    """``SiLU`` of PyTorch's ``Conv1d(D, D, L, groups=D, padding=L - 1)``
    over ``[B, T, D]``, the first T outputs; ``taps [L, D]``.  By its
    definition, output ``t`` is ``sum_i taps_i x_{t - L + 1 + i}`` with
    noughts before the start.  Written as that sum and not as a grouped
    ``conv_general_dilated``: at ``HIGHEST`` the TPU compiler makes some
    20 MB of code for each grouped convolution and its gradient, and the
    step's executable has to fit the compile cache beside the program's."""
    n, t = taps.shape[0], x.shape[1]
    x = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    taps = _f32(taps)
    return jax.nn.silu(sum(x[:, i:i + t] * taps[i] for i in range(n)))


def delta_rule(q, k, v, a, beta):
    """``S_t = (I - beta_t k_t k_t^T) Diag(exp a_t) S_{t-1} + beta_t k_t
    v_t^T``, ``o_t = S_t^T q_t``, token by token: ``[B, T, H, d]`` and
    ``beta [B, T, H]`` -> ``[B, T, H, dv]``."""
    b, t, h, dk = q.shape
    rows = BLOCK if t % BLOCK == 0 else t

    def token(s, x):
        q, k, v, a, beta = x
        s = jnp.exp(a)[..., None] * s
        kv = jnp.einsum("bhk,bhkv->bhv", k, s, precision=HIGHEST)
        s = s + beta[..., None, None] * k[..., :, None] * (v - kv)[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q, s, precision=HIGHEST)

    @jax.checkpoint
    def some_tokens(s, xs):
        return lax.scan(token, s, xs)

    xs = tuple(x.swapaxes(0, 1).reshape(t // rows, rows, *x.shape[:1],
                                        *x.shape[2:])
               for x in (q, k, v, a, beta))
    _, o = lax.scan(some_tokens, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return o.reshape(t, b, h, -1).swapaxes(0, 1)


def _columns(w, groups):
    """``[..., n]`` -> ``[groups, ..., n / groups]``: heads' columns (or
    entries) in groups of consecutive heads."""
    w = _f32(w)
    return jnp.moveaxis(w.reshape(*w.shape[:-1], groups, -1), -2, 0)


def _rows(w, groups):
    """``[n, d]`` -> ``[groups, n / groups, d]``."""
    return _f32(w).reshape(groups, -1, w.shape[-1])


def _unit(y):
    return y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)


def kda(p, x, head_dim, eps):
    """The ``kda`` operator of ``[B, T, d]``, ``HEAD_GROUPS`` groups of
    heads one after another (their shares of ``W_o`` summed)."""
    b, t, d = x.shape
    h = p["A_log"].shape[0]
    g = HEAD_GROUPS if h % HEAD_GROUPS == 0 else 1
    fa, ga = _dot(x, p["w_fa"]), _dot(x, p["w_ga"])

    @jax.checkpoint
    def some_heads(y, w):
        wq, wk, wv, tq, tk, tv, wfb, bdt, alog, wbeta, wgb, wo = w
        n = alog.shape[0]

        def heads(wp, taps):
            return conv_silu(_dot(x, wp), taps).reshape(b, t, n, head_dim)

        a = -jnp.exp(alog)[:, None] * jax.nn.softplus(
            _dot(fa, wfb) + bdt).reshape(b, t, n, head_dim)
        o = delta_rule(_unit(heads(wq, tq)) / jnp.sqrt(head_dim),
                       _unit(heads(wk, tk)), heads(wv, tv), a,
                       jax.nn.sigmoid(_dot(x, wbeta)))
        o = rms_norm(o, p["o_norm"]["scale"], eps)
        gate = jax.nn.sigmoid(_dot(ga, wgb))
        return y + _dot(o.reshape(b, t, -1) * gate, wo), None

    ws = tuple(_columns(p[k], g) for k in (
        "wq", "wk", "wv", "q_conv", "k_conv", "v_conv", "w_fb", "b_dt",
        "A_log", "w_beta", "w_gb")) + (_rows(p["wo"], g),)
    return lax.scan(some_heads, jnp.zeros((b, t, d)), ws)[0]


def mla(p, x, nope, rope, v_dim, eps):
    """The ``mla`` operator of ``[B, T, d]``: softmax a head and
    ``AT_ONCE`` queries at a time, ``HEAD_GROUPS`` groups of heads one
    after another."""
    b, t, d = x.shape
    latent = p["w_kvb"].shape[0]
    h = p["w_kvb"].shape[1] // (nope + v_dim)
    g = HEAD_GROUPS if h % HEAD_GROUPS == 0 else 1
    ckr = _dot(x, p["w_kva"])
    c = rms_norm(ckr[..., :latent], p["kv_norm"]["scale"], eps)
    rows = AT_ONCE if t % AT_ONCE == 0 else t

    def one_head(qkv):
        q, k, v = qkv                                       # [B, T, hd]

        @jax.checkpoint
        def some_queries(qi):
            q, i = qi                                       # [B, rows, hd]
            s = jnp.einsum("bqd,bkd->bqk", q, k, precision=HIGHEST) \
                / jnp.sqrt(nope + rope)
            seen = (i * rows + jnp.arange(rows))[:, None] >= jnp.arange(t)
            s = jnp.where(seen, s, -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v,
                              precision=HIGHEST)

        o = lax.map(some_queries, (q.reshape(b, t // rows, rows, -1)
                                   .swapaxes(0, 1), jnp.arange(t // rows)))
        return o.swapaxes(0, 1).reshape(b, t, -1)

    @jax.checkpoint
    def some_heads(y, w):
        wq, wkvb, wo = w
        n = wq.shape[-1] // (nope + rope)
        q = _dot(x, wq).reshape(b, t, n, nope + rope)
        kv = _dot(c, wkvb).reshape(b, t, n, nope + v_dim)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            ckr[:, :, None, latent:], (b, t, n, rope))], -1)
        o = lax.map(jax.checkpoint(one_head), tuple(
            z.transpose(2, 0, 1, 3) for z in (q, k, kv[..., nope:])))
        return y + _dot(o.transpose(1, 2, 0, 3).reshape(b, t, -1), wo), None

    ws = (_columns(p["wq"], g), _columns(p["w_kvb"], g), _rows(p["wo"], g))
    return lax.scan(some_heads, jnp.zeros((b, t, d)), ws)[0]


def layer(p, h, bias, head_dim, nope, rope, v_dim, top_k, scale, first_held,
          eps):
    b, t, d = h.shape
    x = rms_norm(h, p["norm1"]["scale"], eps)
    u = h + (kda(p["kda"], x, head_dim, eps) if "kda" in p
             else mla(p["mla"], x, nope, rope, v_dim, eps))
    x = rms_norm(u, p["norm2"]["scale"], eps).reshape(b * t, d)
    ff = gated_mlp(p["mlp"], x) if "mlp" in p else \
        gated_mlp(p["moe"]["shared_expert"], x) + routed_part(
            p["moe"], x, bias, top_k, scale, first_held)
    return u + ff.reshape(b, t, d)


def final_state(params, x, bias, eps, **sizes):
    """Ids ``[B, T]`` -> ``[B, T, d]``: ``N_f(h^L)``."""
    n_layer = sum(k.startswith("block") for k in params)
    h = _f32(params["embed"]["w"])[x]
    for i in range(n_layer):
        p = params[f"block{i}"]
        b = None if "moe" not in p else _f32(
            (bias or {}).get(f"block{i}",
                             jnp.zeros(p["moe"]["router"].shape[1])))
        h = jax.checkpoint(functools.partial(layer, eps=eps, **sizes))(
            p, h, b)
    return rms_norm(h, params["norm_f"]["scale"], eps)


def head(params, h):
    return _dot(h, params["head"]["w"])


def forward(params, x, bias=None, **sizes):
    return head(params, final_state(params, x, bias,
                                    **{**PUBLISHED, **sizes}))


def train_loss(params, x, y, bias=None, **sizes):
    """Mean next-token cross-entropy over the vocabulary held."""
    h = final_state(params, x, bias, **{**PUBLISHED, **sizes})
    d = h.shape[-1]
    y = y.reshape(-1)
    rows = AT_ONCE if y.size % AT_ONCE == 0 else y.size

    @jax.checkpoint
    def some_tokens(hy):
        return cross_entropy(head(params, hy[0]), hy[1])

    return jnp.mean(lax.map(some_tokens, (h.reshape(-1, rows, d),
                                          y.reshape(-1, rows))))
