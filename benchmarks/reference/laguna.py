"""Plain float32 reference of poolside's Laguna decoder (sizes from
https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json), held
as one chip's share: some of each layer's heads, some of its experts, a
slice of the vocabulary.

Written from the equations, ``jax.numpy`` only, every contraction at
``Precision.HIGHEST``, no import from the program:

    h^0 = E[x];   u = h + Attn_l(N1(h));   h' = u + FF_l(N2(u))
    N(x) = x / sqrt(mean(x^2) + eps) * scale
    Attn_l: q heads of 128 (as many as W_g has columns), k/v heads of 128
      (as many as W_k holds), query head i reads key/value head
      i // (Hq / Hkv); rotary on q and k; scores q.k / sqrt(128), causal,
      and in a sliding_attention layer 0 <= t - s < window; softmax;
      o_i <- sigmoid(x W_g)_i * o_i;  concat(o) W_o
    rotary: the first `rot` entries of a head in half-split pairs
      (x[i], x[i + rot/2]) turned by t * inv_i, the rest passed on;
      default: inv_i = theta^(-2i/rot); yarn: the transformers
      initialisation (see `yarn`), cos and sin times attention_factor
    FF_l = W_d(silu(W_g x) * W_u x)   where the layer holds `mlp`
    FF_l = Shared(x) + sum_{e chosen and held} w_e Expert_e(x)  where `moe`:
      p = softmax(x W_r) over every expert, the top_k largest chosen,
      w_e = scale * p_e / (sum of the chosen p); an expert chosen and not
      held adds nothing and its weight stays in the normalisation
    logits = W_head N_f(h^L);  train_loss = mean next-token cross-entropy

What the source does not settle is stated in the configuration file under
``assumed``.  The sizes the equations need beside the parameters' shapes
are keyword arguments whose defaults are the published ones and the cell's
share (the first experts); a test at another size passes its own.

So that float32 at ``HIGHEST`` fits beside the optimizer's state on one
chip, attention is taken a head at a time, the feed-forwards and the head
``AT_ONCE`` tokens at a time, the experts one at a time, and each of these
and each layer is made again in the backward pass (``jax.checkpoint``).
None of that changes a number.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PUBLISHED = {
    "head_dim": 128, "window": 512, "top_k": 10, "scale": 2.5, "eps": 1e-6,
    "first_held": 0,        # the held experts are first_held, first_held+1..
    "layer_types": ("full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention") * 12,
    "rope": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}}
AT_ONCE = 512         # tokens whose logits or feed-forward exist at once


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(scale)


def yarn(rot, theta, factor, original_max, beta_fast, beta_slow):
    """The ``rot / 2`` frequencies of YaRN as transformers initialises
    them: ``f_i = theta^(-2i/rot)``; ``inv_i = (f_i / factor)(1 - m_i) +
    f_i m_i`` with ``m_i = 1 - clip((i - lo) / (hi - lo), 0, 1)`` and
    ``lo, hi`` the floor and ceiling of ``rot ln(original_max / (beta 2
    pi)) / (2 ln theta)`` at ``beta_fast`` and ``beta_slow``, clipped to
    ``[0, rot - 1]``."""
    i = np.arange(rot // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / rot)
    at = [rot * math.log(original_max / (b * 2 * math.pi))
          / (2 * math.log(theta)) for b in (beta_fast, beta_slow)]
    lo, hi = max(math.floor(at[0]), 0), min(math.ceil(at[1]), rot - 1)
    m = 1.0 - np.clip((i - lo) / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    return f / factor * (1.0 - m) + f * m


def frequencies(rope, head_dim):
    """``(inv [rot / 2], factor)`` of one attention kind."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    if rope["rope_type"] == "yarn":
        return yarn(rot, rope["rope_theta"], rope["factor"],
                    rope["original_max_position_embeddings"],
                    rope["beta_fast"], rope["beta_slow"]), \
            rope["attention_factor"]
    return rope["rope_theta"] ** (
        -2.0 * np.arange(rot // 2, dtype=np.float64) / rot), 1.0


def rotate(x, inv, factor):
    """``[..., T, hd]``: the first ``2 len(inv)`` entries turned, the rest
    passed on."""
    t, half = x.shape[-2], len(inv)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def attention(p, x, kind, head_dim, window, rope):
    """Grouped-query attention of ``[B, T, d]`` with a per-head output
    gate, a head at a time."""
    b, t, _ = x.shape
    n_q, n_kv = p["wg"].shape[1], p["wk"].shape[1] // head_dim
    inv, factor = frequencies(rope[kind], head_dim)
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None]
    seen = back >= 0
    if kind == "sliding_attention":
        seen = seen & (back < window)

    def heads(name, n):        # [n, B, T, hd]
        y = jnp.dot(x, _f32(p[name]), precision=HIGHEST)
        return y.reshape(b, t, n, head_dim).transpose(2, 0, 1, 3)

    q, k, v = heads("wq", n_q), heads("wk", n_kv), heads("wv", n_kv)
    reads = np.arange(n_q) // (n_q // n_kv)     # the k/v head of each q head
    gate = jax.nn.sigmoid(jnp.dot(x, _f32(p["wg"]), precision=HIGHEST))

    @jax.checkpoint
    def one_head(qkv):
        q, k, v = qkv
        s = jnp.einsum("bqd,bkd->bqk", rotate(q, inv, factor),
                       rotate(k, inv, factor), precision=HIGHEST) \
            / np.sqrt(head_dim)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    o = lax.map(one_head, (q, k[reads], v[reads]))       # [n_q, B, T, hd]
    o = o.transpose(1, 2, 0, 3) * gate[..., None]
    return jnp.dot(o.reshape(b, t, n_q * head_dim), _f32(p["wo"]),
                   precision=HIGHEST)


def swiglu(wg, wu, wd, x):
    g = jnp.dot(x, _f32(wg), precision=HIGHEST)
    u = jnp.dot(x, _f32(wu), precision=HIGHEST)
    return jnp.dot(g * jax.nn.sigmoid(g) * u, _f32(wd), precision=HIGHEST)


def some_tokens_at_a_time(f, x):
    """``f`` over the rows of ``[N, ...]`` in blocks of ``AT_ONCE``."""
    rows = AT_ONCE if x.shape[0] % AT_ONCE == 0 else x.shape[0]
    y = lax.map(jax.checkpoint(f), x.reshape((-1, rows) + x.shape[1:]))
    return y.reshape((x.shape[0],) + y.shape[2:])


def gated_mlp(p, x):
    return some_tokens_at_a_time(
        functools.partial(swiglu, p["wg"], p["wu"], p["wd"]), x)


def routing(p, x, top_k, scale):
    """``[N, d]`` -> ``[N, E]``: each token's weight for every expert of
    the model, nought where the expert is not among its ``top_k``."""
    prob = jax.nn.softmax(jnp.dot(x, _f32(p["router"]), precision=HIGHEST),
                          axis=-1)
    top, chosen = lax.top_k(prob, top_k)
    top = scale * top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.zeros_like(prob).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(top)


def routed_part(p, x, top_k, scale, first_held):
    """``sum_{e chosen and held} w_e Expert_e(x)`` over ``[N, d]``: every
    token through every held expert, weighted (by nought where it did not
    choose it); the experts one after another."""
    held = p["experts"]["wg"].shape[0]
    w = routing(p, x, top_k, scale)[:, first_held:first_held + held]

    def add_expert(y, e):
        wg, wu, wd, we = e
        return y + we[:, None] * some_tokens_at_a_time(
            functools.partial(swiglu, wg, wu, wd), x), None

    experts = tuple(_f32(p["experts"][k]) for k in ("wg", "wu", "wd"))
    return lax.scan(jax.checkpoint(add_expert), jnp.zeros_like(x),
                    experts + (w.T,))[0]


def routed_mlp(p, x, top_k, scale, first_held):
    return gated_mlp(p["shared_expert"], x) \
        + routed_part(p, x, top_k, scale, first_held)


def layer(p, h, kind, head_dim, window, rope, top_k, scale, first_held, eps):
    b, t, d = h.shape
    u = h + attention(p["attn"], rms_norm(h, p["norm1"]["scale"], eps),
                      kind, head_dim, window, rope)
    x = rms_norm(u, p["norm2"]["scale"], eps).reshape(b * t, d)
    ff = gated_mlp(p["mlp"], x) if "mlp" in p \
        else routed_mlp(p["moe"], x, top_k, scale, first_held)
    return u + ff.reshape(b, t, d)


def final_state(params, x, layer_types, eps, **sizes):
    """Ids ``[B, T]`` -> ``[B, T, d]``: ``N_f(h^L)``."""
    n_layer = sum(k.startswith("block") for k in params)
    h = _f32(params["embed"]["w"])[x]
    for i in range(n_layer):
        h = jax.checkpoint(functools.partial(
            layer, kind=layer_types[i], eps=eps, **sizes))(
                params[f"block{i}"], h)
    return rms_norm(h, params["norm_f"]["scale"], eps)


def head(params, h):
    return jnp.dot(h, _f32(params["head"]["w"]), precision=HIGHEST)


def forward(params, x, **sizes):
    return head(params, final_state(params, x, **{**PUBLISHED, **sizes}))


def cross_entropy(logits, y):
    """Per token: ``log sum exp(logits) - logits[y]``."""
    top = logits.max(-1)
    return jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1)) + top \
        - logits[jnp.arange(logits.shape[0]), y]


def train_loss(params, x, y, **sizes):
    """Mean next-token cross-entropy over the vocabulary held."""
    h = final_state(params, x, **{**PUBLISHED, **sizes})
    d = h.shape[-1]
    y = y.reshape(-1)
    rows = AT_ONCE if y.size % AT_ONCE == 0 else y.size

    @jax.checkpoint
    def some_tokens(hy):
        return cross_entropy(head(params, hy[0]), hy[1])

    return jnp.mean(lax.map(some_tokens, (h.reshape(-1, rows, d),
                                          y.reshape(-1, rows))))


def batch(config: dict, rng: np.random.RandomState):
    """Ids uniform over the vocabulary held, one sequence of the cell's
    length, next-token targets."""
    wc = config["worker_config"]
    seq = rng.randint(0, int(wc["vocab"]),
                      (1, int(wc["seq_len"]) + 1)).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]
