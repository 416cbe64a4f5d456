"""The toy token model in plain float32 ``jax.numpy``: learned positions,
pre-LayerNorm blocks of causal multi-head attention and a top-k mixture of
two-layer ReLU experts (Switch / GShard routing: softmax router, the k
largest gates renormalised to sum 1), a final LayerNorm and a linear head.

Capacity: the configuration sets ``capacity_factor`` = experts / k, at which
every expert has a slot for every token and nothing drops, so the reference
routes without a capacity rule.  The auxiliary loss is Switch's, on the
first choice: E * sum_e f_e P_e, averaged over the routed blocks and added
to the cross-entropy with the coefficient ``moe_aux``.

No import from ``theanompi_tpu``; ``params`` is the system's own tree."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import plain_ops as ops

N_HEAD, TOP_K, MOE_AUX = 2, 2, 0.01      # as tests/.../configs/toy_lm.json
HIGHEST = ops.HIGHEST


def batch(config, rng):
    """Four sequences of ids and next-token targets, int32 ``[B, T]`` as the
    data object delivers them."""
    t, v = int(config["seq_len"]), int(config["vocab"])
    seq = rng.randint(0, v, (4, t + 1)).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


def layer_norm(p, x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def attention(p, x):
    b, t, d = x.shape
    hd = d // N_HEAD

    def heads(w):
        return jnp.dot(x, w, precision=HIGHEST).reshape(
            b, t, N_HEAD, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST) / hd ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    return jnp.dot(o.transpose(0, 2, 1, 3).reshape(b, t, d), p["wo"],
                   precision=HIGHEST)


def moe(p, x):
    """``(y, aux)``: every expert on every token, weighted by its gate (0
    for an expert the token was not sent to)."""
    n_exp = p["wg"].shape[-1]
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(jnp.dot(xf, p["wg"], precision=HIGHEST), -1)
    topv, topi = jax.lax.top_k(probs, TOP_K)
    topv = topv / topv.sum(-1, keepdims=True)
    chosen = jax.nn.one_hot(topi, n_exp)                      # [N, K, E]
    gate = (chosen * topv[..., None]).sum(1)                  # [N, E]
    h = jnp.maximum(jnp.einsum("nd,edf->enf", xf, p["w1"], precision=HIGHEST)
                    + p["b1"][:, None], 0.0)
    ye = jnp.einsum("enf,efd->end", h, p["w2"], precision=HIGHEST) \
        + p["b2"][:, None]
    y = jnp.einsum("end,ne->nd", ye, gate, precision=HIGHEST)
    aux = n_exp * jnp.sum(chosen[:, 0].mean(0) * probs.mean(0))
    return y.reshape(x.shape), aux


def _forward(params, x):
    t = x.shape[1]
    h = params["embed"]["w"][x] + params["pos"]["w"][jnp.arange(t)][None]
    auxes = []
    for name in sorted(k for k in params if k.startswith("block")):
        p = params[name]
        h = h + attention(p["attn"], layer_norm(p["ln1"], h))
        y, aux = moe(p["moe"], layer_norm(p["ln2"], h))
        h = h + y
        auxes.append(aux)
    h = layer_norm(params["ln_f"], h)
    logits = jnp.dot(h, params["head"]["w"], precision=HIGHEST) \
        + params["head"]["b"]
    return logits, sum(auxes) / len(auxes)


def forward(params, x):
    return _forward(params, x)[0]


def train_loss(params, x, y):
    logits, aux = _forward(params, x)
    return ops.softmax_loss(logits, y) + MOE_AUX * aux
