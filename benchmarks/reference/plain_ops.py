"""Plain float32 building blocks for the reference forward passes.

Written from the papers' descriptions with ``jax.numpy`` / ``lax`` only: no
bf16, no code from ``theanompi_tpu.models``, every contraction at
``Precision.HIGHEST`` (on a TPU a float32 matmul otherwise runs as one bf16
pass).  Grouped convolution is done by splitting the channels and pooling by
shifted slices, so that neither leans on the option the program uses
(``feature_group_count``, ``reduce_window``).

Layout follows the program's documented choice: NHWC activations, HWIO
kernels, and the flatten before the first FC layer runs over (H, W, C).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def conv_relu(x, p, stride=1, pad=0, groups=1):
    w = jnp.asarray(p["w"], jnp.float32)
    xs = jnp.split(jnp.asarray(x, jnp.float32), groups, axis=-1)
    ws = jnp.split(w, groups, axis=-1)
    ys = [lax.conv_general_dilated(
        xg, wg, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        for xg, wg in zip(xs, ws)]
    y = jnp.concatenate(ys, axis=-1) + jnp.asarray(p["b"], jnp.float32)
    return jnp.maximum(y, 0.0)


def max_pool(x, size, stride):
    out = (x.shape[1] - size) // stride + 1
    y = None
    for dy in range(size):
        for dx in range(size):
            win = x[:, dy:dy + stride * (out - 1) + 1:stride,
                    dx:dx + stride * (out - 1) + 1:stride, :]
            y = win if y is None else jnp.maximum(y, win)
    return y


def lrn(x, n=5, k=2.0, alpha=1e-4, beta=0.75):
    """Cross-channel response normalisation, b = a / (k + alpha/n * sum a^2)
    ** beta over the n channels centred on each one (clipped at the ends).
    The division of alpha by n is the program's documented choice, shared
    with Caffe and theano_alexnet; the paper's formula has alpha alone."""
    half = n // 2
    sq = jnp.pad(jnp.square(x), ((0, 0), (0, 0), (0, 0), (half, half)))
    c = x.shape[-1]
    ssum = sum(sq[..., i:i + c] for i in range(n))
    return x / jnp.power(k + (alpha / n) * ssum, beta)


def fc(x, p, relu=True):
    y = jnp.dot(x, jnp.asarray(p["w"], jnp.float32), precision=HIGHEST) \
        + jnp.asarray(p["b"], jnp.float32)
    return jnp.maximum(y, 0.0) if relu else y


def softmax_loss(logits, labels):
    """Mean negative log-likelihood of integer ``labels``.  Logits of any
    rank: the leading axes are flattened (``[B, T, V]`` with ``[B, T]``
    labels is the mean over all ``B * T`` positions)."""
    logits = logits.reshape(-1, logits.shape[-1])
    labels = labels.reshape(-1)
    logz = jnp.log(jnp.sum(jnp.exp(logits - logits.max(-1, keepdims=True)),
                           axis=-1)) + logits.max(-1)
    picked = logits[jnp.arange(logits.shape[0]), labels]
    return jnp.mean(logz - picked)
