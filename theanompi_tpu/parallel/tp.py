"""Tensor (model) parallelism over a second mesh axis.

Beyond-parity capability (the reference — Theano-MPI, SURVEY.md §1 — is pure
data parallelism): Megatron-style intra-layer model parallelism for the
transformer family, composed with every data-parallel rule on a 2-D
``('workers', 'model')`` mesh.

Design (the scaling-book recipe, done manually inside ``shard_map``):

* **Column-parallel** linear: weight sharded on the OUTPUT dim
  (``P(None, 'model')``), bias sharded with it.  The local matmul needs no
  communication; activations come out sharded on the feature dim.  A plain
  :class:`..models.layers.FC` applied to the local shard IS the
  column-parallel layer — only the PartitionSpec differs.
* **Row-parallel** linear (:class:`RowFC`): weight sharded on the INPUT dim
  (``P('model', None)``); each shard computes a partial product which is
  ``psum``'d over ``'model'`` BEFORE the (replicated) bias is added.
* **Attention** (:class:`TPMultiHeadAttention`): QKV projections
  column-parallel → each shard owns ``n_head/tp`` complete heads; the output
  projection is row-parallel.  One ``psum`` per attention block.
* **Embedding** (:class:`VocabParallelEmbedding`): vocabulary sharded; out-of
  -shard ids contribute zeros and one ``psum`` assembles the dense vectors.
* **Vocab-parallel loss** (:func:`tp_softmax_cross_entropy`): the LM head is
  column-parallel over the vocab, and cross-entropy works on the SHARDED
  logits — a ``psum`` of shard-local sum-exp and label log-likelihood instead
  of materializing (or gathering) the full ``[B·T, V]`` logits.  At real
  vocab sizes this is the difference between the head being free and the head
  being the memory high-water mark.

Gradient correctness falls out of shard_map's varying-axes type system: the
step state is "boxed" (varying over ``'workers'``), sharded leaves are varying
over ``'model'`` too, and autodiff inserts the transpose-psums for
replicated-over-'model' leaves (LayerNorms, row-parallel biases)
automatically — verified against a dense oracle in ``tests/test_tp.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..models import layers as L

MODEL_AXIS = "model"


@partial(jax.custom_jvp, nondiff_argnums=(1,))
def pmax_sg(x, axis_name):
    """``lax.pmax`` with a zero tangent.

    Used for the max-subtraction in the sharded log-sum-exp, where the true
    gradient contribution cancels exactly (same reason plain logsumexp may
    stop-gradient its max) — and ``pmax`` has no differentiation rule anyway.
    Output is vma-INVARIANT over ``axis_name``, which is what keeps the whole
    loss invariant and the transpose-psums correct.
    """
    return lax.pmax(x, axis_name)


@pmax_sg.defjvp
def _pmax_sg_jvp(axis_name, primals, tangents):
    (x,) = primals
    out = lax.pmax(x, axis_name)
    return out, jnp.zeros_like(out)


# ---------------------------------------------------------------------------
# TP layers (local-shard apply; global-shape init, sharded at placement)
# ---------------------------------------------------------------------------

class RowFC(L.FC):
    """Row-parallel linear: partial products ``psum``'d before the bias.

    ``init`` returns the GLOBAL weight; the per-leaf PartitionSpec
    ``P('model', None)`` (see :func:`fc_row_spec`) makes shard_map hand
    ``apply`` the local ``[n_in/tp, n_out]`` slice.
    """

    def __init__(self, *args, axis: str = MODEL_AXIS, **kwargs):
        super().__init__(*args, **kwargs)
        self.axis = axis

    def pre_activation(self, params, x):
        cd = self.compute_dtype
        y = jnp.dot(x.astype(cd), params["w"].astype(cd))
        return lax.psum(y, self.axis) + params["b"].astype(cd)


class TPMultiHeadAttention(L.MultiHeadAttention):
    """Head-sharded attention: ``n_head/tp`` complete heads per shard.

    QKV are column-parallel (no comm), the output projection is row-parallel
    (one ``psum``).  Same math and init as the dense layer — pinned equal in
    ``tests/test_tp.py``.
    """

    def __init__(self, dim, n_head, tp: int, causal: bool = True,
                 axis: str = MODEL_AXIS, **kwargs):
        super().__init__(dim, n_head, causal=causal, **kwargs)
        assert n_head % tp == 0, f"n_head={n_head} not divisible by tp={tp}"
        assert dim % tp == 0, f"dim={dim} not divisible by tp={tp}"
        self.tp = tp
        self.axis = axis

    def apply(self, params, x, *, train=False, rng=None, state=None):
        cd = self.compute_dtype
        b, t, d = x.shape
        h_loc = self.n_head // self.tp
        hd = self.dim // self.n_head
        d_loc = h_loc * hd
        xc = x.astype(cd)

        def proj(w):
            # local w slice is [d, d/tp] — a contiguous block of whole heads
            y = jnp.dot(xc, w.astype(cd))
            return y.reshape(b, t, h_loc, hd).transpose(0, 2, 1, 3)

        q, k, v = proj(params["wq"]), proj(params["wk"]), proj(params["wv"])
        o = self._attend(q, k, v)     # local heads, full sequence
        o = o.transpose(0, 2, 1, 3).reshape(b, t, d_loc)
        # output projection: local wo slice is [d/tp, d] (row-parallel)
        return lax.psum(jnp.dot(o.astype(cd), params["wo"].astype(cd)),
                        self.axis)


class VocabParallelEmbedding(L.Embedding):
    """Vocabulary-sharded embedding: out-of-shard ids contribute zeros; one
    ``psum`` assembles the dense vectors (Megatron's input embedding)."""

    def __init__(self, vocab, dim, tp: int, axis: str = MODEL_AXIS, **kwargs):
        super().__init__(vocab, dim, **kwargs)
        assert vocab % tp == 0, f"vocab={vocab} not divisible by tp={tp}"
        self.tp = tp
        self.axis = axis

    def apply(self, params, x, *, train=False, rng=None, state=None):
        w = params["w"]                      # local [vocab/tp, dim]
        v_loc = self.vocab // self.tp
        rank = lax.axis_index(self.axis)
        loc = x - rank * v_loc
        ok = (loc >= 0) & (loc < v_loc)
        rows = w[jnp.clip(loc, 0, v_loc - 1)]
        rows = jnp.where(ok[..., None], rows, 0.0)
        return lax.psum(rows, self.axis).astype(self.compute_dtype)


# ---------------------------------------------------------------------------
# vocab-parallel loss / metric heads (logits sharded [N, V/tp])
# ---------------------------------------------------------------------------

def tp_softmax_cross_entropy(local_logits, labels, axis: str = MODEL_AXIS,
                             label_smoothing: float = 0.0):
    """Mean NLL over VOCAB-SHARDED logits — never materializes ``[N, V]``.

    Shard-local sum-exp and label log-likelihood, one ``psum`` each; the max
    subtraction uses :func:`pmax_sg`.  ``label_smoothing`` mixes in the
    uniform term (its full-vocab logit mean is one more ``psum``).  Output
    is invariant over ``axis``.
    """
    l32 = local_logits.astype(jnp.float32)
    v_loc = l32.shape[-1]
    lmax = pmax_sg(jnp.max(l32, axis=-1), axis)
    z = lax.psum(jnp.sum(jnp.exp(l32 - lmax[:, None]), axis=-1), axis)
    logz = jnp.log(z) + lmax
    rank = lax.axis_index(axis)
    loc = labels - rank * v_loc
    ok = (loc >= 0) & (loc < v_loc)
    ll_loc = jnp.take_along_axis(
        l32, jnp.clip(loc, 0, v_loc - 1)[:, None], axis=-1)[:, 0]
    ll = lax.psum(jnp.where(ok, ll_loc, 0.0), axis)
    nll = jnp.mean(logz - ll)
    if label_smoothing:
        eps = float(label_smoothing)
        v_tot = v_loc * lax.psum(1, axis)
        mean_logit = lax.psum(jnp.sum(l32, axis=-1), axis) / v_tot
        return (1.0 - eps) * nll + eps * jnp.mean(logz - mean_logit)
    return nll


def tp_errors(local_logits, labels, axis: str = MODEL_AXIS):
    """Top-1 error over vocab-sharded logits: gather one (max, argmax) PAIR
    per shard (``[tp, N]``, not the logits) and pick the global winner."""
    v_loc = local_logits.shape[-1]
    rank = lax.axis_index(axis)
    l32 = local_logits.astype(jnp.float32)
    vals = lax.all_gather(jnp.max(l32, axis=-1), axis)            # [tp, N]
    args = lax.all_gather(jnp.argmax(l32, axis=-1) + rank * v_loc, axis)
    pred = jnp.take_along_axis(args, jnp.argmax(vals, axis=0)[None], 0)[0]
    err = jnp.mean((pred != labels).astype(jnp.float32))
    return lax.pmean(err, axis)       # values equal; pmean marks invariant


def tp_errors_top_x(local_logits, labels, x: int = 5,
                    axis: str = MODEL_AXIS):
    """Top-x error: shard-local top-x (clamped to the shard width), gathered
    ``[tp, N, x]`` and merged — ``tp·x`` candidates always cover the true
    global top-x."""
    v_loc = local_logits.shape[-1]
    x_loc = min(x, v_loc)
    rank = lax.axis_index(axis)
    l32 = local_logits.astype(jnp.float32)
    vals, idx = lax.top_k(l32, x_loc)
    vals = lax.all_gather(vals, axis)                    # [tp, N, x_loc]
    idx = lax.all_gather(idx + rank * v_loc, axis)
    n = l32.shape[0]
    vals = vals.transpose(1, 0, 2).reshape(n, -1)        # [N, tp·x_loc]
    idx = idx.transpose(1, 0, 2).reshape(n, -1)
    x_eff = min(x, vals.shape[-1])
    _, sel = lax.top_k(vals, x_eff)
    top = jnp.take_along_axis(idx, sel, axis=-1)
    hit = jnp.any(top == labels[:, None], axis=-1)
    err = jnp.mean((~hit).astype(jnp.float32))
    return lax.pmean(err, axis)
