"""Mixture-of-Experts with expert parallelism over the ``'model'`` axis.

Beyond-parity capability (the reference — Theano-MPI, SURVEY.md §1 — is pure
data parallelism): a Switch-Transformer-style top-1 MoE FFN whose experts are
SHARDED over the ``'model'`` mesh axis — each chip in a tensor-parallel group
hosts ``E/ep`` complete experts, so the FFN parameter count scales with the
mesh while per-chip compute stays flat.

TPU-first mapping (the Mesh-TensorFlow / Switch einsum formulation):

* routing, capacity masking, and the dispatch one-hot ``[N, E, C]`` are
  computed from REPLICATED activations (identical on every chip of the tp
  group) — no all-to-all is needed: each chip slices ITS experts' columns of
  the dispatch tensor, gathers its tokens with one einsum (an MXU matmul, no
  ragged scatter), runs its experts batched, and one ``psum`` over
  ``'model'`` assembles the combined output.  Static shapes throughout —
  over-capacity tokens are dropped (they ride the residual connection), the
  standard Switch behavior.
* the load-balance auxiliary loss is the Switch one: ``E · Σ_e f_e · P_e``
  (``f_e`` = fraction of tokens routed to expert e, ``P_e`` = mean router
  probability), 1.0 at perfectly uniform routing.

``ep == 1`` (no ``'model'`` axis) runs the identical math without the slice
and psum — pinned equal to a dense MLP when all experts share weights
(``tests/test_moe.py``).

Round-4, sequence-sharded tokens (``seq_shards > 1``):

* with ``ep == 1`` the experts shard over the **'seq'** axis instead and
  tokens travel by ALL-TO-ALL: each shard routes its local block, gathers
  per-expert slots ``[E, C, d]``, one ``lax.all_to_all`` ships each expert
  group to its owner (which batches S sources' slots through its experts),
  and a second all-to-all returns them for the local combine — the classic
  distributed-Switch dispatch, static shapes throughout.  Capacity is per
  SOURCE shard (S·C total per expert); drop-free capacities reproduce the
  dense math exactly (layer-pinned).
* with ``ep > 1`` (sp×tp) the experts stay on 'model' — activations are
  replicated over that axis, so the existing slice+psum path runs on the
  local token block unchanged.
* the load-balance statistic averages the per-shard token means BEFORE the
  ``Σ f_e·P_e`` product (``pmean`` over 'seq') — the EXACT global aux, not
  the noisier mean-of-products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models import layers as L
from .mesh import MODEL_AXIS


class MoE(L.Layer):
    """Top-k mixture of 2-layer MLP experts, optionally expert-parallel
    over ``'model'``.  ``top_k=1`` (default) is the Switch formulation;
    ``top_k=2`` the GShard one — the k selected gates renormalize to sum
    1, and choice ranks claim capacity slots in priority order (every
    token's primary route before any secondary).

    ``apply`` returns ``(y, aux)`` — the combined output and the scalar load
    -balance loss — so callers must unpack (the transformer block does).
    """

    has_state = False

    def __init__(self, dim, n_experts, mlp_ratio=4, ep: int = 1,
                 capacity_factor: float = 1.25, w_init=("normal", 0.02),
                 compute_dtype=jnp.bfloat16, axis: str = MODEL_AXIS,
                 seq_shards: int = 1, seq_axis: str = None,
                 top_k: int = 1, name: str = "moe"):
        assert n_experts % ep == 0, \
            f"n_experts={n_experts} not divisible by ep={ep}"
        assert 1 <= int(top_k) <= n_experts, (top_k, n_experts)
        self.top_k = int(top_k)
        if seq_shards > 1 and ep == 1:
            # experts shard over the SEQUENCE axis: the all-to-all dispatch
            assert n_experts % seq_shards == 0, (
                f"n_experts={n_experts} not divisible by sp={seq_shards}")
        self.dim, self.n_experts, self.hidden = dim, n_experts, mlp_ratio * dim
        self.ep = ep
        self.seq_shards = int(seq_shards)
        if seq_axis is None:
            from .mesh import SEQ_AXIS
            seq_axis = SEQ_AXIS
        self.seq_axis = seq_axis
        self.capacity_factor = float(capacity_factor)
        self.w_init = w_init
        self.compute_dtype = compute_dtype
        self.axis = axis
        self.name = name

    def init(self, key):
        kg, k1, k2 = jax.random.split(key, 3)
        E, d, f = self.n_experts, self.dim, self.hidden
        return {
            "wg": L.init_weight(kg, (d, E), self.w_init),
            "w1": L.init_weight(k1, (E, d, f), self.w_init),
            "b1": jnp.zeros((E, f)),
            "w2": L.init_weight(k2, (E, f, d), self.w_init),
            "b2": jnp.zeros((E, d)),
        }

    def specs(self):
        """Per-leaf PartitionSpecs: router replicated, experts sharded on
        their leading (expert) dim — over ``'model'`` (ep) or over
        ``'seq'`` (the sp all-to-all mode).  None when unsharded."""
        from jax.sharding import PartitionSpec as P
        if self.ep > 1:
            M = self.axis
        elif self.seq_shards > 1:
            M = self.seq_axis
        else:
            return None
        return {"wg": P(), "w1": P(M, None, None), "b1": P(M, None),
                "w2": P(M, None, None), "b2": P(M, None)}

    def capacity(self, n_tokens: int, train: bool = True) -> int:
        """Per-expert token slots.  Training uses the Switch capacity bound
        (over-capacity tokens drop to the residual — the load-balance
        pressure); inference is DROP-FREE (capacity = n): dropping at eval
        only hurts, and it keeps the KV-decode sampler (which routes one
        step's tokens at a time) exactly consistent with the full-forward
        one (which routes the whole buffer)."""
        if not train:
            return max(1, n_tokens)
        # top_k routes k·n assignments over E experts — capacity scales
        # with k (GShard), else secondaries would drop even at perfect
        # balance
        return max(1, int(np.ceil(
            n_tokens * self.top_k / self.n_experts * self.capacity_factor)))

    def apply(self, params, x, *, train=False, rng=None, state=None):
        cd = self.compute_dtype
        shape = x.shape
        d, E = self.dim, self.n_experts
        xf = x.reshape(-1, d)
        n = xf.shape[0]
        C = self.capacity(n, train)

        # -- routing (fp32, replicated over the model axis) ---------------
        logits = jnp.dot(xf.astype(jnp.float32),
                         params["wg"].astype(jnp.float32))       # [N, E]
        probs = jax.nn.softmax(logits, axis=-1)
        K = self.top_k
        topv, topi = lax.top_k(probs, K)                         # [N, K]
        if K > 1:
            # GShard-style: the k selected gates renormalize to sum 1
            topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
        assigns = [jax.nn.one_hot(topi[:, j], E, dtype=jnp.float32)
                   for j in range(K)]                            # k × [N, E]

        # Switch aux loss on the PRIMARY assignment: E · Σ_e f_e · P_e
        # (1.0 at uniform routing)
        f_e = jnp.mean(assigns[0], axis=0)
        p_e = jnp.mean(probs, axis=0)
        if self.seq_shards > 1:
            # EXACT global routing fractions: average the per-shard token
            # means BEFORE the product (mean-of-products would be a noisier
            # estimator and deviate from the dense objective)
            f_e = lax.pmean(f_e, self.seq_axis)
            p_e = lax.pmean(p_e, self.seq_axis)
        aux = E * jnp.sum(f_e * p_e)

        # -- capacity + dispatch one-hot [N, E, C] -------------------------
        # choice ranks claim slots in PRIORITY order (every token's primary
        # route before any secondary — GShard's ordering): rank j's
        # positions continue from the slots ranks < j actually kept
        disp = jnp.zeros((n, E, C), jnp.float32)
        comb_gate = jnp.zeros((n, E), jnp.float32)
        base = jnp.zeros((E,), jnp.float32)
        for j in range(K):
            a = assigns[j]
            pos = jnp.cumsum(a, axis=0) - 1.0 + base[None, :]    # [N, E]
            kept = (pos < C).astype(jnp.float32) * a
            disp = disp + kept[:, :, None] * jax.nn.one_hot(
                pos.astype(jnp.int32), C, dtype=jnp.float32)
            comb_gate = comb_gate + kept * topv[:, j:j + 1]
            base = base + jnp.sum(kept, axis=0)

        if self.ep == 1 and self.seq_shards > 1:
            y, aux = self._apply_seq_a2a(params, xf, disp, comb_gate, aux,
                                         C, cd)
            return y.reshape(shape).astype(x.dtype), aux

        # -- expert-parallel slice: my E/ep experts ------------------------
        e_loc = E // self.ep
        if self.ep > 1:
            rank = lax.axis_index(self.axis)
            disp = lax.dynamic_slice_in_dim(disp, rank * e_loc, e_loc, axis=1)
            comb_gate = lax.dynamic_slice_in_dim(
                comb_gate, rank * e_loc, e_loc, axis=1)
        w1, b1 = params["w1"], params["b1"]    # local [E/ep, ...] shards
        w2, b2 = params["w2"], params["b2"]

        # -- gather → batched expert MLP → combine (all MXU einsums) -------
        xe = jnp.einsum("nec,nd->ecd", disp.astype(cd), xf.astype(cd))
        h = jax.nn.relu(
            jnp.einsum("ecd,edf->ecf", xe, w1.astype(cd))
            + b1[:, None, :].astype(cd))
        ye = jnp.einsum("ecf,efd->ecd", h, w2.astype(cd)) \
            + b2[:, None, :].astype(cd)
        comb = (disp * comb_gate[:, :, None]).astype(cd)
        y = jnp.einsum("ecd,nec->nd", ye, comb)
        if self.ep > 1:
            y = lax.psum(y, self.axis)
            aux = lax.pmean(aux, self.axis)   # equal values; mark invariant
        return y.reshape(shape).astype(x.dtype), aux

    def _apply_seq_a2a(self, params, xf, disp, comb_gate, aux, C, cd):
        """Sequence-sharded expert parallelism: experts live on the 'seq'
        shards, so each chip's locally-routed tokens travel to their
        expert's chip with ONE ``lax.all_to_all`` (and return with one) —
        the classic distributed-Switch dispatch, static shapes throughout.

        Capacity accounting is per SOURCE shard (each of the S shards
        reserves C slots per expert from its own token block), so an expert
        processes up to S·C slots — the same total budget as the replicated
        path, with drops distributed per shard.  Drop-free capacities are
        exactly the dense math (tested).
        """
        S, E = self.seq_shards, self.n_experts
        e_loc = E // S
        d = self.dim
        # my tokens, gathered into per-expert slots: [E, C, d] → grouped by
        # owner shard [S, e_loc, C, d]; the a2a ships group s to shard s and
        # returns every shard's slots for MY experts (dim 0 = source shard)
        xe = jnp.einsum("nec,nd->ecd", disp.astype(cd), xf.astype(cd))
        xe = xe.reshape(S, e_loc, C, d)
        xe = lax.all_to_all(xe, self.seq_axis, split_axis=0, concat_axis=0)
        # batched local-expert MLP over all sources' slots
        w1, b1 = params["w1"], params["b1"]        # local [e_loc, ...]
        w2, b2 = params["w2"], params["b2"]
        h = jax.nn.relu(
            jnp.einsum("secd,edf->secf", xe, w1.astype(cd))
            + b1[None, :, None, :].astype(cd))
        ye = jnp.einsum("secf,efd->secd", h, w2.astype(cd)) \
            + b2[None, :, None, :].astype(cd)
        # return every source's slots, re-assemble my [E, C, d], combine
        ye = lax.all_to_all(ye, self.seq_axis, split_axis=0, concat_axis=0)
        ye = ye.reshape(E, C, d)
        comb = (disp * comb_gate[:, :, None]).astype(cd)
        y = jnp.einsum("ecd,nec->nd", ye, comb)
        return y, aux       # aux already global+invariant (pmean'd f/P)


class HeldExperts(L.Layer):
    """One chip's part of a routed feed-forward layer of gated (SwiGLU)
    experts, told which experts it holds (``held = (first, past)``, a
    contiguous range of the model's ``n_experts``):

        p = softmax(x W_r) over all n_experts;  the top_k largest chosen,
        w_e = scale * p_e / (sum of the chosen p)
        y = Shared(x) + sum over e chosen and held of w_e * Expert_e(x)

    The router keeps its whole width and its ``top_k``; an expert that is
    chosen and not held adds nothing here (its chip adds it) and its weight
    stays in the normalisation.  **Nothing is dropped, whatever the
    routing**: every (token, expert) pair whose expert is held is
    computed.  The pairs are sorted by expert; the static shape is the
    worst case, ``N * min(top_k, held)`` rows, walked a sixteenth at a
    time (gather, grouped products, weighted scatter-add): one stretch
    straight-line, added into the shared expert's result, then a loop as
    far as the last routed pair and no further, so the work follows the
    rows actually routed and the memory is one stretch's
    (``_routed_part``).  The expected routing, ``N * top_k * held /
    n_experts`` pairs, fits the first stretch of a chip that holds a
    small share of the experts (2,560 pairs in 4,096 rows at 8 of 256
    held and 10 a token): the loop is for routing that overflows it, and
    with no pair routed the one stretch runs with every row cut off and
    adds nought.  The experts' products are grouped
    matrix products over the stacked ``[held, d, width]`` weights
    (``lax.ragged_dot``); the backward pass is written out beside the
    forward one, and a stretch's weight gradients are its grouped
    products' float32 results, written once.  Router in float32 at the
    highest precision, so that a float32 reference chooses alike; matrix
    operands in ``compute_dtype``, sums in float32.

    With more than one chip in the expert group this layer would be
    followed by its exchange (a sum over the group of the routed parts,
    the shared expert counted once); on one chip it runs without.

    Beside :class:`MoE` and not in its place: that one is the Switch/GShard
    layer of ``MoETransformerLM`` (2-layer MLP experts, a capacity factor
    that drops, an auxiliary loss, whole-layer sharding over mesh axes),
    which its tests and the toy token cell keep.

    Scopes: ``<name>`` around all of it, ``router``, ``experts`` around
    the grouped products alone, ``shared_expert``."""

    def __init__(self, dim: int, n_experts: int, held, top_k: int,
                 width: int, shared_width: int = 0, scale: float = 1.0,
                 w_init=("normal", 0.02), compute_dtype=jnp.bfloat16,
                 name: str = "moe"):
        first, past = (int(i) for i in held)
        assert 0 <= first < past <= n_experts, (held, n_experts)
        assert 1 <= top_k <= n_experts, (top_k, n_experts)
        self.dim, self.n_experts, self.width = dim, n_experts, width
        self.first, self.n_held = first, past - first
        self.top_k, self.scale = int(top_k), float(scale)
        self.w_init = w_init
        self.compute_dtype = compute_dtype
        self.name = name
        self.shared = L.GatedMLP(dim, shared_width, w_init=w_init,
                                 compute_dtype=compute_dtype,
                                 name="shared_expert") \
            if shared_width else None

    def init(self, key):
        kr, kg, ku, kd, ks = jax.random.split(key, 5)
        d, f, h = self.dim, self.width, self.n_held
        p = {"router": L.init_weight(kr, (d, self.n_experts), self.w_init),
             "experts": {"wg": L.init_weight(kg, (h, d, f), self.w_init),
                         "wu": L.init_weight(ku, (h, d, f), self.w_init),
                         "wd": L.init_weight(kd, (h, f, d), self.w_init)}}
        if self.shared is not None:
            p["shared_expert"] = self.shared.init(ks)
        return p

    def route(self, params, xf):
        """``[N, d]`` -> the chosen experts ``[N, top_k]`` and their
        weights, normalised over the chosen and scaled."""
        with jax.named_scope("router"):
            logits = jnp.dot(xf.astype(jnp.float32),
                             params["router"].astype(jnp.float32),
                             precision=lax.Precision.HIGHEST)
            top, chosen = lax.top_k(jax.nn.softmax(logits, axis=-1),
                                    self.top_k)
            return chosen, self.scale * top / jnp.sum(top, axis=-1,
                                                      keepdims=True)

    def rows_at_once(self, n_tokens: int) -> int:
        """Rows of the sorted pairs that go through the experts at once: a
        sixteenth of the worst case, ``N * min(top_k, held)``."""
        return max(1, n_tokens * min(self.top_k, self.n_held) // 16)

    def apply(self, params, x, *, train=False, rng=None, state=None):
        with jax.named_scope(self.name):
            xf = x.reshape(-1, self.dim)
            chosen, weights = self.route(params, xf)
            # pairs (token, choice) by expert held; one not held sorts last
            local = chosen.reshape(-1) - self.first
            local = jnp.where((local >= 0) & (local < self.n_held), local,
                              self.n_held)
            order = jnp.argsort(local)
            ends = jnp.cumsum(jnp.sum(
                local[:, None] == jnp.arange(self.n_held)[None], axis=0,
                dtype=jnp.int32))
            # what the routed part is added into: the shared expert's
            # result where the layer has one
            base = jnp.zeros(xf.shape, jnp.float32) if self.shared is None \
                else self.shared.apply(params["shared_expert"],
                                       xf).astype(jnp.float32)
            y = _routed_part(self.rows_at_once(xf.shape[0]),
                             params["experts"], xf.astype(self.compute_dtype),
                             weights, order, ends, base)
            return y.reshape(x.shape[:-1] + (self.dim,))


# The held pairs' part of a routed layer, added into ``base`` (``[N, d]``
# float32: the shared expert's result, or nought):
# ``base[n] + sum_j weights[n, j] * Expert_{chosen[n, j]}(xf[n])`` over the
# pairs whose expert is held.  ``order`` sorts the pairs ``n * top_k + j`` by
# held expert, one not held last; expert ``e``'s rows of that order end at
# ``ends[e]``.  The sorted pairs are walked ``rows`` at a time: one stretch
# straight-line, then as far as the last routed pair and no further (a loop
# whose trip count is the step's own, and nought where the routed pairs fit
# the first stretch, as the expected routing does), forward and backward
# alike.  With no pair routed the first stretch runs with every row cut off
# and adds nought.  Reverse-mode differentiation cannot transpose such a
# loop, so the backward pass is written out beside the forward one.  It
# makes each stretch's first two products again; nothing but the arguments
# is kept.  A stretch's weight gradients are its grouped products' float32
# results as they are written: the first stretch's are the loop's first
# carry, so where the loop does not run nothing passes over an array of the
# stacked experts' shape but the product that writes it.

# an expert's weight gradient from its rows of two arrays: [rows, k] and
# [rows, n], grouped along the rows, give [held, k, n]
_WEIGHT_GRADIENT = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _stretch(rows, order, ends, top_k, i):
    """Stretch ``i``: its pairs, their tokens and choices, each expert's
    number of rows in it, and which rows are pairs at all."""
    start = i * rows
    pair = lax.dynamic_slice_in_dim(order, start, rows)
    upto = jnp.clip(ends - start, 0, rows)
    return pair // top_k, pair % top_k, jnp.diff(upto, prepend=0), \
        jnp.arange(rows) < upto[-1]


def _expert_rows(sizes, live, experts, xs, w):
    """``[rows, d]`` float32: row ``r``'s expert (by ``sizes``) applied to
    ``xs[r]``, times ``w[r]``; nought where ``r`` is no pair.  A grouped
    product leaves in a row of no group whatever it finds, and so does
    its transpose: such a row is cut off on the way in and on the way
    out (on the chip it is not nought: PERF.md section 6, PR 37)."""
    cd = xs.dtype
    xs = jnp.where(live[:, None], xs, 0)
    with jax.named_scope("experts"):
        g = lax.ragged_dot(xs, experts["wg"].astype(cd), sizes,
                           preferred_element_type=jnp.float32)
        u = lax.ragged_dot(xs, experts["wu"].astype(cd), sizes,
                           preferred_element_type=jnp.float32)
        ye = lax.ragged_dot((jax.nn.silu(g) * u).astype(cd),
                            experts["wd"].astype(cd), sizes,
                            preferred_element_type=jnp.float32)
    return jnp.where(live[:, None], ye * w[:, None], 0.0)


def _expert_rows_back(sizes, live, experts, turned, xs, w, dy):
    """The cotangents of :func:`_expert_rows` to ``experts``, ``xs`` and
    ``w`` at ``dy`` ``[rows, d]``, all float32; ``turned`` is
    :func:`_turned` of ``experts``.  ``g`` and ``u`` are made again,
    ``a = silu(g) * u``; with ``p[r] = dy[r] wd^T`` the weight's cotangent
    is ``<a[r], p[r]>`` (the third forward product is not needed for it)
    and ``a``'s is ``w[r] p[r]``.  Each weight gradient is one grouped
    product over the stretch's rows, returned as the product wrote it.
    Rows of no group are cut off on the way in, and what a product left
    in one (it need not be finite) before anything is multiplied by it or
    summed, so that neither operand of a weight gradient holds anything
    there."""
    cd = xs.dtype
    cut = lambda a: jnp.where(live[:, None], a, 0)          # noqa: E731
    rows_by = functools.partial(lax.ragged_dot, group_sizes=sizes,
                                preferred_element_type=jnp.float32)
    weight_gradient = functools.partial(
        lax.ragged_dot_general, group_sizes=sizes,
        ragged_dot_dimension_numbers=_WEIGHT_GRADIENT,
        preferred_element_type=jnp.float32)
    xs, dy = cut(xs), cut(dy).astype(cd)
    with jax.named_scope("experts"):
        g = rows_by(xs, experts["wg"].astype(cd))
        u = rows_by(xs, experts["wu"].astype(cd))
        p = cut(rows_by(dy, turned["wd"]))
    gate = jax.nn.sigmoid(g)
    silu = g * gate
    a = cut(silu * u)
    d_w = jnp.sum(a * p, axis=-1)
    d_a = p * w[:, None]
    d_g = cut(d_a * u * (gate + silu * (1 - gate))).astype(cd)
    d_u = cut(d_a * silu).astype(cd)
    with jax.named_scope("experts"):
        d_xs = rows_by(d_g, turned["wg"]) + rows_by(d_u, turned["wu"])
        d_experts = {"wg": weight_gradient(xs, d_g),
                     "wu": weight_gradient(xs, d_u),
                     "wd": weight_gradient((a * w[:, None]).astype(cd), dy)}
    return d_experts, cut(d_xs), d_w


def _turned(experts, cd, any_pair):
    """The experts' matrices in ``cd``, each transposed, for the backward
    products that take them so; nought where no pair is routed (every row
    is then cut off and nothing reads them).  Behind a branch for the
    compiler's sake: with the transposes straight-line in the step
    the compiler transposes the float32 parameters themselves at the top
    of the program, and then runs the optimizer's update of the experts
    in that layout, its moments and gradients copied into it and the
    results copied back (PERF.md section 6, PR 40: 16 ms a step).  A
    branch takes its operands as they are laid out."""
    with jax.named_scope("experts"):
        turn = lambda a: jnp.swapaxes(a.astype(cd), 1, 2)   # noqa: E731
        return lax.cond(
            any_pair, lambda: jax.tree.map(turn, experts),
            # of the same varying type as the other branch's
            lambda: jax.tree.map(lambda a: 0 * turn(a), experts))


def _stretches(rows, ends):
    return (ends[-1] + rows - 1) // rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed_part(rows, experts, xf, weights, order, ends, base):
    top_k = weights.shape[1]

    def some_rows(i, y):
        token, choice, sizes, live = _stretch(rows, order, ends, top_k, i)
        return y.at[token].add(_expert_rows(
            sizes, live, experts, xf[token], weights[token, choice]))

    return lax.fori_loop(1, _stretches(rows, ends), some_rows,
                         some_rows(0, base))


def _routed_part_fwd(rows, experts, xf, weights, order, ends, base):
    return _routed_part(rows, experts, xf, weights, order, ends, base), \
        (experts, xf, weights, order, ends)


def _routed_part_bwd(rows, kept, dy):
    experts, xf, weights, order, ends = kept
    top_k = weights.shape[1]
    turned = _turned(experts, xf.dtype, ends[-1] > 0)

    def some_rows(i, g_xf, g_weights):
        token, choice, sizes, live = _stretch(rows, order, ends, top_k, i)
        d_experts, d_xs, d_w = _expert_rows_back(
            sizes, live, experts, turned, xf[token],
            weights[token, choice], dy[token])
        return (d_experts, g_xf.at[token].add(d_xs),
                g_weights.at[token, choice].add(d_w))

    def more_rows(i, grads):
        g_experts, g_xf, g_weights = grads
        d_experts, g_xf, g_weights = some_rows(i, g_xf, g_weights)
        return jax.tree.map(jnp.add, g_experts, d_experts), g_xf, g_weights

    zeros = lambda a: lax.full_like(a, 0, jnp.float32)      # noqa: E731
    g_experts, g_xf, g_weights = lax.fori_loop(
        1, _stretches(rows, ends), more_rows,
        some_rows(0, zeros(xf), zeros(weights)))
    return (jax.tree.map(lambda g, a: g.astype(a.dtype), g_experts, experts),
            g_xf.astype(xf.dtype), g_weights.astype(weights.dtype),
            None, None, dy)


_routed_part.defvjp(_routed_part_fwd, _routed_part_bwd)
