"""Collective exchange strategies — the communication backend zoo.

TPU-native rebuild of Theano-MPI's ``theanompi/lib/exchanger_strategy.py``
(SURVEY.md §2.3), the reference's richest ``lib/`` file.  There, a BSP
exchange could run over host-staged MPI (``Exch_allreduce``), CUDA-aware MPI
(``Exch_ar``), a hand-written alltoall-sum-allgather ring with inline PyCUDA
fp16 pack/unpack kernels (``Exch_asa32/asa16``, ``Exch_copper(16)``), or NCCL
(``Exch_nccl32/16``).  On TPU all of these are expressible as XLA collectives
over the ICI mesh, but the *capability* — a selectable wire
format/algorithm — is preserved:

==================  =====================================================
reference name(s)   TPU-native strategy
==================  =====================================================
``allreduce``,      :class:`AllReduce` — ``lax.psum`` (XLA picks the ICI
``ar``, ``nccl32``  algorithm; this is the fast default, ≙ NCCL's role)
``nccl16``          :class:`AllReduce` with bfloat16 wire (cast → psum →
                    cast, fp32 master copy untouched)
``asa32``,          :class:`Ring` — explicit reduce-scatter + allgather
``copper``          over ``lax.ppermute`` hops, the same algorithm the
                    reference hand-wrote over MPI point-to-point
``asa16``,          :class:`Ring` with bfloat16 wire per hop (the
``copper16``        reference's inline fp32↔fp16 PyCUDA kernels, N1/N2 in
                    SURVEY.md §2.9, become dtype casts that XLA fuses)
``onebit``,         :class:`OneBit` / :class:`TopK` — error-feedback
``topk``,           compressed exchange (BASELINE.json config #5); sign
``compressed``      bits are bit-packed 8-per-byte before the collective
                    (``theanompi_tpu.ops.compress``)
==================  =====================================================

Every strategy is a pure function traced INSIDE the compiled step (within a
``shard_map`` over the ``'workers'`` mesh axis), so comm fuses with compute
and rides ICI — there is no host staging to come back to.

Semantics: every strategy returns the **mean** of the input pytree across
workers (the reference divided by size with a fused PyCUDA kernel).
Stateful strategies (error feedback) carry per-worker state.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..jax_compat import (all_gather_done, all_gather_start, psum_done,
                          psum_start)
from ..utils import helper_funcs
from ..ops import compress as compress_ops
from . import buckets

#: Deliberate non-bit-exact rounding sites, audited by tpulint's
#: dtype-flow checker (docs/design.md §26) — every direct
#: ``.astype(a).astype(b)`` round-trip must carry an entry here.
NONBITEXACT = {
    "Ring.__call__": "owned chunk is rounded to the wire dtype before "
                     "the allgather so every rank (owner included) "
                     "holds the identical bit pattern",
}


class Strategy:
    """Base: callable ``(tree, state, axis, size) -> (mean_tree, new_state)``
    traced inside the compiled SPMD step."""

    name = "base"
    stateful = False
    # True when the strategy operates on one FLATTENED vector rather than
    # leaf-wise: under tensor parallelism the flatten mixes sharded and
    # replicated-leaf segments, so the exchanger re-imposes replication on
    # the replicated leaves afterwards (a pmean over 'model')
    flattens = False
    # bucketed overlap-scheduled wire (parallel/buckets.py, ROADMAP item
    # 1): > 0 splits this strategy's collectives into ~bucket_bytes
    # slices issued as async start/done pairs; 0 keeps the monolithic
    # wire.  Set by BSP_Exchanger from config['bucket_bytes'] — a
    # SCHEDULE knob only: bucketed ≡ monolithic bit-for-bit, pinned per
    # strategy in tests/test_buckets.py.
    bucket_bytes = 0

    def init_state(self, params) -> Any:
        """Per-worker persistent state (unsharded template; the exchanger adds
        the leading ``[n_workers]`` axis)."""
        return ()

    def n_buckets(self, params, bucket_bytes: int) -> Optional[int]:
        """Wire slices one exchange of a ``params``-shaped payload ships
        at ``bucket_bytes`` (bench's ``n_buckets`` row column).  The
        default models the fp32 leaf payload (allreduce-family);
        compressed strategies override with their packed layouts; None =
        this strategy's wire does not bucket (ring's hand-rolled chunk
        pipeline, the no-comm probe)."""
        return buckets.count_buckets(params, bucket_bytes)

    def __call__(self, tree, state, *, axis: str, size: int):
        raise NotImplementedError


class NoComm(Strategy):
    """Local-only pseudo-strategy: the per-worker mean WITHOUT the collective.

    Exists for comm-time measurement (``measure_comm``): the fused BSP step
    hides t_comm inside one XLA program, so the reference's headline
    t_train/t_comm decomposition (SURVEY.md §6) is recovered by differencing
    step time under the selected strategy vs under ``none``.  Training with
    it breaks the BSP invariant — replicas diverge.
    """

    name = "none"

    def n_buckets(self, params, bucket_bytes: int):
        return None                       # no collective, nothing to slice

    def __call__(self, tree, state, *, axis: str, size: int):
        inv = 1.0 / size
        return jax.tree.map(lambda g: g * inv, tree), state


# A wide FC layer has many parameters and little arithmetic (Krizhevsky,
# arXiv:1404.5997): its weight gradient is ``x^T . dy`` with a batch's rows,
# so the wire can carry the two operands instead of the product.  The
# all-reduce of a float32 ``in x out`` leaf moves ``2(n-1)/n . in.out.4``
# bytes a chip; all-gathering the ``b`` rows a chip holds of both operands,
# ``m`` microbatches a step, moves ``m(n-1) . b(in+out) . s``, and every
# chip then forms the full-batch product itself: ``n-1`` redundant
# weight-gradient matmuls.  ``BSP_Exchanger.gathered_grads`` applies the
# rule; ``models/layers.py`` ``FC`` is the other half of the mechanism.
# Below 8 MiB an all-reduce costs latency, not bytes.
GATHER_MIN_BYTES = 8 << 20
# The gathers may move at most this share of the all-reduce's bytes: the
# margin pays for the redundant product.  At n=4, b=384, bf16 VGG-16's fc6
# reads 0.109 and fc7 0.188 (engage), its 4096x1000 head 0.478 (stays).
GATHER_MAX_SHARE = 0.25


def fc_wire_bytes(n: int, rows: int, n_subb: int, n_in: int, n_out: int,
                  itemsize: int) -> Tuple[int, int]:
    """Bytes one chip moves a step for a float32 ``n_in x n_out`` weight:
    ``(all-reduced, gathered)``."""
    return (2 * (n - 1) * n_in * n_out * 4 // n,
            n_subb * (n - 1) * rows * (n_in + n_out) * itemsize)


def gather_engages(n: int, rows: int, n_subb: int, n_in: int, n_out: int,
                   itemsize: int, min_bytes: int = GATHER_MIN_BYTES) -> bool:
    """The rule: does an FC weight's gradient travel as gathered operands?"""
    reduced, gathered = fc_wire_bytes(n, rows, n_subb, n_in, n_out, itemsize)
    return (n > 1 and n_in * n_out * 4 >= min_bytes
            and gathered <= GATHER_MAX_SHARE * reduced)


class AllReduce(Strategy):
    """``lax.psum``-based mean — XLA emits the tuned ICI allreduce.

    Covers the reference's ``Exch_allreduce`` / ``Exch_ar`` / ``Exch_nccl32``
    (and ``nccl16`` with ``wire_dtype=bfloat16``): on TPU there is no
    host-staged vs device-aware distinction to preserve, the compiled
    collective IS the device-aware path.

    ``summed``: key paths (``jax.tree_util.keystr``) of leaves that arrive
    already summed over the workers (an ``FC`` weight on the gathered
    path): scaled to the mean, no collective.
    """

    def __init__(self, wire_dtype=None):
        self.wire_dtype = wire_dtype
        self.name = "allreduce" if wire_dtype is None else "allreduce16"

    def __call__(self, tree, state, *, axis: str, size: int, summed=()):
        inv = 1.0 / size
        if summed:
            flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
            held = [jax.tree_util.keystr(p) in summed for p, _ in flat]
            rest, state = self([g for (_, g), h in zip(flat, held) if not h],
                               state, axis=axis, size=size)
            rest = iter(rest)
            return treedef.unflatten(
                [g * inv if h else next(rest)
                 for (_, g), h in zip(flat, held)]), state
        wd = self.wire_dtype
        if self.bucket_bytes > 0:
            # per-bucket async psum pairs: all starts issued before the
            # first done so the latency-hiding scheduler can overlap the
            # buckets with the backprop tail.  The wire cast (if any)
            # happens per bucket — same elementwise cast→psum→cast chain
            # as the monolithic leaf, so bit-identity holds either way.
            plan = buckets.plan_buckets(tree, self.bucket_bytes)
            vecs = buckets.pack(tree, plan)
            tickets = [psum_start(v if wd is None else v.astype(wd), axis)
                       for v in vecs]
            sums = [psum_done(t) for t in tickets]
            reduced = [(s if wd is None else s.astype(v.dtype)) * inv
                       for s, v in zip(sums, vecs)]
            return buckets.unpack(reduced, tree, plan), state
        if wd is None:
            out = jax.tree.map(lambda g: lax.psum(g, axis) * inv, tree)
        else:
            out = jax.tree.map(
                lambda g: lax.psum(g.astype(wd), axis).astype(g.dtype) * inv, tree
            )
        return out, state


class Ring(Strategy):
    """Explicit chunked ring: reduce-scatter then allgather over
    ``lax.ppermute``.

    Algorithmic parity with the reference's ``Exch_asa32/asa16`` ("alltoall
    sum allgather" over CUDA-aware MPI p2p) and ``Exch_copper(16)``: the
    parameter pytree is flattened to one contiguous fp32 vector (the
    reference walked a concatenated GPUArray buffer), split into ``size``
    chunks, and each of the ``2(size-1)`` hops moves one chunk to the right
    neighbor.  ``wire_dtype=bfloat16`` casts each hop's payload — the role of
    the reference's runtime-compiled fp32↔fp16 PyCUDA kernels — while the
    accumulator stays fp32.
    """

    def __init__(self, wire_dtype=None):
        self.wire_dtype = wire_dtype
        self.name = "ring" if wire_dtype is None else "ring16"
        self.flattens = True

    def n_buckets(self, params, bucket_bytes: int):
        # the ring IS a chunk pipeline already (2(size-1) ppermute hops
        # over size-th slices) — the bucket planner does not re-slice it
        return None

    def __call__(self, tree, state, *, axis: str, size: int):
        if size == 1:
            return tree, state
        flat = helper_funcs.flatten_tree(tree, pad_to_multiple_of=size)
        chunk = flat.shape[0] // size
        buf = flat.reshape(size, chunk)
        rank = lax.axis_index(axis)
        perm = [(i, (i + 1) % size) for i in range(size)]
        wd = self.wire_dtype

        def send(x):
            return lax.ppermute(x if wd is None else x.astype(wd), axis, perm)

        def recv_cast(x):
            return x if wd is None else x.astype(jnp.float32)

        # Reduce-scatter: after step s, the partial sum for chunk
        # (rank - s - 1) has accumulated s+2 contributions.
        def rs_body(s, carry):
            acc, cur = carry  # cur: the partial chunk we just received/own
            nxt = recv_cast(send(cur))
            idx = (rank - s - 1) % size
            mine = lax.dynamic_index_in_dim(acc, idx, 0, keepdims=False)
            summed = mine + nxt
            acc = lax.dynamic_update_index_in_dim(acc, summed, idx, 0)
            return acc, summed

        own_first = lax.dynamic_index_in_dim(buf, rank % size, 0, keepdims=False)
        acc, _ = lax.fori_loop(0, size - 1, rs_body, (buf, own_first))
        my_idx = (rank + 1) % size
        my_chunk = lax.dynamic_index_in_dim(acc, my_idx, 0, keepdims=False) / size
        if wd is not None:
            # Round the owned chunk to the wire dtype BEFORE the allgather so
            # every rank (owner included) holds the identical bit pattern —
            # replica divergence here would silently break BSP's invariant.
            my_chunk = my_chunk.astype(wd).astype(jnp.float32)

        # Allgather: at step s each rank forwards the chunk it received last.
        out = jnp.zeros_like(buf)
        out = lax.dynamic_update_index_in_dim(out, my_chunk, my_idx, 0)

        def ag_body(s, carry):
            out, cur = carry
            got = recv_cast(send(cur))
            idx = (rank - s) % size
            out = lax.dynamic_update_index_in_dim(out, got, idx, 0)
            return out, got

        out, _ = lax.fori_loop(0, size - 1, ag_body, (out, my_chunk))
        return helper_funcs.unflatten_like(tree, out.reshape(-1)), state


class OneBit(Strategy):
    """1-bit sign compression with error feedback (BASELINE.json config #5).

    Each worker quantizes its (gradient + carried error) vector to
    ``scale * sign``, keeps the quantization residual as next step's error
    feedback, and only sign *bits* plus one scalar scale cross the wire:
    signs are bit-packed 8-per-byte (Pallas kernel on TPU, jnp fallback
    elsewhere — ``ops/compress.py``), all-gathered, then decoded and averaged
    locally.  Wire cost per worker ≈ P/8 bytes vs 4P for fp32 — a 32×
    compression, the modern version of the reference's fp16 wire trick.
    """

    name = "onebit"
    stateful = True
    flattens = True

    def init_state(self, params):
        n = helper_funcs.tree_size(params)
        padded = n + (-n) % compress_ops.PACK_ALIGN
        return jnp.zeros((padded,), jnp.float32)

    def _segment_elems(self, bucket_bytes: int) -> int:
        """fp32 elements per wire bucket, rounded DOWN to the pack-kernel
        grid (PACK_ALIGN) so every bucket's packed buffer is whole tiles
        — the pack/decode pair is blockwise, which is exactly why
        bucketed ≡ monolithic bit-for-bit."""
        return max(compress_ops.PACK_ALIGN,
                   (int(bucket_bytes) // 4 // compress_ops.PACK_ALIGN)
                   * compress_ops.PACK_ALIGN)

    def n_buckets(self, params, bucket_bytes: int):
        n = helper_funcs.tree_size(params)
        n += (-n) % compress_ops.PACK_ALIGN
        seg = self._segment_elems(bucket_bytes)
        return max(1, -(-n // seg))

    def __call__(self, tree, state, *, axis: str, size: int):
        flat = helper_funcs.flatten_tree(
            tree, pad_to_multiple_of=compress_ops.PACK_ALIGN)
        n_true = helper_funcs.tree_size(tree)
        # fused encode: c = flat + state is formed in VMEM and emits the
        # packed sign tiles AND |c| in one pass — c itself never lands in
        # HBM (ops/compress.py pack_signs_encode; jnp oracle elsewhere)
        packed, absc = compress_ops.pack_signs_encode(flat, state)
        # scale over the TRUE length only: the PACK_ALIGN zero pad would
        # deflate mean(|c|) by up to pad/n
        scale = jnp.mean(absc[:n_true]) + 1e-12
        # new error state from |c| + sign bits + scale, bit-exact vs the
        # unfused c − scale·sign(c)
        new_state = compress_ops.signed_residual(absc, packed, scale)
        all_scales = lax.all_gather(scale, axis)       # [size] — one scalar
        if self.bucket_bytes > 0:
            # per-bucket wire: the vector is packed ONCE and each bucket
            # all-gathers its PACK_ALIGN-aligned slice of PACKED rows as
            # its own async pair (all starts before the first done),
            # decoding per bucket with the GLOBAL scale — the pack/decode
            # pair is blockwise, so bucketed ≡ monolithic bit-for-bit
            seg = self._segment_elems(self.bucket_bytes)
            rows_per = seg // (32 * compress_ops.LANES)  # packed rows/bucket
            p_rows = packed.shape[0]
            bounds = [(a, min(a + rows_per, p_rows))
                      for a in range(0, p_rows, rows_per)]
            tickets = [all_gather_start(packed[a:b], axis)
                       for a, b in bounds]
            segs = [compress_ops.unpack_signs_weighted_mean(
                all_gather_done(t), all_scales, size) for t in tickets]
            mean = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
        else:
            all_packed = lax.all_gather(packed, axis)  # P/8 bytes/worker
            mean = compress_ops.unpack_signs_weighted_mean(
                all_packed, all_scales, size)
        return helper_funcs.unflatten_like(tree, mean), new_state


class TopK(Strategy):
    """Chunk-local top-k sparsification with error feedback and a packed
    wire format (BASELINE.json config #5 alongside :class:`OneBit`).

    The gradient+error vector is viewed as ``[C, chunk_size]`` chunks and
    the ``k_c = ratio·chunk_size`` largest-magnitude entries of EACH chunk
    are selected — a vectorized row-wise ``lax.top_k`` instead of a global
    top-k sort of the whole 138M-element VGG-16 vector (the round-1 version,
    which both sorted the full vector and shipped fp32 values + int32
    global indices).  Chunk-local selection is the standard large-model
    variant (error feedback absorbs the difference from exact global top-k)
    and makes the wire format packable:

    * values cross as **bfloat16** (master accumulation stays fp32),
    * indices cross as **int16** chunk-local offsets (signed int16, so
      chunk_size ≤ 32768 — enforced in ``__init__``; the chunk id is
      implicit in position), global index = c·chunk + off.

    Wire bytes per worker ≈ 4·k total (vs 8·k before; vs P/8 for onebit —
    at the 1% default ratio that is 0.04·P vs 0.125·P, ~3× less than
    onebit and 100× less than fp32 allreduce).
    """

    name = "topk"
    stateful = True
    flattens = True

    CHUNK = 8192          # ≤ 2^16 for int16 offsets; multiple of the lane dim

    def __init__(self, ratio: float = 0.01, k: Optional[int] = None,
                 chunk: Optional[int] = None):
        self.ratio = ratio
        self.k = k                    # per-chunk override (mostly for tests)
        self.chunk = int(chunk or self.CHUNK)
        # signed int16 offsets: anything past 2^15−1 would wrap negative on
        # the wire and silently corrupt the scatter indices
        assert self.chunk <= 1 << 15, "int16 offsets need chunk ≤ 32768"

    def init_state(self, params):
        n = helper_funcs.tree_size(params)
        padded = n + (-n) % self.chunk
        return jnp.zeros((padded,), jnp.float32)

    def _k_c(self) -> int:
        """Selected entries per chunk row — ONE derivation for the
        exchange itself and the n_buckets bench column."""
        return self.k or max(1, int(round(self.chunk * self.ratio)))

    def _rows_per_bucket(self, k_c: int, bucket_bytes: int) -> int:
        """Chunk rows per wire bucket: a row ships ``k_c`` bf16 values +
        ``k_c`` int16 offsets = 4·k_c bytes.  Shared by the bucketed
        exchange and n_buckets so the bench column can't drift from the
        collectives actually issued."""
        return max(1, int(bucket_bytes) // (4 * k_c))

    def __call__(self, tree, state, *, axis: str, size: int):
        flat = helper_funcs.flatten_tree(tree, pad_to_multiple_of=self.chunk)
        c = flat + state
        n = c.shape[0]
        n_chunks = n // self.chunk
        k_c = self._k_c()
        c2 = c.reshape(n_chunks, self.chunk)

        # fused encode: top-k select, bf16 value cast, int16 offset emit
        # and the in-place bf16 rounding residual, one chunk-row pass
        # (ops/compress.py topk kernels; jnp oracle elsewhere).  The bf16
        # quantization residual of each shipped value feeds back into the
        # error buffer alongside the unselected mass, so the fp32 master
        # stream loses nothing to the wire rounding either.
        wire_vals, wire_idx, new_c2 = compress_ops.topk_encode(c2, k_c)
        new_state = new_c2.reshape(-1)
        if self.bucket_bytes > 0:
            # per-bucket wire: the (vals, idx) pairs of ~bucket_bytes
            # worth of CHUNK ROWS ride as their own async all-gather
            # pairs; each bucket decodes into its own disjoint dense
            # segment (chunk c only ever lands in [c·chunk, (c+1)·chunk)),
            # so the per-bucket decodes reproduce the monolithic decode
            # bit-for-bit
            rows_per = self._rows_per_bucket(k_c, self.bucket_bytes)
            bounds = [(a, min(a + rows_per, n_chunks))
                      for a in range(0, n_chunks, rows_per)]
            tickets = [(all_gather_start(wire_vals[a:b], axis),
                        all_gather_start(wire_idx[a:b], axis))
                       for a, b in bounds]
            segs = [compress_ops.topk_decode(all_gather_done(tv),
                                             all_gather_done(ti),
                                             self.chunk, size)
                    for tv, ti in tickets]
            mean = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
        else:
            all_vals = lax.all_gather(wire_vals, axis)  # [size, C, k_c]
            all_idx = lax.all_gather(wire_idx, axis)
            mean = compress_ops.topk_decode(all_vals, all_idx,
                                            self.chunk, size)
        return helper_funcs.unflatten_like(tree, mean), new_state

    def n_buckets(self, params, bucket_bytes: int):
        n = helper_funcs.tree_size(params)
        n += (-n) % self.chunk
        n_chunks = n // self.chunk
        rows_per = self._rows_per_bucket(self._k_c(), bucket_bytes)
        return max(1, -(-n_chunks // rows_per))


class PowerSGD(Strategy):
    """Rank-r low-rank gradient compression with error feedback (PowerSGD,
    Vogels et al. 2019, arXiv:1905.13727) — the modern production
    compressor alongside :class:`OneBit` / :class:`TopK`, and the one that
    maps best to the TPU: the encode/decode are small MATMULS (MXU work,
    not elementwise bit-twiddling) and the wire shrinks from rows·cols to
    r·(rows+cols) per matrix.

    Per matrix-shaped leaf M (conv kernels reshape to [k·k·ci, co]), with
    per-worker error feedback e and a warm-started shared Q:

        M' = M + e                       # local, fp32 master stream
        P  = mean_w(M' Q)      (psum)    # [rows, r] on the wire
        P̂  = qr(P).Q                     # orthonormal basis, same everywhere
        Q' = mean_w(M'ᵀ P̂)     (psum)    # [cols, r] on the wire
        M̂  = P̂ Q'ᵀ                       # decoded rank-r mean
        e' = M' − M̂                      # local residual feeds back

    Every worker decodes the SAME M̂ (both collectives precede the decode),
    so BSP replicas stay bit-identical; error feedback keeps the lost mass
    in the fp32 master stream.  When r ≥ rank(mean(M')), P̂ spans its
    column space and the decode is EXACT — pinned against the psum oracle
    in ``tests/test_powersgd.py``.  Vectors/norm scales and matrices too
    small to win (min dim ≤ 4r) reduce exactly — their wire share is
    negligible.

    State is PER LEAF ([Q, e] list aligned with the gradient leaves), not
    a flat vector.  Under model parallelism (tp/pp) each model/pipe rank
    compresses ITS local grad shard independently — the same shard-wise
    composition the flat strategies use — with the per-leaf state carried
    in a leading ``[prod(group)]`` axis sharded over the group axes
    (``BSP_Exchanger.extra_state_template`` builds it from the LOCAL
    shard template and ``extra_specs`` declares ``P(group)``; the
    exchanger unwraps the leading axis around the call).  Select via
    ``exch_strategy='powersgd'`` (rank 2) or ``'powersgd<r>'``.
    """

    stateful = True
    flattens = False
    leafwise_state = True      # extra_state_template gates model-parallel

    def __init__(self, rank: int = 2):
        self.rank = int(rank)
        assert self.rank >= 1
        self.name = f"powersgd{self.rank}"

    def _compressible(self, shape) -> bool:
        if len(shape) < 2:
            return False
        rows = int(np.prod(shape[:-1]))
        return min(rows, int(shape[-1])) > 4 * self.rank

    def init_state(self, params):
        state = []
        for i, l in enumerate(jax.tree.leaves(params)):
            shape = np.shape(l)
            if self._compressible(shape):
                rows, cols = int(np.prod(shape[:-1])), int(shape[-1])
                # deterministic per-leaf init — identical on every worker,
                # so the shared-Q invariant holds from step one
                q = jax.random.normal(jax.random.key(1905 + i),
                                      (cols, self.rank), jnp.float32)
                state.append({"q": q,
                              "e": jnp.zeros((rows, cols), jnp.float32)})
            else:
                state.append({"q": jnp.zeros((0, self.rank), jnp.float32),
                              "e": jnp.zeros((0, 0), jnp.float32)})
        return state

    def n_buckets(self, params, bucket_bytes: int):
        # the compressible leaves' P/Q factor psums are per-leaf small
        # collectives already (their own pipeline); the planner buckets
        # the DENSE remainder (vectors, norms, tiny matrices)
        dense = [l for l in jax.tree.leaves(params)
                 if not self._compressible(np.shape(l))]
        return buckets.count_buckets(dense, bucket_bytes) if dense else 0

    def __call__(self, tree, state, *, axis: str, size: int):
        from ..ops import factor_pack
        from .steps import _vary
        inv = 1.0 / size
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        assert len(leaves) == len(state), (len(leaves), len(state))
        out = [None] * len(leaves)
        new_state = list(state)
        comp = [i for i, g in enumerate(leaves)
                if self._compressible(np.shape(g))]

        # -- compressible leaves: stacked low-rank factor exchange --------
        # Every factor matmul lands directly in its zero-padded slice of
        # ONE staging buffer (ops/factor_pack.matmul_pack fuses the matmul
        # with the staging pack), so all P factors ride a single psum —
        # and likewise all Q factors — instead of one collective per leaf.
        # Zero pad rows psum to zero, so each slice equals the per-leaf
        # psum it replaces bit-for-bit.
        Mps = {i: leaves[i].reshape(-1, leaves[i].shape[-1])
               .astype(jnp.float32) + state[i]["e"] for i in comp}

        def _stacked_psum(tiles):
            buf = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, 0)
            return lax.psum(buf, axis) * inv

        if comp:
            p_tiles = [factor_pack.matmul_pack(Mps[i], state[i]["q"])
                       for i in comp]
            P_all = _stacked_psum(p_tiles)
            Phs, off = {}, 0
            for i, t in zip(comp, p_tiles):
                rows = Mps[i].shape[0]
                Phs[i], _ = jnp.linalg.qr(P_all[off:off + rows])
                off += t.shape[0]
            q_tiles = [factor_pack.matmul_pack(Mps[i].T, Phs[i])
                       for i in comp]
            Q_all = _stacked_psum(q_tiles)
            off = 0
            for i, t in zip(comp, q_tiles):
                g = leaves[i]
                cols = Mps[i].shape[1]
                Qn = Q_all[off:off + cols]
                off += t.shape[0]
                Mhat = Phs[i] @ Qn.T
                out[i] = Mhat.reshape(g.shape).astype(g.dtype)
                # Qn is a psum result (worker-INVARIANT in the vma type
                # system), but it persists in the boxed per-worker state
                # whose scan carry under steps_per_call is worker-varying —
                # re-mark it (values are identical everywhere; a type cast)
                new_state[i] = {"q": _vary(Qn, axis), "e": Mps[i] - Mhat}

        # -- dense remainder ----------------------------------------------
        dense_ids = [i for i in range(len(leaves)) if out[i] is None]
        if self.bucket_bytes > 0 and dense_ids:
            # the dense remainder rides the bucket planner: one async
            # psum pair per ~bucket_bytes of incompressible leaves
            # (element-wise sum — bit-identical to the leaf-wise psums)
            summed = buckets.bucketed_psum([leaves[i] for i in dense_ids],
                                           axis, self.bucket_bytes)
            for i, s in zip(dense_ids, summed):
                out[i] = s * inv
        else:
            for i in dense_ids:
                out[i] = lax.psum(leaves[i], axis) * inv
        return jax.tree_util.tree_unflatten(treedef, out), new_state


def get_strategy(name: str, **kwargs) -> Strategy:
    """Resolve a strategy by its reference-compatible config string."""
    name = name.lower()
    table = {
        "none": lambda: NoComm(),
        "nocomm": lambda: NoComm(),
        "allreduce": lambda: AllReduce(),
        "ar": lambda: AllReduce(),
        "nccl32": lambda: AllReduce(),
        "nccl16": lambda: AllReduce(wire_dtype=jnp.bfloat16),
        "asa32": lambda: Ring(),
        "ring": lambda: Ring(),
        "copper": lambda: Ring(),
        "asa16": lambda: Ring(wire_dtype=jnp.bfloat16),
        "ring16": lambda: Ring(wire_dtype=jnp.bfloat16),
        "copper16": lambda: Ring(wire_dtype=jnp.bfloat16),
        "bf16": lambda: AllReduce(wire_dtype=jnp.bfloat16),
        "onebit": lambda: OneBit(),
        "compressed": lambda: OneBit(),
        "topk": lambda: TopK(**kwargs),
        "powersgd": lambda: PowerSGD(**kwargs),
    }
    if name.startswith("powersgd") and name[8:].isdigit():
        # 'powersgd4' etc.; an explicit rank kwarg must not silently lose
        assert "rank" not in kwargs or int(kwargs["rank"]) == int(name[8:]), \
            f"strategy name {name!r} conflicts with rank={kwargs['rank']}"
        return PowerSGD(rank=int(name[8:]))
    try:
        return table[name]()
    except KeyError:
        raise ValueError(f"unknown exchange strategy {name!r}; "
                         f"have {sorted(table)}")
