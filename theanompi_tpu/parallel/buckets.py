"""Bucketed, overlap-scheduled collectives — the exchange wire in slices.

ROADMAP item 1: every exchange rule used to issue its payload as ONE
monolithic collective (leaf-wise ``lax.psum`` sites in ``exchanger.py``,
whole-vector gathers in ``strategies.py``) that serializes against
compute.  The CUDA-aware-MPI characterization paper (PAPERS.md,
1810.11112) shows *overlap of reduction with backprop* — not raw
bandwidth — governs scaling; the standard mechanism (NCCL/DDP buckets,
the pjit/TPUv4 LM stack) is to split the payload into size-targeted
buckets and let the scheduler start bucket k's reduction while bucket
k+1's producers (the tail of backprop) are still running.

This module is the ONE bucket planner and pack/collect/unpack engine all
wires share:

* :func:`plan_buckets` — a PURE function of the payload's tree-def +
  leaf shapes/dtypes (never of values): flatten the leaves in tree order
  and greedily close a bucket when it reaches ``bucket_bytes``
  (default :data:`DEFAULT_BUCKET_BYTES` ≈ 4 MiB).  Buckets are
  dtype-homogeneous (a dtype change closes the current bucket — packing
  must never cast, or bucketed ≢ monolithic), and a leaf larger than a
  bucket becomes its own single-leaf bucket, never split across buckets
  mid-leaf and never merged with neighbors.  Purity makes the plan
  stable across compiles, independent of membership masks (masks scale
  VALUES, not shapes), and hashable (:func:`plan_signature`).

* :func:`pack` / :func:`unpack` — leaves ↔ one contiguous 1-D vector
  per bucket.  Reshape+concatenate+slice only: bit-exact round-trip by
  construction.

* :func:`bucketed_psum` (and the generic :func:`bucketed_collect`) —
  issue EVERY bucket's collective start before the first done is
  awaited, through the ``jax_compat`` async start/done shim.  On a
  jaxlib exposing a real async-collective surface the in-flight window
  is explicit; on this one the shim's sync fallback still leaves XLA's
  latency-hiding scheduler N independent collectives to pipeline into
  the backward pass inside the fused scan (``steps.build_train_step``)
  instead of one serializing monolith.  tpulint's collective-discipline
  checker enforces the start/done pairing (every start's ticket must
  reach a done in the same scope — the bucket-balance probe).

Correctness contract (pinned per rule in ``tests/test_buckets.py``):
at fixed membership, bucketed ≡ monolithic BIT-FOR-BIT.  ``psum`` /
``all_gather`` / ``ppermute`` are element-wise in the payload, so
slicing the payload differently cannot change any element's reduction
order across workers — only the schedule.
"""

from __future__ import annotations

from typing import Any, Callable, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..jax_compat import psum_done, psum_start

DEFAULT_BUCKET_BYTES = 4 << 20          # ~4 MiB, the DDP/NCCL sweet spot


class Bucket(NamedTuple):
    """One wire slice: which flat leaf segments ride together."""

    dtype: str                 # numpy dtype name — buckets never mix dtypes
    leaf_ids: Tuple[int, ...]  # indices into the flattened leaf list
    sizes: Tuple[int, ...]     # element count per member leaf (same order)

    @property
    def size(self) -> int:
        return sum(self.sizes)

    def nbytes(self) -> int:
        return self.size * np.dtype(self.dtype).itemsize


class BucketPlan(NamedTuple):
    """The full schedule: every non-empty leaf appears in exactly one
    bucket, in tree order; empty leaves are carried through untouched
    (nothing to reduce, nothing on the wire)."""

    bucket_bytes: int
    buckets: Tuple[Bucket, ...]
    n_leaves: int              # total leaves of the planned tree
    empty_leaf_ids: Tuple[int, ...]

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)


def plan_buckets(tree, bucket_bytes: int = DEFAULT_BUCKET_BYTES
                 ) -> BucketPlan:
    """Deterministic bucket plan for ``tree`` — a pure function of its
    tree-def and leaf shapes/dtypes (traced values are fine: only
    ``.shape``/``.dtype`` are read).  ``bucket_bytes <= 0`` degenerates
    to one bucket per dtype run (still covered by the same pack/collect
    machinery, useful for tests)."""
    bucket_bytes = int(bucket_bytes)
    leaves = jax.tree.leaves(tree)
    buckets: List[Bucket] = []
    empty: List[int] = []
    cur_ids: List[int] = []
    cur_sizes: List[int] = []
    cur_dtype = None
    cur_bytes = 0

    def close():
        nonlocal cur_ids, cur_sizes, cur_dtype, cur_bytes
        if cur_ids:
            buckets.append(Bucket(cur_dtype, tuple(cur_ids),
                                  tuple(cur_sizes)))
        cur_ids, cur_sizes, cur_dtype, cur_bytes = [], [], None, 0

    for i, leaf in enumerate(leaves):
        size = int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
        if size == 0:
            empty.append(i)
            continue
        dt = np.dtype(getattr(leaf, "dtype", None)
                      or np.asarray(leaf).dtype)
        nbytes = size * dt.itemsize
        if cur_dtype is not None and dt.name != cur_dtype:
            close()                       # dtype-homogeneous buckets only
        if bucket_bytes > 0 and nbytes >= bucket_bytes:
            close()                       # oversized leaf: its own bucket,
            buckets.append(Bucket(dt.name, (i,), (size,)))  # never split
            continue
        cur_ids.append(i)
        cur_sizes.append(size)
        cur_dtype = dt.name
        cur_bytes += nbytes
        if bucket_bytes > 0 and cur_bytes >= bucket_bytes:
            close()
    close()
    return BucketPlan(bucket_bytes, tuple(buckets), len(leaves),
                      tuple(empty))


def plan_signature(plan: BucketPlan) -> str:
    """Compact stable identity of one plan (AOT key extras, bench rows):
    ``<bucket_bytes>:<n_buckets>b/<n_leaves>l``."""
    return f"{plan.bucket_bytes}:{plan.n_buckets}b/{plan.n_leaves}l"


def count_buckets(tree, bucket_bytes: int) -> int:
    """Collectives one bucketed exchange of ``tree`` issues (bench's
    ``n_buckets`` row column)."""
    return plan_buckets(tree, bucket_bytes).n_buckets


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

def pack(tree, plan: BucketPlan) -> List[jnp.ndarray]:
    """Leaves → one contiguous 1-D vector per bucket (dtype preserved —
    packing must never cast, or bucketed ≢ monolithic)."""
    leaves = jax.tree.leaves(tree)
    assert len(leaves) == plan.n_leaves, (
        f"plan built for {plan.n_leaves} leaves, tree has {len(leaves)} — "
        "plan and payload tree drifted")
    out = []
    for b in plan.buckets:
        segs = [leaves[i].reshape(-1) for i in b.leaf_ids]
        out.append(segs[0] if len(segs) == 1 else jnp.concatenate(segs))
    return out


def unpack(vectors: Sequence[jnp.ndarray], tree, plan: BucketPlan):
    """Inverse of :func:`pack`, shaped/structured like ``tree`` (whose
    leaves supply shape+dtype; empty leaves pass through verbatim)."""
    leaves, treedef = jax.tree.flatten(tree)
    out: List[Any] = list(leaves)         # empty leaves keep their slot
    assert len(vectors) == plan.n_buckets
    for b, vec in zip(plan.buckets, vectors):
        ofs = 0
        for i, size in zip(b.leaf_ids, b.sizes):
            # static slice bounds — the plan is Python-level, so XLA sees
            # plain slices it can fuse with the consumer
            out[i] = vec[ofs:ofs + size].reshape(np.shape(leaves[i]))
            ofs += size
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# bucketed collectives
# ---------------------------------------------------------------------------

def bucketed_collect(tree, plan: BucketPlan,
                     start_fn: Callable[[jnp.ndarray], Any],
                     done_fn: Callable[[Any], jnp.ndarray]):
    """The overlap schedule every bucketed wire shares: pack, issue EVERY
    bucket's ``start_fn`` before awaiting the first ``done_fn`` (so a
    real async surface has all buckets in flight at once and the sync
    fallback still hands XLA independent collectives to pipeline), then
    unpack.  ``start_fn``/``done_fn`` wrap one ``jax_compat`` async pair
    — tpulint's collective-discipline bucket-balance probe checks every
    ticket list produced here is drained."""
    tickets = [start_fn(vec) for vec in pack(tree, plan)]
    reduced = [done_fn(t) for t in tickets]
    return unpack(reduced, tree, plan)


def bucketed_psum(tree, axis: str, bucket_bytes: int,
                  plan: BucketPlan = None):
    """Per-bucket ``psum`` of ``tree`` over mesh axis ``axis`` —
    bit-identical to the leaf-wise monolithic ``lax.psum`` (the reduction
    is element-wise; bucketing changes the schedule, not any element's
    cross-worker sum).  ``bucket_bytes <= 0`` falls back to the
    leaf-wise monolithic path so one call site serves both modes."""
    if plan is None:
        if int(bucket_bytes) <= 0:
            return jax.tree.map(lambda x: jax.lax.psum(x, axis), tree)
        plan = plan_buckets(tree, bucket_bytes)
    return bucketed_collect(
        tree, plan,
        lambda vec: psum_start(vec, axis),
        lambda t: psum_done(t))
