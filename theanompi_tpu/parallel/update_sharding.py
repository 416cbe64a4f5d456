"""Leaf-wise update-plane sharding over the data axis (ROADMAP item 2a).

Per "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (PAPERS.md, arXiv:2004.13336): under any rule whose update-plane
state is bit-identical across workers — BSP optimizer moments (every worker
applies the same reduced gradient), EASGD/ASGD center copies — replicating
that state per chip is pure memory waste.  This module is the ONE place the
partitioning lives:

* :func:`plan_tree` stamps a per-leaf schema (:class:`LeafPlan`): every leaf
  above a byte threshold is sharded on the data axis as a padded
  evenly-divisible flat chunk (spec ``P(workers)``); smaller leaves stay
  replicated (``P()``).  Worker-local divergent state (error-feedback
  buffers, gossip α) is never planned — rules declare their shardable keys
  via ``Exchanger.shardable_extra``.
* :func:`shard_tree` / :func:`unshard_tree` are the traced partition /
  rebuild primitives: per-leaf ``dynamic_slice`` down, ONE fused
  ``all_gather`` (per dtype) back up.  Elementwise update math on disjoint
  chunks followed by a value-exact gather is bit-identical to the
  replicated path — no reduction order changes anywhere
  (``tests/test_update_sharding.py`` pins it per rule).
* :func:`shard_opt` wraps any ``utils/opt.py`` ``OptPair`` so its state
  lives on the local chunks (the boxed ``[n_workers, chunk]`` layout IS the
  partition — per-chip update-plane bytes shrink ~N×), with the fused
  allgather rebuilding full params for the forward pass inside the same
  compiled step.
* :func:`flat_shard_opt` is the flat-chunk-everything configuration —
  ZeRO-1 (``parallel/zero.py``) collapses into a thin delegation to it.

tpulint's shard-rebuild-dominance checker
(``analysis/checkers/donation_safety.py``) gates the contract statically:
a chunk produced by :func:`slice_chunk`/:func:`shard_tree` may only escape
a function through its allgather rebuild (or from the schema's own named
producer functions) — a donated full buffer must never be silently
replaced by a local shard.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..utils import helper_funcs
from ..utils.opt import OptPair
from .mesh import WORKER_AXIS

# default byte threshold below which a leaf stays replicated: sharding a
# LayerNorm scale or a per-leaf step counter buys nothing and costs a
# gather lane; 64 KiB ≈ the point where the chunk still amortizes its
# slice/concat bookkeeping (config ``ushard_min_bytes`` overrides)
DEFAULT_MIN_BYTES = 65536


def chunk_size(n_total: int, n_workers: int) -> int:
    """ceil(P/N) — the per-worker chunk length of an N-way flat partition."""
    return -(-n_total // n_workers)


def padded_size(n_total: int, n_workers: int) -> int:
    """``chunk_size·N`` — the evenly-divisible padded flat length.  Callers
    pad to THIS, explicitly, before slicing chunks: a ragged ``n_total``
    (P=10, N=4 → chunk 3, padded 12) must never rely on an implicit
    zero-fill downstream (tests/test_zero.py pins the ragged case)."""
    return chunk_size(n_total, n_workers) * n_workers


class LeafPlan(NamedTuple):
    """The schema entry for ONE update-plane leaf."""
    path: str            # jax key-path string, for reports and errors
    shape: Tuple[int, ...]
    dtype: Any           # numpy dtype
    size: int            # prod(shape)
    sharded: bool        # above threshold → flat-chunked over the data axis
    chunk: int           # per-worker chunk length (== size when not sharded)
    pad: int             # chunk·N − size (0 when not sharded)
    spec: P              # P(workers) when sharded, P() when replicated


class UpdatePlan(NamedTuple):
    """A :class:`LeafPlan` per leaf, in the template's flatten order."""
    leaves: Tuple[LeafPlan, ...]
    n_workers: int
    min_bytes: int

    @property
    def any_sharded(self) -> bool:
        return any(l.sharded for l in self.leaves)

    def specs(self, template):
        """The schema as a template-structured pytree of PartitionSpecs."""
        flat, treedef = jax.tree_util.tree_flatten(template)
        assert len(flat) == len(self.leaves), (
            f"tree has {len(flat)} leaves, plan has {len(self.leaves)}")
        return jax.tree_util.tree_unflatten(
            treedef, [l.spec for l in self.leaves])


def plan_tree(template, n_workers: int, *,
              min_bytes: int = DEFAULT_MIN_BYTES,
              axis: str = WORKER_AXIS) -> UpdatePlan:
    """Stamp the leaf-wise sharding schema for ``template``.

    A leaf is sharded when its byte size reaches ``min_bytes`` AND it has at
    least ``n_workers`` elements (a scalar step counter can't usefully
    chunk).  ``n_workers == 1`` plans everything replicated — there is no
    partition to build."""
    leaves = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(template)[0]:
        shape = tuple(np.shape(leaf))
        dtype = np.dtype(getattr(leaf, "dtype", None)
                         or np.asarray(leaf).dtype)
        size = int(np.prod(shape)) if shape else 1
        sharded = (n_workers > 1 and size >= n_workers
                   and size * dtype.itemsize >= min_bytes)
        chunk = chunk_size(size, n_workers) if sharded else size
        leaves.append(LeafPlan(
            path=jax.tree_util.keystr(path), shape=shape, dtype=dtype,
            size=size, sharded=sharded, chunk=chunk,
            pad=(chunk * n_workers - size) if sharded else 0,
            spec=P(axis) if sharded else P()))
    return UpdatePlan(tuple(leaves), int(n_workers), int(min_bytes))


def _zip_leaves(tree, plan: UpdatePlan):
    flat, treedef = jax.tree_util.tree_flatten(tree)
    assert len(flat) == len(plan.leaves), (
        f"tree has {len(flat)} leaves, plan has {len(plan.leaves)} — "
        f"the plan must be built on the same template")
    return flat, treedef


def slice_chunk(flat, rank, chunk: int):
    """This worker's ``[chunk]`` window of an evenly-padded flat vector.
    ``flat`` must already be ``padded_size`` long — the slice is then always
    in bounds (dynamic_slice would silently clamp a ragged layout)."""
    return lax.dynamic_slice(flat, (rank * chunk,), (chunk,))


def all_gather_chunks(chunk_vec, axis: str = WORKER_AXIS):
    """Rebuild the padded flat vector from every worker's chunk — the ONE
    collective of the update-sharding wire (concatenating along the flat
    axis, so worker i's chunk lands at offset i·chunk exactly as
    :func:`slice_chunk` cut it)."""
    return lax.all_gather(chunk_vec, axis, tiled=True)


def shard_tree(tree, plan: UpdatePlan, rank):
    """Traced partition: each sharded leaf → this worker's flat ``[chunk]``
    (zero-padded to the evenly-divisible length first); replicated leaves
    pass through untouched.  Dtypes are preserved — the chunk is a window
    of the leaf's own storage, not an fp32 working copy."""
    flat, treedef = _zip_leaves(tree, plan)
    out = []
    for leaf, lp in zip(flat, plan.leaves):
        if not lp.sharded:
            out.append(leaf)
            continue
        v = jnp.reshape(leaf, (-1,))
        if lp.pad:
            v = jnp.pad(v, (0, lp.pad))
        out.append(slice_chunk(v, rank, lp.chunk))
    return jax.tree_util.tree_unflatten(treedef, out)


def unshard_tree(chunked, plan: UpdatePlan, axis: str = WORKER_AXIS):
    """Traced rebuild: ONE fused allgather per dtype.  All sharded chunks of
    a dtype concatenate into a single ``[C_total]`` vector, one
    ``all_gather(tiled=False)`` lifts it to ``[N, C_total]``, and each leaf
    slices its column block back out — ``[N, chunk] → flat[:size] → shape``.
    Values are exactly the chunks each worker cut, so the round trip is the
    identity bit for bit."""
    flat, treedef = _zip_leaves(chunked, plan)
    order = [i for i, lp in enumerate(plan.leaves) if lp.sharded]
    if not order:
        return chunked
    by_dtype: dict = {}
    for i in order:
        by_dtype.setdefault(plan.leaves[i].dtype, []).append(i)
    out = list(flat)
    for dtype, idxs in by_dtype.items():
        vec = flat[idxs[0]] if len(idxs) == 1 else \
            jnp.concatenate([flat[i] for i in idxs])
        gathered = lax.all_gather(vec, axis, tiled=False)  # [N, C_total]
        off = 0
        for i in idxs:
            lp = plan.leaves[i]
            block = lax.slice_in_dim(gathered, off, off + lp.chunk, axis=1)
            full = jnp.reshape(block, (-1,))[:lp.size]
            out[i] = jnp.reshape(full, lp.shape)
            off += lp.chunk
    return jax.tree_util.tree_unflatten(treedef, out)


def chunk_template(template, plan: UpdatePlan):
    """The per-worker shape template: sharded leaves become ``[chunk]``
    zeros of the leaf dtype (identical on every worker — broadcasting ONE
    template replicates it correctly, since optimizer state initializes to
    zeros); replicated leaves keep their full value."""
    flat, treedef = _zip_leaves(template, plan)
    out = [jnp.zeros((lp.chunk,), lp.dtype) if lp.sharded else leaf
           for leaf, lp in zip(flat, plan.leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def shard_host_boxed(tree, plan: UpdatePlan):
    """Host-side boxed init for state whose VALUES differ per worker chunk
    (the EASGD/ASGD center copies): each sharded leaf partitions into its
    ``[N, chunk]`` rows (row i IS worker i's chunk — ``steps.place_boxed``
    with the uniform ``P(workers)`` spec then hands each chip exactly its
    shard); replicated leaves broadcast to ``[N, ...]`` rows.  The
    broadcast path of ``steps.replicate_tree`` can't do this — it places
    ONE template on every row."""
    n = plan.n_workers
    flat, treedef = _zip_leaves(tree, plan)
    out = []
    for leaf, lp in zip(flat, plan.leaves):
        a = np.asarray(leaf)
        if lp.sharded:
            v = np.pad(a.reshape(-1), (0, lp.pad))
            out.append(v.reshape(n, lp.chunk))
        else:
            out.append(np.broadcast_to(a[None], (n,) + a.shape).copy())
    return jax.tree_util.tree_unflatten(treedef, out)


def unshard_boxed(boxed, plan: UpdatePlan):
    """Host/device inverse of :func:`shard_host_boxed` on BOXED state: a
    sharded leaf's ``[N, chunk]`` rows concatenate back to the full value
    (trimming the pad); a replicated leaf reads row 0.  Pure array-method
    algebra (reshape/slice), so it serves both the gathered-host checkpoint
    path and the on-device ``begin_val`` read."""
    flat, treedef = _zip_leaves(boxed, plan)
    out = []
    for leaf, lp in zip(flat, plan.leaves):
        if lp.sharded:
            out.append(leaf.reshape((-1,))[:lp.size].reshape(lp.shape))
        else:
            out.append(leaf[0])
    return jax.tree_util.tree_unflatten(treedef, out)


def shard_opt(opt: OptPair, plan: UpdatePlan,
              axis: str = WORKER_AXIS) -> OptPair:
    """Wrap ``opt`` so its state lives on the per-leaf local chunks.

    ``init`` builds state for the chunked template (the boxed
    ``[n_workers, chunk]`` layout is the partition); ``update`` slices
    grads/params down to this worker's chunks, runs the inner optimizer's
    elementwise math on them, and rebuilds full params with the fused
    allgather — inside whatever compiled step traces it, so
    ``steps_per_call`` scans and the exchange collectives are
    untouched.  Pad lanes are zeros in params AND grads, and every wrapped
    optimizer's update maps zeros to zeros, so the pad never leaks (and is
    trimmed by the rebuild regardless).  Requires bit-identical grads
    across workers (BSP grads mode) — ``model_base.compile_iter_fns``
    asserts it."""

    def init(params):
        return {"opt": opt.init(chunk_template(params, plan))}

    def update(grads, st, params, lr):
        rank = lax.axis_index(axis)
        my_g = shard_tree(grads, plan, rank)
        my_p = shard_tree(params, plan, rank)
        my_p_new, opt_state = opt.update(my_g, st["opt"], my_p, lr)
        new_params = unshard_tree(my_p_new, plan, axis)
        return new_params, {"opt": opt_state}

    return OptPair(init, update)


def flat_shard_opt(opt: OptPair, n_workers: int, params_template,
                   axis: str = WORKER_AXIS, model_shards: int = 1,
                   pspecs=None, model_axes: tuple = ()) -> OptPair:
    """The flat-chunk-everything configuration — ZeRO-1.  One ceil(P/N)
    chunk of the WHOLE flattened tree per worker instead of per-leaf
    chunks: simpler layout, fp32 working copy, and the model-parallel
    composition (``model_shards``/``pspecs``) the leaf-wise wrapper does
    not carry.  ``parallel/zero.py`` is a thin delegation to this.

    Model parallelism (round-4): under tensor/pipeline param specs the
    per-device params are already the LOCAL shard, so ``params_template``
    must be the local template (``steps.local_param_template``) and
    ``update`` composes unchanged — flatten local, slice my worker chunk,
    all-gather over workers rebuilds the local flat.  Only ``init``
    differs: the HOST state template must be global-shaped,
    ``model_shards`` × the chunk (one chunk per model-group rank), laid
    out so the boxed spec ``P(workers, <model axes>)`` hands each device
    exactly its chunk (``steps.state_partition_specs``)."""
    n_total = helper_funcs.tree_size(params_template)
    chunk = chunk_size(n_total, n_workers)
    padded = padded_size(n_total, n_workers)

    def init(params):
        # per-worker view: state for ONE chunk per model-group rank (boxed
        # to [n_workers, model_shards·chunk] by the step machinery and
        # sharded so each chip holds exactly its [chunk] shard)
        return {"opt": opt.init(
            jnp.zeros((model_shards * chunk,), jnp.float32))}

    def update(grads, st, params, lr):
        flat_g = helper_funcs.flatten_tree(grads, pad_to_multiple_of=padded)
        flat_p = helper_funcs.flatten_tree(params, pad_to_multiple_of=padded)
        rank = lax.axis_index(axis)
        my_g = slice_chunk(flat_g, rank, chunk)
        my_p = slice_chunk(flat_p, rank, chunk)
        my_p_new, opt_state = opt.update(my_g, st["opt"], my_p, lr)
        full = all_gather_chunks(my_p_new, axis)                # [padded]
        new_params = helper_funcs.unflatten_like(params, full)
        if model_axes and pspecs is not None:
            # the flat concat JOINS every leaf's varying-mesh-axes set, so
            # leaves replicated over a model axis (LN scales, biases)
            # come back statically unprovable as invariant even though
            # their values are (grads of replicated leaves are psum'd over
            # model in the tp backward).  Re-anchor each leaf bit-exactly
            # (steps.anchor_invariant) over exactly the model axes its spec
            # does NOT shard — per axis, so a 3-D mesh leaf sharded over
            # 'pipe' but replicated over 'model' anchors on 'model' only.
            from .steps import _is_spec, anchor_invariant, spec_mentions

            def anchor(s, v):
                axes = tuple(a for a in model_axes
                             if not spec_mentions(s, (a,)))
                return anchor_invariant(v, axes)

            new_params = jax.tree.map(anchor, pspecs, new_params,
                                      is_leaf=_is_spec)
        return new_params, {"opt": opt_state}

    return OptPair(init, update)
