"""ZeRO-1: optimizer state sharded over the data-parallel workers.

Beyond-parity capability (the reference — Theano-MPI, SURVEY.md §1 —
replicates optimizer state per GPU, like every pre-ZeRO framework): under
BSP every worker applies the SAME reduced gradient, so the momentum /
second-moment buffers are identical replicas — pure memory waste.  ZeRO
stage 1 (Rajbhandari et al. 2020) shards them: each worker keeps 1/N of the
flattened optimizer state, updates only ITS parameter chunk, and one
``all_gather`` rebuilds the full parameters for the next forward pass.

Since the leaf-wise update-plane schema landed
(``parallel/update_sharding.py``, docs/design.md §23), this module is a
THIN CONFIGURATION of that wrapper: :func:`zero1` is
``update_sharding.flat_shard_opt`` — the flat-chunk-everything layout,
which additionally carries the tensor/pipeline composition
(``model_shards``/``pspecs``).  Config ``zero_opt=true`` behaves exactly
as before.  Bit-equivalence with the
unsharded optimizer holds exactly (elementwise update math on disjoint
chunks; no reduction-order change) and is pinned in ``tests/test_zero.py``,
ragged param counts (P=10, N=4 — explicit ``padded_size`` padding)
included.
"""

from __future__ import annotations

from ..utils.opt import OptPair
from .mesh import WORKER_AXIS
from .update_sharding import chunk_size, flat_shard_opt, padded_size

__all__ = ["chunk_size", "padded_size", "rechunk_boxed", "zero1"]


def rechunk_boxed(arr, n_new: int, shards: int, local_total: int):
    """Re-partition a saved boxed ZeRO state leaf ``[n_saved, shards·chunk_s]``
    onto ``[n_new, shards·chunk_new]`` (worker-count-portable resume).

    Dim 1 is laid out one chunk per model-group rank (``state_partition_
    specs`` shards it over the model axes), so model rank r's local flat
    vector is the concatenation over workers of column block r — reassemble
    each rank's flat, trim its padding, re-pad and re-slice for the new
    worker count.  The model-axes sizes themselves must match (``shards``
    and ``local_total`` are properties of the model layout, not of N).
    """
    import numpy as np
    n_s = int(arr.shape[0])
    assert arr.ndim == 2 and arr.shape[1] % shards == 0, arr.shape
    chunk_s = arr.shape[1] // shards
    # [n_s, shards, chunk_s] -> [shards, n_s·chunk_s] -> trim pad
    per_rank = np.transpose(np.asarray(arr).reshape(n_s, shards, chunk_s),
                            (1, 0, 2)).reshape(shards, -1)[:, :local_total]
    chunk_n = chunk_size(local_total, n_new)
    per_rank = np.pad(per_rank, ((0, 0), (0, padded_size(local_total, n_new)
                                          - local_total)))
    return np.transpose(per_rank.reshape(shards, n_new, chunk_n),
                        (1, 0, 2)).reshape(n_new, shards * chunk_n)


def zero1(opt: OptPair, n_workers: int, params_template,
          axis: str = WORKER_AXIS, model_shards: int = 1,
          pspecs=None, model_axes: tuple = ()) -> OptPair:
    """Wrap ``opt`` so its state lives flat-chunked over ``axis`` — the
    ZeRO-1 special case of the update-sharding wrapper.  See
    :func:`update_sharding.flat_shard_opt` for the layout contract."""
    return flat_shard_opt(opt, n_workers, params_template, axis=axis,
                          model_shards=model_shards, pspecs=pspecs,
                          model_axes=model_axes)
