"""PowerSGD factor matmuls fused with the collective staging pack.

PowerSGD ships per-leaf low-rank factors (``P = Mp @ Q``, ``Qn = Mpᵀ @ Ph``,
arXiv:1905.13727).  Unfused, every leaf's small matmul lands in its own HBM
buffer and a separate flatten/pad/concat pass assembles the collective's
staging buffer — one extra round-trip per factor per step.  The fused kernel
here emits each factor tile already padded to the staging row alignment, so
the MXU output IS the staging slice: the strategy concatenates the padded
tiles and issues ONE psum for every compressible leaf's factors instead of
one collective per leaf (``parallel/strategies.py`` PowerSGD).

House pattern (docs/design.md §24): pure-jnp oracle :func:`matmul_pack_jnp`
with the identical layout as the non-TPU dispatch target, interpret-mode
equality test, ``vma_of`` for shard_map vma propagation, dispatch gated by
``THEANOMPI_TPU_NO_PALLAS``.  The padded rows are zeros, so a psum over the
staging buffer is elementwise identical to the per-leaf psums it replaces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_util import dispatch_pallas as _dispatch_pallas
from ._pallas_util import vma_of as _vma_of

# Factor tiles are padded to the fp32 sublane multiple so every slice of the
# concatenated staging buffer stays tile-aligned.
_SUBLANE = 8
# Grid blocks: ROW_BLOCK output rows by K_BLOCK of the contraction dim, so the
# double-buffered tiles (2 MiB of ``m``, 1 MiB of lane-padded ``q``) stay far
# inside the 16 MiB scoped-VMEM limit whatever the leaf.  The Q-side pass
# contracts over a leaf's ROWS (9,216 for AlexNet's fc6, 25,088 for
# VGG-16's): riding that dim whole in VMEM does not compile on a v5e.
ROW_BLOCK = 256
K_BLOCK = 2048


def pad_rows(rows: int) -> int:
    """Staging row count for a factor with ``rows`` true rows."""
    return -(-rows // _SUBLANE) * _SUBLANE


def matmul_pack_jnp(m: jnp.ndarray, q: jnp.ndarray,
                    rows_pad: int) -> jnp.ndarray:
    """Oracle: ``m @ q`` zero-padded to ``[rows_pad, rank]`` — the staging
    slice layout the kernel emits directly from the MXU."""
    p = m @ q
    return jnp.pad(p, ((0, rows_pad - p.shape[0]), (0, 0)))


def _make_matmul_pack_kernel(rows: int, cols: int, block_rows: int,
                             block_k: int):
    n_k = -(-cols // block_k)

    def kernel(m_ref, q_ref, out_ref):
        """(block, bk) f32 × (bk, rank) f32 accumulated over the K grid axis
        into the (block, rank) f32 staging tile; rows ≥ the true row count
        are zeroed on the last K step so the downstream psum of the
        concatenated staging buffer matches the per-leaf psums exactly."""
        j, kk = pl.program_id(0), pl.program_id(1)

        @pl.when(kk == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        m, q = m_ref[:], q_ref[:]
        if cols % block_k:
            # the last K block reads past the operands: whatever lies there
            # must not reach the sum
            k0 = kk * block_k
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            row = jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
            m = jnp.where(k0 + lane < cols, m, 0.0)
            q = jnp.where(k0 + row < cols, q, 0.0)
        out_ref[:] += jnp.dot(m, q, preferred_element_type=jnp.float32)

        @pl.when(kk == n_k - 1)
        def _():
            rid = j * block_rows + jax.lax.broadcasted_iota(
                jnp.int32, (block_rows, 1), 0)
            out_ref[:] = jnp.where(rid < rows, out_ref[:], 0.0)
    return kernel


@functools.partial(jax.jit, static_argnames=("rows_pad", "interpret"))
def _matmul_pack_pallas(m: jnp.ndarray, q: jnp.ndarray, rows_pad: int,
                        interpret: bool) -> jnp.ndarray:
    rows, cols = m.shape
    rank = q.shape[1]
    block = min(ROW_BLOCK, rows_pad)
    block_k = cols if cols <= K_BLOCK else K_BLOCK
    return pl.pallas_call(
        _make_matmul_pack_kernel(rows, cols, block, block_k),
        grid=(pl.cdiv(rows_pad, block), pl.cdiv(cols, block_k)),
        in_specs=[
            pl.BlockSpec((block, block_k), lambda j, k: (j, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_k, rank), lambda j, k: (k, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, rank), lambda j, k: (j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows_pad, rank), jnp.float32,
                                       vma=_vma_of(m, q)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(m, q)


def matmul_pack(m: jnp.ndarray, q: jnp.ndarray,
                rows_pad: int | None = None) -> jnp.ndarray:
    """``m [rows, cols] @ q [cols, rank]`` emitted as a zero-padded
    ``[rows_pad, rank]`` staging tile (``rows_pad`` defaults to the sublane
    round-up of ``rows``).  For the Q-side factor pass callers hand in the
    transposed operand (``matmul_pack(Mp.T, Ph, ...)``)."""
    rows = m.shape[0]
    if rows_pad is None:
        rows_pad = pad_rows(rows)
    assert rows_pad >= rows and rows_pad % _SUBLANE == 0, (rows, rows_pad)
    if not _dispatch_pallas():
        return matmul_pack_jnp(m, q, rows_pad)
    return _matmul_pack_pallas(m, q, rows_pad, False)


# pallas_call wrapper → jnp oracle pairing (tpulint ``oracle-pair`` checker).
PALLAS_ORACLES = {
    "_matmul_pack_pallas": "matmul_pack_jnp",
}
