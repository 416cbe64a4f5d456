"""Compression ops for the compressed exchanger (onebit kernels + topk).

TPU-native successor to the reference's in-repo native code: Theano-MPI's
``Exch_asa16``/``Exch_copper16`` compiled inline fp32↔fp16 CUDA kernels at
runtime via ``pycuda.compiler.SourceModule`` to halve wire bandwidth
(SURVEY.md §2.9, items N1/N2).  Here the compression is more aggressive —
1 bit per element — and the kernels are **Pallas TPU kernels** (the TPU-native
kernel language), with a pure-jnp implementation in the identical bit layout
kept as the numerical oracle and as the dispatch target on non-TPU backends
(and under ``THEANOMPI_TPU_NO_PALLAS=1``).  The kernel unit tests run the
Pallas pair in interpret mode against the oracle bit-for-bit.

Beyond the original sign pack/unpack pair, this module carries the fused
single-pass pipelines (docs/design.md §24):

* **onebit encode** (:func:`pack_signs_encode`): per 256×128 block, read the
  gradient and the error state once, form ``c = flat + state`` in VMEM, and
  emit BOTH the packed sign tile and ``|c|`` — ``c`` itself never exists in
  HBM.  The follow-up :func:`signed_residual` turns ``|c|`` + packed bits +
  the scalar scale into the new error state ``c − scale·sign(c)`` in one more
  pass (bit-identical to the unfused formula; see the oracle's docstring).
* **onebit decode** (:func:`unpack_signs_weighted_mean`): the decode+weighted
  accumulate with the ``/size`` mean folded into the per-worker scales, so the
  full-length division pass disappears.

The topk wire (:func:`topk_encode` / :func:`topk_decode`) is plain jnp on
every backend: its Pallas pair was deleted after its first compiled run on a
v5e (ROADMAP S6 has the numbers) — the decode ran at half the speed of XLA's
scatter and the encode no faster than ``lax.top_k``.

Every ``pl.pallas_call`` wrapper here is paired with its jnp oracle in
:data:`PALLAS_ORACLES`; the tpulint ``oracle-pair`` checker enforces the
pairing and the existence of an equality test.

Wire format (internal contract between :func:`pack_signs` and the unpackers —
chosen for TPU tiling, NOT byte-compatible with anything external):

* the fp32 input vector ``c`` of length ``n`` (``n % PACK_ALIGN == 0``) is
  viewed as blocks of 256 sublanes × 128 lanes;
* within a block, packed word ``[r, l]`` (r∈[0,8)) collects bit ``b`` from
  input row ``8b + r`` — so every bit plane is a contiguous (8, 128) fp32
  tile and every output tile is a full (8, 128) uint32 tile.  No intra-lane
  shuffles anywhere.
* packed shape: ``[n // 4096, 128]`` uint32 = n/8 bytes on the wire (32×
  smaller than fp32).

Layout contract: input length must be a multiple of :data:`PACK_ALIGN`
(= 32768 = 256 sublanes × 128 lanes) so every Pallas grid block is full.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_util import dispatch_pallas as _dispatch_pallas
from ._pallas_util import vma_of as _vma_of

# 256 fp32 sublanes × 128 lanes per grid block: packs to one (8, 128) uint32
# tile, keeping both sides of the kernel exactly tile-aligned.
BLOCK_ROWS = 256
LANES = 128
PACK_ALIGN = BLOCK_ROWS * LANES          # 32768 elements per grid block
_WORDS_PER_BLOCK = 8                     # uint32 rows produced per block


def _check_len(n: int) -> None:
    assert n % PACK_ALIGN == 0, (
        f"compressed exchange needs length % {PACK_ALIGN} == 0, got {n} "
        "(flatten_tree(pad_to_multiple_of=PACK_ALIGN) upstream)")


# ---------------------------------------------------------------------------
# jnp reference implementations (oracle + fallback)
# ---------------------------------------------------------------------------

def pack_signs_jnp(c: jnp.ndarray) -> jnp.ndarray:
    """Oracle: f32 [n] → uint32 [n//4096, 128] in the wire layout above."""
    n = c.shape[0]
    _check_len(n)
    nb = n // PACK_ALIGN
    bits = (c >= 0).astype(jnp.uint32).reshape(nb, 32, _WORDS_PER_BLOCK, LANES)
    shifts = jnp.arange(32, dtype=jnp.uint32).reshape(1, 32, 1, 1)
    # Bit positions are disjoint across the reduced axis, so sum == OR.
    words = jnp.sum(bits << shifts, axis=1, dtype=jnp.uint32)
    return words.reshape(nb * _WORDS_PER_BLOCK, LANES)


def unpack_signs_jnp(packed: jnp.ndarray) -> jnp.ndarray:
    """Oracle inverse: uint32 [m, 128] → f32 [32·m·128] of ±1."""
    m = packed.shape[0]
    nb = m // _WORDS_PER_BLOCK
    p = packed.reshape(nb, 1, _WORDS_PER_BLOCK, LANES)
    shifts = jnp.arange(32, dtype=jnp.uint32).reshape(1, 32, 1, 1)
    bits = (p >> shifts) & jnp.uint32(1)
    return (bits.astype(jnp.float32) * 2.0 - 1.0).reshape(-1)


def unpack_signs_weighted_sum_jnp(all_packed: jnp.ndarray,
                                  scales: jnp.ndarray) -> jnp.ndarray:
    """Oracle: decode [w, m, 128] packed buffers → Σ_w scales[w]·signs[w]."""
    w = all_packed.shape[0]
    decoded = jax.vmap(unpack_signs_jnp)(all_packed)       # [w, n]
    return jnp.sum(decoded * scales.reshape(w, 1), axis=0)


def pack_signs_encode_jnp(flat: jnp.ndarray, state: jnp.ndarray):
    """Oracle for the fused onebit encode: ``c = flat + state`` →
    (packed signs of c, |c|).  Same packed bit layout as
    :func:`pack_signs_jnp` applied to the materialized sum."""
    c = flat + state
    return pack_signs_jnp(c), jnp.abs(c)


def signed_residual_jnp(absc: jnp.ndarray, packed: jnp.ndarray,
                        scale: jnp.ndarray) -> jnp.ndarray:
    """Oracle for the fused residual: reconstruct ``c − scale·sign(c)`` from
    ``|c|`` and the packed sign bits.

    Bit-exact equivalence with the unfused ``c − scale·sign(where(c==0,1,c))``
    (the packed bit for c == 0 is 1, matching that ``where``):

    * c ≥ 0 (bit 1): ``c − scale·(+1) = c ⊖ scale = |c| ⊖ scale``  (|c| = c).
    * c < 0 (bit 0): ``c − scale·(−1) = c ⊕ scale = scale ⊖ |c|`` — IEEE
      ``x ⊖ y`` is ``x ⊕ (−y)`` with an exact sign flip, and |c| = −c exactly.
    """
    sign_pos = unpack_signs_jnp(packed) > 0
    return jnp.where(sign_pos, absc - scale, scale - absc)


def unpack_signs_weighted_mean_jnp(all_packed: jnp.ndarray,
                                   scales: jnp.ndarray,
                                   size: int) -> jnp.ndarray:
    """Oracle: decode + weighted accumulate with the ``/size`` mean folded
    into the scales — ``Σ_w (scales[w]/size)·signs[w]``.  The full-length
    division pass of the unfused ``sum/size`` becomes a [w]-length one."""
    return unpack_signs_weighted_sum_jnp(all_packed, scales / jnp.float32(size))


# ---------------------------------------------------------------------------
# topk wire (plain jnp on every backend)
# ---------------------------------------------------------------------------

def topk_encode(c2: jnp.ndarray, k: int):
    """Topk encode: per chunk row of ``c2`` [rows, chunk] select the k
    largest-|·| entries, cast to the wire dtypes, and write the bf16
    rounding residual back in place.

    Returns ``(wire_vals bf16 [rows, k], wire_idx int16 [rows, k],
    new_c2 f32 [rows, chunk])``.  Tie-break follows ``lax.top_k``: equal
    magnitudes pick the lower index first.
    """
    rows = c2.shape[0]
    _, idx = jax.lax.top_k(jnp.abs(c2), k)                 # [rows, k]
    vals = jnp.take_along_axis(c2, idx, axis=1)            # f32 [rows, k]
    # Round to bf16 with reduce_precision, not an f32→bf16→f32 cast pair:
    # on the TPU XLA keeps excess precision through such a pair, the
    # residual came out exactly zero and the wire's rounding error never
    # reached the error feedback (seen on a v5e, PR 21).
    rounded = jax.lax.reduce_precision(vals, exponent_bits=8,
                                       mantissa_bits=7)
    wire_vals = rounded.astype(jnp.bfloat16)
    wire_idx = idx.astype(jnp.int16)
    residual = vals - rounded
    r = jnp.arange(rows)[:, None]
    new_c2 = c2.at[r, idx].set(residual)
    return wire_vals, wire_idx, new_c2


def topk_decode(all_vals: jnp.ndarray, all_idx: jnp.ndarray,
                chunk: int, size: int = 1) -> jnp.ndarray:
    """Topk decode: expand every worker's (vals, idx) chunk rows into the
    dense vector — dense[r·chunk + idx] += val summed over workers, divided
    by ``size`` (the worker mean folded into the decode so no full-length
    division pass follows; ``acc / size`` per element is bit-identical to
    dividing the assembled dense vector).
    [w, rows, k] bf16/int16 → f32 [rows·chunk]."""
    w, rows, k = all_vals.shape
    base = (jnp.arange(rows, dtype=jnp.int32) * chunk).reshape(1, rows, 1)
    gidx = all_idx.astype(jnp.int32) + base                # [w, rows, k]
    dense = jnp.zeros((rows * chunk,), jnp.float32)
    dense = dense.at[gidx.reshape(-1)].add(
        all_vals.astype(jnp.float32).reshape(-1))
    return dense / jnp.float32(size) if size != 1 else dense


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _pack_kernel(x_ref, out_ref):
    """(256, 128) f32 block → (8, 128) uint32 block.

    Bit plane b is the contiguous fp32 tile rows [8b, 8b+8); planes are OR'd
    together after shifting — pure VPU work on full (8, 128) registers.
    """
    word = jnp.zeros((_WORDS_PER_BLOCK, LANES), jnp.uint32)
    for b in range(32):
        plane = x_ref[8 * b:8 * (b + 1), :]
        word = word | ((plane >= 0).astype(jnp.uint32) << np.uint32(b))
    out_ref[:] = word


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pack_pallas(x2d: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    nb = x2d.shape[0] // BLOCK_ROWS
    return pl.pallas_call(
        _pack_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda j: (j, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((_WORDS_PER_BLOCK, LANES), lambda j: (j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nb * _WORDS_PER_BLOCK, LANES),
                                       jnp.uint32, vma=_vma_of(x2d)),
        interpret=interpret,
    )(x2d)


def _make_unpack_wsum_kernel(n_workers: int):
    def kernel(packed_ref, scales_ref, out_ref):
        """packed (W, 8, 128) u32 + scales (W,) → (256, 128) f32 of
        Σ_w scale_w · sign_w  (decode fused with the weighted accumulate, so
        the fp32 expansion never round-trips through HBM)."""
        total = jnp.float32(0.0)
        for w in range(n_workers):
            total = total + scales_ref[w]
        for b in range(32):
            acc = jnp.zeros((_WORDS_PER_BLOCK, LANES), jnp.float32)
            for w in range(n_workers):
                # the 0/1 bit goes to int32 first: Mosaic has no
                # uint32 → float32 cast
                bits = ((packed_ref[w] >> np.uint32(b))
                        & np.uint32(1)).astype(jnp.int32)
                acc = acc + bits.astype(jnp.float32) * (2.0 * scales_ref[w])
            # Σ scale·(2·bit − 1) = Σ 2·scale·bit − Σ scale
            out_ref[8 * b:8 * (b + 1), :] = acc - total
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def _unpack_wsum_pallas(all_packed: jnp.ndarray, scales: jnp.ndarray,
                        interpret: bool) -> jnp.ndarray:
    w, m, _ = all_packed.shape
    nb = m // _WORDS_PER_BLOCK
    kernel = _make_unpack_wsum_kernel(w)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((w, _WORDS_PER_BLOCK, LANES), lambda j: (0, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda j: (j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nb * BLOCK_ROWS, LANES), jnp.float32,
                                       vma=_vma_of(all_packed, scales)),
        interpret=interpret,
    )(all_packed, scales)


def _encode_kernel(flat_ref, state_ref, packed_ref, abs_ref):
    """(256, 128) f32 flat + state blocks → (8, 128) u32 packed + (256, 128)
    f32 |c|.  ``c = flat + state`` lives only in VMEM registers: the fused
    encode reads the error-fed vector once and never writes ``c`` to HBM."""
    word = jnp.zeros((_WORDS_PER_BLOCK, LANES), jnp.uint32)
    for b in range(32):
        c = flat_ref[8 * b:8 * (b + 1), :] + state_ref[8 * b:8 * (b + 1), :]
        word = word | ((c >= 0).astype(jnp.uint32) << np.uint32(b))
        abs_ref[8 * b:8 * (b + 1), :] = jnp.abs(c)
    packed_ref[:] = word


@functools.partial(jax.jit, static_argnames=("interpret",))
def _encode_pallas(flat2d: jnp.ndarray, state2d: jnp.ndarray,
                   interpret: bool):
    nb = flat2d.shape[0] // BLOCK_ROWS
    vma = _vma_of(flat2d, state2d)
    block_in = pl.BlockSpec((BLOCK_ROWS, LANES), lambda j: (j, 0),
                            memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _encode_kernel,
        grid=(nb,),
        in_specs=[block_in, block_in],
        out_specs=[
            pl.BlockSpec((_WORDS_PER_BLOCK, LANES), lambda j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * _WORDS_PER_BLOCK, LANES), jnp.uint32,
                                 vma=vma),
            jax.ShapeDtypeStruct((nb * BLOCK_ROWS, LANES), jnp.float32,
                                 vma=vma),
        ],
        interpret=interpret,
    )(flat2d, state2d)


def _residual_kernel(abs_ref, packed_ref, scale_ref, out_ref):
    """(256, 128) f32 |c| + (8, 128) u32 packed + SMEM scale →
    (256, 128) f32 residual ``c − scale·sign(c)``, recovered branch-free as
    ``where(bit, |c| − scale, scale − |c|)`` (bit-exact; see the oracle)."""
    scale = scale_ref[0]
    for b in range(32):
        bit = (packed_ref[:] >> np.uint32(b)) & np.uint32(1)
        a = abs_ref[8 * b:8 * (b + 1), :]
        out_ref[8 * b:8 * (b + 1), :] = jnp.where(bit == 1, a - scale,
                                                  scale - a)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _residual_pallas(abs2d: jnp.ndarray, packed: jnp.ndarray,
                     scale: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    nb = abs2d.shape[0] // BLOCK_ROWS
    return pl.pallas_call(
        _residual_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_WORDS_PER_BLOCK, LANES), lambda j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda j: (j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nb * BLOCK_ROWS, LANES), jnp.float32,
                                       vma=_vma_of(abs2d, packed, scale)),
        interpret=interpret,
    )(abs2d, packed, scale.reshape(1).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Public API (dispatching)
# ---------------------------------------------------------------------------

def pack_signs(c: jnp.ndarray) -> jnp.ndarray:
    """Pack sign bits of ``c`` (>=0 → 1, <0 → 0), 32 per uint32 word.

    ``c`` must be 1-D with length % PACK_ALIGN == 0.  Returns
    ``[len(c)//4096, 128]`` uint32 (= len(c)/8 bytes on the wire).
    """
    n = c.shape[0]
    _check_len(n)
    if not _dispatch_pallas():
        return pack_signs_jnp(c)
    return _pack_pallas(c.reshape(n // LANES, LANES), False)


def unpack_signs(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_signs`: uint32 [m, 128] → f32 [32·m·128] of ±1."""
    if not _dispatch_pallas():
        return unpack_signs_jnp(packed)
    one = jnp.ones((1,), jnp.float32)
    return _unpack_wsum_pallas(packed[None], one, False).reshape(-1)


def unpack_signs_weighted_sum(all_packed: jnp.ndarray,
                              scales: jnp.ndarray) -> jnp.ndarray:
    """Decode ``[n_workers, m, 128]`` packed sign buffers and return
    ``sum_w scales[w] * signs[w]`` as float32 ``[32·m·128]``.

    This is the decode+accumulate half of the compressed allreduce: each
    worker runs it locally after the all-gather of packed bits, so only bits
    ever cross ICI.
    """
    if not _dispatch_pallas():
        return unpack_signs_weighted_sum_jnp(all_packed, scales)
    return _unpack_wsum_pallas(
        all_packed, scales.astype(jnp.float32), False).reshape(-1)


def pack_signs_encode(flat: jnp.ndarray, state: jnp.ndarray):
    """Fused onebit encode: ``c = flat + state`` formed in VMEM, returning
    ``(packed signs of c, |c|)`` — one read of each input, no HBM copy of
    ``c``.  Both 1-D inputs must share a length % PACK_ALIGN == 0."""
    n = flat.shape[0]
    _check_len(n)
    if not _dispatch_pallas():
        return pack_signs_encode_jnp(flat, state)
    packed, abs2d = _encode_pallas(flat.reshape(n // LANES, LANES),
                                   state.reshape(n // LANES, LANES), False)
    return packed, abs2d.reshape(-1)


def signed_residual(absc: jnp.ndarray, packed: jnp.ndarray,
                    scale: jnp.ndarray) -> jnp.ndarray:
    """New onebit error state ``c − scale·sign(c)`` from ``|c|`` + packed
    sign bits + the scalar scale (bit-exact vs the unfused formula)."""
    n = absc.shape[0]
    _check_len(n)
    if not _dispatch_pallas():
        return signed_residual_jnp(absc, packed, scale)
    return _residual_pallas(absc.reshape(n // LANES, LANES), packed,
                            scale, False).reshape(-1)


def unpack_signs_weighted_mean(all_packed: jnp.ndarray, scales: jnp.ndarray,
                               size: int) -> jnp.ndarray:
    """Decode ``[n_workers, m, 128]`` packed buffers into the worker-mean
    ``Σ_w (scales[w]/size)·signs[w]`` — the ``/size`` folded into the [w]
    scale vector so no full-length division pass follows the decode."""
    ws = scales.astype(jnp.float32) / jnp.float32(size)
    if not _dispatch_pallas():
        return unpack_signs_weighted_sum_jnp(all_packed, ws)
    return _unpack_wsum_pallas(all_packed, ws, False).reshape(-1)


# pallas_call wrapper → jnp oracle pairing, enforced by the tpulint
# ``oracle-pair`` checker (every wrapper must appear here, every oracle must
# be defined in this module, and a test must reference both).
PALLAS_ORACLES = {
    "_pack_pallas": "pack_signs_jnp",
    "_unpack_wsum_pallas": "unpack_signs_weighted_sum_jnp",
    "_encode_pallas": "pack_signs_encode_jnp",
    "_residual_pallas": "signed_residual_jnp",
}
