"""Ring attention — sequence-parallel exact attention over the ICI ring.

Beyond-parity capability (the reference is a CNN data-parallel framework
with no sequence models, SURVEY.md §1/§5): long-context training needs the
sequence dimension sharded across chips, and the TPU-native way to make
exact attention work under that sharding is the ring algorithm (Liu et al.
2023's blockwise formulation): each chip holds one Q/K/V sequence block,
K/V blocks rotate around the ring via ``lax.ppermute``, and a numerically
stable online-softmax accumulator combines the per-block partial attentions
— compute overlaps the neighbor exchange hop by hop, HBM never holds the
full [T, T] score matrix, and the wire cost per chip is one K/V block per
hop riding ICI.

:func:`ring_attention` is written to be traced INSIDE a ``shard_map`` whose
``axis`` shards the sequence dimension (the same pattern as the exchanger
collectives, ``parallel/strategies.py``).  :func:`ring_attention_sharded`
wraps it for direct calls on a sequence mesh.  Exactness vs a single-device
softmax-attention oracle is pinned in ``tests/test_ring_attention.py``,
causal and non-causal, fwd AND grads.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..jax_compat import shard_map as _shard_map

NEG_INF = -1e30


def ring_attention(q, k, v, *, axis: str, causal: bool = False,
                   scale: Optional[float] = None):
    """Exact attention with the sequence dimension sharded over ``axis``.

    Args (per-device shards, inside ``shard_map``):
      q, k, v: ``[B, H, T_local, D]`` — this device's sequence block.
      causal: standard causal masking in GLOBAL positions.
      scale: defaults to ``1/sqrt(D)``.

    Returns ``[B, H, T_local, D]`` — this device's block of the attention
    output, bit-comparable to slicing full attention (up to fp accumulation
    order).
    """
    n = lax.psum(1, axis)
    idx = lax.axis_index(axis)
    b, h, t_loc, d = q.shape
    scale = (1.0 / (d ** 0.5)) if scale is None else scale
    qf = q.astype(jnp.float32) * scale

    q_pos = idx * t_loc + jnp.arange(t_loc)             # global q positions
    perm = [(i, (i + 1) % n) for i in range(n)]

    def block(o, m, l, kj, vj, j):
        """Online-softmax accumulation of one K/V block.  After j forward
        rotations this device holds the block that originated at device
        (idx - j) mod n."""
        src = (idx - j) % n
        k_pos = src * t_loc + jnp.arange(t_loc)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj.astype(jnp.float32))
        if causal:
            valid = q_pos[:, None] >= k_pos[None, :]    # [Tq, Tk]
            s = jnp.where(valid[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # rows with no valid key yet keep m == NEG_INF; exp(s - m) would be
        # exp(0)=1 on masked entries, so re-zero them explicitly
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(valid[None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vj.astype(jnp.float32))
        return o, m_new, l

    def hop(carry, j):
        o, m, l, kj, vj = carry
        # rotate BEFORE compute (hops 1..n-1): the local block was consumed
        # outside the scan, so only n-1 exchanges cross ICI in total
        kj = lax.ppermute(kj, axis, perm)
        vj = lax.ppermute(vj, axis, perm)
        o, m, l = block(o, m, l, kj, vj, j)
        return (o, m, l, kj, vj), None

    # derive the zero-init carries from qf so they inherit its FULL set of
    # device-varying mesh axes (on a 2-D data×seq mesh q varies over both;
    # fresh zeros would be device-invariant and fail scan's carry typing)
    o0 = qf * 0.0
    m0 = qf.max(axis=-1) * 0.0 + NEG_INF
    l0 = qf.max(axis=-1) * 0.0
    o0, m0, l0 = block(o0, m0, l0, k, v, 0)             # the local block
    if n > 1:
        (o, m, l, _, _), _ = lax.scan(hop, (o0, m0, l0, k, v),
                                      jnp.arange(1, n))
    else:
        o, m, l = o0, m0, l0
    # causal row 0 of device 0 always has ≥1 valid key (itself), so l > 0
    out = o / l[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, *, axis: str,
                           causal: bool = False,
                           scale: Optional[float] = None):
    """Convenience wrapper: shard ``[B, H, T, D]`` tensors over ``axis`` on
    ``mesh`` (sequence dim) and run :func:`ring_attention` under
    ``shard_map``."""
    spec = P(None, None, axis, None)
    fn = _shard_map(
        partial(ring_attention, axis=axis, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    sh = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    return fn(q, k, v)


def attention_reference(q, k, v, *, causal: bool = False,
                        scale: Optional[float] = None,
                        window: Optional[int] = None):
    """Single-device softmax attention oracle (tests).  ``window`` (with
    ``causal``): no key further back than ``window - 1``."""
    d = q.shape[-1]
    scale = (1.0 / (d ** 0.5)) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        valid = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        if window is not None:
            valid &= jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :] \
                < window
        s = jnp.where(valid[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
