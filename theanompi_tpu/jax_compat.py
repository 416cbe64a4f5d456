"""The one place this package touches jax's moving SPMD surface:
``shard_map``, the vma "varying" marker, and async collectives.

The target is the jax this container and the chip machine run (0.9.0):
``jax.shard_map`` with the vma varying-axes type system, ``jax.typeof`` and
``lax.pcast``.  Every call site goes through this module (the tpulint
``compat-boundary`` checker enforces it), so the next API move is absorbed
in one file.

**Async collective start/done pairs** (the bucketed-overlap wire,
``parallel/buckets.py``): some jaxlibs expose an explicit async
collective surface (``lax.psum_start``/``psum_done``-shaped APIs that
return an in-flight token); most — including this one — do not, and rely
on XLA's latency-hiding scheduler to convert independent collectives to
``<op>-start``/``<op>-done`` HLO pairs itself.  The shims below give the
exchange path ONE calling convention for both worlds:

* when the running jaxlib exposes the async API, ``<x>_start`` returns
  its real in-flight ticket and ``<x>_done`` awaits it;
* otherwise (the sync fallback) ``<x>_start`` issues the plain
  collective eagerly — the ticket IS the result — and ``<x>_done``
  unwraps it.  Scheduling-wise nothing is lost: each bucket is still its
  own independent collective for the latency-hiding scheduler to
  overlap with the backward pass.

Discipline contract (enforced by tpulint's collective-discipline
checker): every ``<x>_start`` call's ticket must reach a matching
``<x>_done`` in the same scope — a dropped ticket is a leaked in-flight
collective the day a real async surface binds.
"""

from __future__ import annotations

import inspect
from typing import Any, NamedTuple

import jax
from jax import lax


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def vary(x, axis_name: str):
    """Mark a replicated value as device-varying over ``axis_name`` for
    shard_map's vma type system (scan carries that accumulate per-worker
    values need this).  Idempotent: an already-varying value passes
    through — ``pcast`` raises on varying→varying, and callers like
    ``steps._revary_bn`` see either kind (the async rules' ``sync_bn`` is
    the identity, so their BN stats arrive varying; BSP's pmean'd stats
    arrive invariant)."""
    if axis_name in jax.typeof(x).vma:
        return x
    return lax.pcast(x, (axis_name,), to="varying")


def splash_attention(q, k, v, *, axis_name: str, mask, **tiles):
    """jax's Pallas TPU splash-attention kernel over ``[B, H, T, hd]``,
    callable inside a ``shard_map`` over ``axis_name`` that checks vma
    (every step of this package).  The kernel takes no scale: ``q`` comes
    scaled.  ``mask`` is known at trace time: ``"causal"``, ``"full"``, or
    ``("window", w)``, causal and no key further back than ``w - 1``
    (``0 <= t - s < w``); tiles the mask leaves empty are never fetched.
    ``tiles`` are the kernel's ``BlockSizes``.  The batch goes in as more
    heads (one mask serves them all), so nothing is vmapped.

    The library builds its kernels' ``out_shape``s without ``vma``, which
    ``pallas_call`` refuses while the check is on, in the forward call and
    in the backward one that is traced long after this function has
    returned; so the library module's view of ``jax`` is replaced, once and
    for the process, by one whose ``ShapeDtypeStruct`` marks an output
    varying over ``axis_name`` wherever that axis is manual.  A jax that no
    longer builds them that way fails here, by name.  The mask's block
    tables are constants of the trace and are marked varying the same way."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    view = sk.jax
    if not isinstance(view, _VaryingOutShapes):
        assert view is jax and \
            "jax.ShapeDtypeStruct(" in inspect.getsource(sk), (
                "jax's splash_attention_kernel module no longer builds its "
                "out_shapes through its global jax.ShapeDtypeStruct: "
                "jax_compat.splash_attention has nothing to give vma to")
        view = sk.jax = _VaryingOutShapes()
    view.axes.add(axis_name)
    b, h, t, _ = q.shape
    if mask == "causal":
        mask = sm.CausalMask((t, t))
    elif mask == "full":
        mask = sm.FullMask((t, t))
    else:
        kind, w = mask
        assert kind == "window" and 1 <= w, mask
        mask = sm.LocalMask((t, t), window_size=(w - 1, 0), offset=0)
    kernel = sk.make_splash_mha(
        sm.MultiHeadMask([mask] * (b * h)), head_shards=1, q_seq_shards=1,
        block_sizes=sk.BlockSizes(**tiles))
    if axis_name in jax.sharding.get_abstract_mesh().manual_axes:
        kernel = jax.tree.map(lambda a: vary(a, axis_name), kernel)
    return kernel(*(a.reshape(b * h, t, a.shape[-1]) for a in (q, k, v))
                  ).reshape(b, h, t, v.shape[-1])


class _VaryingOutShapes:
    """``jax``, but for ``ShapeDtypeStruct``."""

    def __init__(self):
        self.axes = set()       # the axes a caller's step is mapped over

    def __getattr__(self, name):
        return getattr(jax, name)

    def ShapeDtypeStruct(self, shape, dtype, **kw):
        manual = jax.sharding.get_abstract_mesh().manual_axes
        varying = frozenset(a for a in manual if a in self.axes)
        if varying:
            kw.setdefault("vma", varying)
        return jax.ShapeDtypeStruct(shape, dtype, **kw)


# -- async collective start/done ---------------------------------------------

# True when the running jaxlib exposes a real async start/done surface;
# the sync fallback below is used otherwise.  False on jax 0.9.0
# (``lax.psum_start`` is absent).
HAS_ASYNC_COLLECTIVES = all(
    hasattr(lax, n) for n in ("psum_start", "psum_done"))


class _SyncTicket(NamedTuple):
    """Sync-fallback in-flight token: the collective already ran eagerly,
    the ticket carries its result to the paired ``<x>_done``."""

    value: Any


def psum_start(x, axis_name):
    """Begin one bucket's cross-worker sum; returns an in-flight ticket
    for :func:`psum_done`."""
    if HAS_ASYNC_COLLECTIVES:
        return lax.psum_start(x, axis_name)
    return _SyncTicket(lax.psum(x, axis_name))


def psum_done(ticket):
    """Await one :func:`psum_start` ticket and return the reduced value."""
    if HAS_ASYNC_COLLECTIVES:
        return lax.psum_done(ticket)
    return ticket.value


def all_gather_start(x, axis_name):
    """Begin one bucket's all-gather (compressed wires ship packed
    buckets); returns an in-flight ticket for :func:`all_gather_done`."""
    if hasattr(lax, "all_gather_start"):
        return lax.all_gather_start(x, axis_name)
    return _SyncTicket(lax.all_gather(x, axis_name))


def all_gather_done(ticket):
    """Await one :func:`all_gather_start` ticket."""
    if hasattr(lax, "all_gather_done"):
        return lax.all_gather_done(ticket)
    return ticket.value


def ppermute_start(x, axis_name, perm):
    """Begin one bucket's peer-to-peer permute (GoSGD gossip payloads);
    returns an in-flight ticket for :func:`ppermute_done`."""
    if hasattr(lax, "ppermute_start"):
        return lax.ppermute_start(x, axis_name, perm)
    return _SyncTicket(lax.ppermute(x, axis_name, perm))


def ppermute_done(ticket):
    """Await one :func:`ppermute_start` ticket."""
    if hasattr(lax, "ppermute_done"):
        return lax.ppermute_done(ticket)
    return ticket.value
