"""Compiled SPMD train/val step assembly.

This is where Theano-MPI's ``model.compile_iter_fns()`` → ``theano.function``
train/val functions (SURVEY.md §2.5, §3.4) become ``jax.jit``-compiled SPMD
programs.  The reference compiled one opaque native function per sub-batch
(cuDNN fwd → loss → bwd → in-place momentum update); here the WHOLE hot
iteration — microbatch ``lax.scan``, backward pass, cross-worker exchange,
optimizer update — is one XLA program per step, so the collective fuses with
compute and rides ICI with no host round-trip.

State layout (uniform across all four rules — see SURVEY.md §2.2): every
state leaf carries a leading ``[n_workers]`` axis sharded over the
``'workers'`` mesh axis, so each chip holds exactly one replica.  For BSP the
replicas stay bit-identical (the exchanger reduces gradients); for
EASGD/ASGD/GoSGD they diverge between exchanges, which is the whole point of
those rules.  A uniform "boxed" layout means one code path, no replication
bookkeeping, and zero memory overhead versus replicated params.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..jax_compat import shard_map
from ..jax_compat import vary as _vary
from ..utils import numerics
from .mesh import WORKER_AXIS, batch_sharding, worker_local_sharding


# ---------------------------------------------------------------------------
# boxing helpers: [*shape] per-worker view <-> [n_workers, *shape] global
# ---------------------------------------------------------------------------

def box(tree):
    """Add the local leading axis (inside shard_map: local shard is [1,...])."""
    return jax.tree.map(lambda x: x[None], tree)


def unbox(tree):
    return jax.tree.map(lambda x: x[0], tree)


def place_boxed(tree, mesh: Mesh, specs=None):
    """Place an already-boxed ``[n_workers, ...]`` host pytree onto the mesh
    (checkpoint restore: per-worker replicas round-trip without collapsing).
    ``specs``: optional same-structure pytree of BOXED PartitionSpecs (tensor
    -parallel models shard some leaves over ``'model'`` too)."""
    if specs is None:
        sh = worker_local_sharding(mesh)
        return jax.tree.map(lambda x: jax.device_put(np.asarray(x), sh), tree)
    return jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(x), NamedSharding(mesh, s)),
        tree, specs)


def tree_to_host(tree):
    """Materialize a (possibly multi-host-sharded) pytree as host numpy with
    GLOBAL shapes — rank 0 can then save it, as the reference's rank-0 save."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        return multihost_utils.process_allgather(tree, tiled=True)
    return jax.device_get(tree)


def replicate_tree(tree, n: int, mesh: Mesh, specs=None):
    """Broadcast an unboxed pytree to the boxed [n_workers, ...] layout and
    place it sharded over the workers axis (one replica per chip — or per
    tp GROUP of chips when ``specs`` shard leaves over ``'model'`` too)."""
    def put(x, sh):
        x = np.asarray(x)
        return jax.device_put(np.broadcast_to(x[None], (n,) + x.shape), sh)

    if specs is None:
        sh = worker_local_sharding(mesh)
        return jax.tree.map(lambda x: put(x, sh), tree)
    return jax.tree.map(lambda x, s: put(x, NamedSharding(mesh, s)),
                        tree, specs)


def boxed_specs(tree, axis: str = WORKER_AXIS):
    """Prefix every leaf PartitionSpec in ``tree`` with the worker axis
    (``None`` leaves mean replicated)."""
    return jax.tree.map(lambda s: P(axis, *(s or ())), tree, is_leaf=_is_spec)


def state_partition_specs(model, exchanger, axis: str = WORKER_AXIS):
    """Boxed PartitionSpecs for the four step-state parts.

    Data-parallel-only models (``param_specs() is None``, the whole CNN zoo):
    the uniform prefix ``P(axis)`` — every leaf is a per-worker replica.

    Tensor-parallel models declare per-leaf specs over the ``'model'`` axis
    (``parallel/tp.py``); here they are prefixed with the worker axis and
    propagated structurally to the optimizer state (same per-leaf layout as
    the params they belong to — ``utils/opt.py``) and the exchanger's extra
    state (``Exchanger.extra_specs``).
    """
    pspecs = model.param_specs()
    if pspecs is None:
        return {k: P(axis)
                for k in ("params", "opt_state", "bn_state", "extra")}

    from ..utils.opt import opt_state_specs
    if not model.config.get("zero_opt", False):
        ospecs = opt_state_specs(model.optimizer, pspecs)
        if model.config.get("ema_decay"):
            # ema_wrap nests the base layout and adds a param-shaped shadow
            ospecs = {"inner": ospecs, "ema": pspecs, "t": P()}
    else:
        # zero1 replaces the layout with flat chunk vectors: every rank-1
        # leaf is [model_shards·chunk], one chunk per model-group rank —
        # sharded over ALL non-worker mesh axes so each device unboxes its
        # own [chunk]; scalars (adam/ema step counts) stay replicated.
        # eval_shape on the wrapped init derives the exact layout for any
        # inner optimizer/wrapper combination without running it.
        maxes = tuple(a for a in model.mesh.axis_names if a != axis)
        shapes = jax.eval_shape(model.opt.init, model.params)
        ospecs = jax.tree.map(
            lambda l: P(maxes) if l.ndim else P(), shapes)
    bn = jax.tree.map(lambda x: P(), model.bn_state)
    return {"params": boxed_specs(pspecs, axis),
            "opt_state": boxed_specs(ospecs, axis),
            "bn_state": boxed_specs(bn, axis),
            "extra": boxed_specs(exchanger.extra_specs(pspecs), axis)}


def _is_spec(x) -> bool:
    return x is None or isinstance(x, P)


def spec_mentions(s, axes) -> bool:
    """True when PartitionSpec ``s`` shards over any of ``axes`` (entries
    may be axis names or tuples of axis names)."""
    for e in (s or ()):
        for a in (e if isinstance(e, (tuple, list)) else (e,)):
            if a in axes:
                return True
    return False


def anchor_invariant(value, axes):
    """Re-establish the statically-known invariance of ``value`` over mesh
    ``axes`` when it is SEMANTICALLY replicated there but the vma tracking
    lost the proof (e.g. after a flatten that joined sharded and replicated
    leaves).  ``psum(where(rank==0, v, 0))`` is bit-exact for any axis size
    (v + zeros) and marks the output invariant; all_gather+[0] does not."""
    if not axes:
        return value
    from jax import lax
    r0 = sum(lax.axis_index(a) for a in axes) == 0
    return lax.psum(jnp.where(r0, value, jnp.zeros_like(value)), axes)


def local_param_template(params, pspecs, mesh: Mesh):
    """Zeros shaped like each leaf's LOCAL shard under ``pspecs`` — what a
    device actually sees inside shard_map.  Sizes the error-feedback state
    of compressed strategies under tensor parallelism."""
    def shrink(x, s):
        shape = list(np.shape(x))
        for i, ax in enumerate(s or ()):
            for a in (ax if isinstance(ax, (tuple, list)) else (ax,)):
                if a is not None:
                    assert shape[i] % mesh.shape[a] == 0, \
                        f"dim {i} of {tuple(np.shape(x))} not divisible " \
                        f"by mesh axis {a!r}={mesh.shape[a]}"
                    shape[i] //= mesh.shape[a]
        dtype = getattr(x, "dtype", None) or np.asarray(x).dtype
        return jnp.zeros(shape, dtype)

    return jax.tree.map(shrink, params, pspecs)


# ---------------------------------------------------------------------------
# microbatch gradient accumulation (reference: n_subb sub-batches, §3.4)
# ---------------------------------------------------------------------------

def _revary_bn(bn_state, axis: str):
    """Re-mark synced BN stats as worker-varying.  ``sync_bn``'s pmean
    returns worker-INVARIANT values (the whole point — replicas stay in
    lockstep), but the boxed state carry is worker-varying by type: under
    ``steps_per_call > 1`` the ``lax.scan`` carry would mismatch
    (``float32[...]{V:workers}`` in, plain ``float32[...]`` out) and
    refuse to trace — found pre-hardware by the round-5 AOT compile of
    the staged ``resnet50-*-spc8`` rows (BN models never met spc>1
    anywhere else: AlexNet/GoogLeNet/VGG use LRN).  The cast is
    type-level only; values are identical on every worker."""
    return jax.tree.map(lambda x: _vary(x, axis), bn_state)


def _accumulate_grads(loss_and_metrics: Callable, params, bn_state, batch,
                      rng, n_subb: int, axis: str = WORKER_AXIS):
    """Grad accumulation over ``n_subb`` microbatches as a ``lax.scan``.

    ``loss_and_metrics(params, bn_state, batch, rng, train=True)`` must
    return ``(cost, (err, new_bn_state))``.  BN state threads sequentially
    through microbatches (matching the reference's sequential sub-batch
    execution).
    """

    def lf(p, bn, b, r):
        return loss_and_metrics(p, bn, b, r, True)

    if n_subb == 1:
        (cost, (err, new_bn)), grads = jax.value_and_grad(lf, has_aux=True)(
            params, bn_state, batch, rng)
        return cost, err, grads, new_bn

    def reshape(x):
        assert x.shape[0] % n_subb == 0, (
            f"batch dim {x.shape[0]} not divisible by n_subb={n_subb}")
        return x.reshape((n_subb, x.shape[0] // n_subb) + x.shape[1:])

    micro = jax.tree.map(reshape, batch)

    def body(carry, mb):
        acc_g, acc_c, acc_e, bn, key = carry
        key, sub = jax.random.split(key)
        (cost, (err, bn)), grads = jax.value_and_grad(lf, has_aux=True)(
            params, bn, mb, sub)
        acc_g = jax.tree.map(jnp.add, acc_g, grads)
        return (acc_g, acc_c + cost, acc_e + err, bn, key), None

    zero_g = jax.tree.map(jnp.zeros_like, params)
    zero_c = _vary(jnp.zeros(()), axis)
    (acc_g, acc_c, acc_e, new_bn, _), _ = lax.scan(
        body, (zero_g, zero_c, zero_c, bn_state, rng), micro)
    inv = 1.0 / n_subb
    return acc_c * inv, acc_e * inv, jax.tree.map(lambda g: g * inv, acc_g), new_bn


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

# fold tag separating the fused-exchange key stream from the step rng's
# dropout stream (which folds (ridx, count) in the other order)
FUSED_EXCHANGE_FOLD = 0x0E5D


def fused_exchange_key(rng):
    """Base key for the in-scan fused exchange cadence (one per multi-step
    dispatch, traced).  The standalone cadence consumes host-split keys
    (``model.next_exchange_key()``); fusing the exchange into the scanned
    step replaces that host draw with a deterministic traced stream:
    rules fold the step count in themselves (``exchange_body``'s
    ``fold_in(key, count)``), so ONE base key per call yields per-step
    draws — the GoSGD RNG contract (docs/design.md §"fused cadence")."""
    return jax.random.fold_in(rng, FUSED_EXCHANGE_FOLD)


def build_train_step(mesh: Mesh, model, exchanger, n_steps: int = 1) -> Callable:
    """Compile the training step.

    Returns ``train_fn(state_dict, batch, lr, rng, count) ->
    (state_dict, cost[n], err[n])`` where ``state_dict`` has boxed leaves and
    is donated (params update in place in HBM, as the reference's in-place
    Theano updates did).

    ``n_steps > 1`` (config ``steps_per_call``): a ``lax.scan`` runs that
    many FULL training steps per dispatch over a stacked ``[k, ...]`` batch —
    the per-call host cost (pytree flatten + hundreds of buffer handles) is
    paid once per k steps instead of per step.  Profiling motivation: on one
    v5e chip the ResNet-50 step showed 13.2 ms device-busy inside a 17.8 ms
    wall step — ~26% host dispatch.  Valid for EVERY rule: exchangers with a
    post-step collective (EASGD/ASGD/GoSGD, BSP params mode) have their
    cadence fused into the scan — each scanned step ends with
    ``lax.cond(count % exchange_freq == 0, exchange_body, identity)`` — so
    one dispatch covers k steps INCLUDING their cadenced exchanges and the
    between-steps Python hook is skipped (``exchanger.fused``); BSP grads
    mode has no post-step hook to begin with.  ``count`` is the index of
    the LAST step in the call.

    Pipelined models compose for free (round 10, ISSUE 16): the model's
    loss calls ``pipeline_apply`` whose whole schedule — fill/drain or
    interleaved virtual stages (``pp_interleave``), ``v·M + pp − 1``
    ticks of chunk compute, per-slot ``ppermute_start/done`` hops,
    inject/collect masks — is ONE inner ``lax.scan`` inside the loss.
    Under ``n_steps > 1`` that scan nests inside this function's step
    scan, so the host still dispatches once per k-step window even with
    pipelining on: a whole pipeline round (forward schedule + its scan
    transpose) per scanned step, zero host round-trips between ticks.
    The schedule table is static (a pure function of ``(pp, v, M)``
    baked at trace time).
    """
    axis = WORKER_AXIS
    n = mesh.shape[axis]
    n_subb = getattr(model, "n_subb", 1)
    fsdp = getattr(model, "_fsdp", None)       # FsdpLayout when fsdp=true
    fuse_exchange = n_steps > 1 and exchanger.has_exchange()
    exchange_freq = int(getattr(exchanger, "exchange_freq", 1))
    # numerics health plane (utils/numerics, docs/design.md §25): None
    # unless config `numerics` is on — the off path below is byte-identical
    # to a build without the plane (the inertness contract).  When on, the
    # sample is computed under ``lax.cond(c % numerics_every == 0, ...)``
    # (the same invariant-count cadence pattern as the fused exchange),
    # carried as a latest-sample scan carry, and returned as a 4th output
    # with one P(axis) out-spec per key — the boxed [n_workers] layout IS
    # the beacon's cross-rank gather, with zero extra host round-trips.
    nx = numerics.graph_plan(model, exchanger, axis)

    def mark_varying(tree):
        return jax.tree.map(lambda x: _vary(x, axis), tree)

    def gated_sample(prev, ing, c):
        """The cadence-gated sample: compute on ``c % every == 0``, else
        keep the carried latest sample.  Both arms are re-marked worker-
        varying — the compute arm's ``iter`` derives from the invariant
        count while the carry is varying, and cond arms must agree."""
        if nx.every == 1:
            return mark_varying(nx.compute(*ing, c))
        return lax.cond(c % nx.every == 0,
                        lambda _: mark_varying(nx.compute(*ing, c)),
                        lambda _: mark_varying(prev), 0)

    def fsdp_step(state, batch, lr, rng, count):
        # FSDP / ZeRO-3 (parallel/fsdp.py): state["params"] is this
        # worker's [chunk] flat shard.  The loss gathers the full tree
        # per (micro)batch; differentiating w.r.t. the chunk transposes
        # the all_gather into psum_scatter, so grads arrive pre-summed
        # over workers — the whole BSP exchange with no exchanger hook.
        chunk = unbox(state["params"])
        opt_state = unbox(state["opt_state"])
        bn_state = unbox(state["bn_state"])
        ridx = lax.axis_index(axis)
        local_rng = jax.random.fold_in(jax.random.fold_in(rng, ridx), count)

        def loss_fn(ch, bn, b, r, train):
            # the gather and its transpose, the psum_scatter, are the wire
            with jax.named_scope("exchange"):
                full = fsdp.gather_params(ch, axis)
            return model.loss_and_metrics(full, bn, b, r, train)

        cost, err, g_chunk, new_bn = _accumulate_grads(
            loss_fn, chunk, bn_state, batch, local_rng, n_subb)
        with jax.named_scope("update"):
            g_chunk = g_chunk * (1.0 / n)      # transpose summed; BSP means
            g_chunk = fsdp.clip_chunk(
                g_chunk, float(model.config.get("grad_clip", 0.0) or 0.0),
                axis)
            new_chunk, new_opt = model.opt.update(g_chunk, opt_state, chunk,
                                                  lr)
            # the compiler names an update's fusion after its root, the
            # reshape that boxes the new state: inside the scope with it
            new_chunk, new_opt = box(new_chunk), box(new_opt)
        new_bn = _revary_bn(exchanger.sync_bn(new_bn, axis=axis, size=n),
                            axis)
        new_state = {
            "params": new_chunk,
            "opt_state": new_opt,
            "bn_state": box(new_bn),
            "extra": state["extra"],
        }
        return new_state, cost, err

    def one_step(state, batch, lr, rng, count):
        if fsdp is not None:
            return fsdp_step(state, batch, lr, rng, count)
        params = unbox(state["params"])
        opt_state = unbox(state["opt_state"])
        bn_state = unbox(state["bn_state"])
        extra = unbox(state["extra"])
        ridx = lax.axis_index(axis)
        local_rng = jax.random.fold_in(jax.random.fold_in(rng, ridx), count)

        # a wide FC's weight gradient may come back already summed over
        # the workers, formed from gathered operands (layers.GatheredGrads)
        gathered = exchanger.gathered_grads()
        loss = model.loss_and_metrics if gathered is None \
            else gathered.loss(model.loss_and_metrics)
        cost, err, grads, new_bn = _accumulate_grads(
            loss, params, bn_state, batch, local_rng, n_subb)
        summed = gathered.taken if gathered is not None else None

        # Model hooks (traced, optional — models outside ModelBase need not
        # define them): grad transform before the exchange, update gating /
        # param projection after it (GAN n_critic cadence, WGAN clipping).
        pg = getattr(model, "postprocess_grads", None)
        if pg is not None:
            grads = pg(grads, count)
        new_params, new_opt, extra = exchanger.step_update(
            params, opt_state, grads, extra, lr, axis=axis, size=n,
            count=count, summed=summed)
        pu = getattr(model, "postprocess_update", None)
        if pu is not None:
            with jax.named_scope("update"):
                new_params, new_opt = pu(params, opt_state, new_params,
                                         new_opt, count)
        # numerics ingredients (§25): the already-live old/new params,
        # grads and extra — handed back for the cadence-gated sample at
        # the per_worker level.  Pure reads; None keeps this path inert.
        if nx is not None and summed:
            # the plane reads one worker's share: a leaf that came back
            # summed over the workers is handed over as its mean
            grads = jax.tree_util.tree_map_with_path(
                lambda p, g: g * (1.0 / n)
                if jax.tree_util.keystr(p) in summed else g, grads)
        ing = None if nx is None else (params, new_params, grads, extra)
        new_bn = _revary_bn(exchanger.sync_bn(new_bn, axis=axis, size=n),
                            axis)

        # the compiler names an update's fusion after its root, the reshape
        # that boxes the new state: that reshape is the update's too
        with jax.named_scope("update"):
            new_params, new_opt = box(new_params), box(new_opt)
        new_state = {
            "params": new_params,
            "opt_state": new_opt,
            "bn_state": box(new_bn),
            "extra": box(extra),
        }
        if nx is None:
            return new_state, cost, err
        return new_state, cost, err, ing

    if n_steps == 1:
        if nx is None:
            def per_worker(state, batch, lr, rng, count):
                new_state, cost, err = one_step(state, batch, lr, rng, count)
                return new_state, cost[None], err[None]
        else:
            def per_worker(state, batch, lr, rng, count):
                new_state, cost, err, ing = one_step(state, batch, lr, rng,
                                                     count)
                # no scan to carry a latest sample through: off-cadence
                # dispatches return the template (iter=-1, host skips it)
                smp = gated_sample(nx.template(), ing, count)
                return (new_state, cost[None], err[None],
                        jax.tree.map(lambda x: x[None], smp))
    elif not fuse_exchange:
        if nx is None:
            def per_worker(state, batches, lr, rng, count):
                # batches leaves: [k, local_rows, ...]; count names the
                # LAST step
                count0 = count - (n_steps - 1)

                def body(carry, xs):
                    batch, j = xs
                    new_state, cost, err = one_step(carry, batch, lr, rng,
                                                    count0 + j)
                    return new_state, (cost, err)

                js = _vary(jnp.arange(n_steps), axis)
                state, (costs, errs) = lax.scan(body, state, (batches, js))
                return state, jnp.mean(costs)[None], jnp.mean(errs)[None]
        else:
            def per_worker(state, batches, lr, rng, count):
                # numerics needs an INVARIANT step counter for its cond
                # predicate (js is worker-varying — a varying predicate
                # would poison the collectives inside the sample), so the
                # scan grows the same (c, latest-sample) carry the fused
                # variant below already uses
                count0 = count - (n_steps - 1)

                def body(carry, xs):
                    s, c, smp = carry
                    batch, j = xs
                    s, cost, err, ing = one_step(s, batch, lr, rng,
                                                 count0 + j)
                    smp = gated_sample(smp, ing, c)
                    return (s, c + 1, smp), (cost, err)

                js = _vary(jnp.arange(n_steps), axis)
                smp0 = mark_varying(nx.template())
                (state, _, smp), (costs, errs) = lax.scan(
                    body, (state, count0, smp0), (batches, js))
                return (state, jnp.mean(costs)[None], jnp.mean(errs)[None],
                        jax.tree.map(lambda x: x[None], smp))
    else:
        def per_worker(state, batches, lr, rng, count):
            # fused cadence: the scan carries an INVARIANT step counter c
            # alongside the state — the cond predicate (and the collectives
            # inside the taken branch) must be provably uniform across
            # workers; the varying js stream still feeds one_step's
            # per-step rng fold exactly as in the unfused trace
            count0 = count - (n_steps - 1)
            exch_key = fused_exchange_key(rng)

            def do_exchange(s, c):
                s = exchanger.exchange_body(s, exch_key, c)
                # exchange collectives (pmean/psum-averaged params) come
                # back worker-INVARIANT by type; the scan carry is varying
                # — re-mark, values untouched (same move as _revary_bn)
                return jax.tree.map(lambda x: _vary(x, axis), s)

            if nx is None:
                def body(carry, xs):
                    s, c = carry
                    batch, j = xs
                    s, cost, err = one_step(s, batch, lr, rng, count0 + j)
                    if exchange_freq == 1:
                        s = do_exchange(s, c)
                    else:
                        s = lax.cond(c % exchange_freq == 0,
                                     lambda s: do_exchange(s, c),
                                     lambda s: s, s)
                    return (s, c + 1), (cost, err)

                js = _vary(jnp.arange(n_steps), axis)
                (state, _), (costs, errs) = lax.scan(
                    body, (state, count0), (batches, js))
                return state, jnp.mean(costs)[None], jnp.mean(errs)[None]
            else:
                def body(carry, xs):
                    s, c, smp = carry
                    batch, j = xs
                    s, cost, err, ing = one_step(s, batch, lr, rng,
                                                 count0 + j)
                    if exchange_freq == 1:
                        s = do_exchange(s, c)
                    else:
                        s = lax.cond(c % exchange_freq == 0,
                                     lambda s: do_exchange(s, c),
                                     lambda s: s, s)
                    # sampled from the PRE-exchange ingredients: the stats
                    # describe the step's own update; the beacon trees
                    # (BSP params / the center copy) persist across the
                    # exchange, so desync detection is unaffected
                    smp = gated_sample(smp, ing, c)
                    return (s, c + 1, smp), (cost, err)

                js = _vary(jnp.arange(n_steps), axis)
                smp0 = mark_varying(nx.template())
                (state, _, smp), (costs, errs) = lax.scan(
                    body, (state, count0, smp0), (batches, js))
                return (state, jnp.mean(costs)[None], jnp.mean(errs)[None],
                        jax.tree.map(lambda x: x[None], smp))

    state_spec = state_partition_specs(model, exchanger, axis)
    bs = model.batch_spec()
    base = tuple(bs) if bs is not None else (axis,)
    # n_steps > 1 prefixes the scan dim (round-4: composes with custom
    # batch specs — a sequence-parallel stack is P(None, workers, seq))
    batch_spec = P(*base) if n_steps == 1 else P(None, *base)
    out_specs = (state_spec, P(axis), P(axis))
    if nx is not None:
        out_specs = out_specs + (
            {k: P(axis) for k in numerics.SAMPLE_KEYS},)
    sm = shard_map(
        per_worker, mesh=mesh,
        in_specs=(state_spec, batch_spec, P(), P(), P()),
        out_specs=out_specs,
    )
    return jax.jit(sm, donate_argnums=(0,),
                   compiler_options=_keep_collectives_apart())


def _keep_collectives_apart() -> Optional[dict]:
    """Compiler options for the train step on the CPU backend: its
    ``cpu-all-reduce-combiner`` pass merges the step's all-reduces (one a
    leaf on the monolithic wire, one a bucket on the bucketed one) into a
    single variadic collective, so a ``devprof`` capture on the CPU mesh
    counted 1 collective a step whatever the wire issued — the structure
    the CPU rehearsals exist to check.  Nothing on any other backend:
    their programs, and cache keys, stay as they were."""
    if jax.default_backend() != "cpu":
        return None
    return {"xla_disable_hlo_passes": "cpu-all-reduce-combiner"}


def build_val_step(mesh: Mesh, model) -> Callable:
    """Compile the validation step: each worker evaluates its shard of the
    val batch with its own replica (the reference's per-rank validation).

    Returns ``val_fn(params_boxed, bn_boxed, batch) ->
    (cost[n], err[n], err_top5[n])``.
    """
    axis = WORKER_AXIS

    def per_worker(params, bn_state, batch):
        params = unbox(params)
        bn_state = unbox(bn_state)
        cost, (err, err5) = model.val_metrics(params, bn_state, batch)
        return cost[None], err[None], err5[None]

    pspecs = model.param_specs()
    if pspecs is None:
        p_spec = bn_spec = P(axis)
    else:
        p_spec = boxed_specs(pspecs, axis)
        bn_spec = jax.tree.map(lambda x: P(axis), model.bn_state)
    vb_spec = model.batch_spec()
    if vb_spec is None:
        vb_spec = P(axis)
    sm = shard_map(
        per_worker, mesh=mesh,
        in_specs=(p_spec, bn_spec, vb_spec),
        out_specs=(P(axis), P(axis), P(axis)),
    )
    return jax.jit(sm)


def is_device_batch(batch) -> bool:
    """True if the batch is already mesh-resident (staged by the parallel
    loader's producer thread) — ``train_iter`` then skips ``put_batch``."""
    leaves = jax.tree_util.tree_leaves(batch)
    return bool(leaves) and isinstance(leaves[0], jax.Array)


def is_device_window(window) -> bool:
    """True if ``window`` is an already-staged ``[k, ...]`` stack: leaves
    are mesh-resident ``jax.Array``s whose sharding leads with the
    replicated scan axis (the ``P(None, *base)`` layout ``stage_window``
    produces).  ``train_iter`` / ``put_batch_stack`` then dispatch without
    touching the host — the parallel loader's window producer staged it."""
    leaves = jax.tree_util.tree_leaves(window)
    if not leaves or not isinstance(leaves[0], jax.Array):
        return False
    spec = getattr(leaves[0].sharding, "spec", None)
    return spec is not None and len(spec) > 0 and spec[0] is None


def stack_host(batches):
    """Host-side ``[k, ...]`` stack of k per-step batches — THE window
    layout ``stage_window`` ships to the mesh.  One definition, shared by
    the consumer path (``put_batch_stack``) and the PrefetchLoader window
    producer, so a layout tweak can't silently fork the two streams."""
    return jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *batches)


def stage_window(mesh: Mesh, window, spec=None):
    """Place a ``[k, ...]``-leaved window pytree onto the mesh, sharded
    ``P(None, *base)`` — the scan dim replicated, each step's slice split
    per ``spec`` (default ``P(workers)`` row split).  THE staging
    primitive for multi-step dispatch inputs: the PrefetchLoader's window
    producer calls it off the hot path (the queue then holds
    device-resident windows), and ``put_batch_stack`` routes its
    consumer-thread stacking through it, so the sharding algebra lives in
    exactly one place.

    Multi-host: ``window`` is this host's LOCAL ``[k, local_rows, ...]``
    stack; the global array is stitched from per-process shards without
    cross-host copies (same contract as ``put_batch``)."""
    base = tuple(spec) if spec is not None else (WORKER_AXIS,)
    sh = NamedSharding(mesh, P(None, *base))
    if jax.process_count() > 1:
        from .mesh import make_per_host_array
        return make_per_host_array(mesh, jax.tree.map(np.asarray, window),
                                   sharding=sh)
    return jax.tree.map(lambda x: jax.device_put(x, sh), window)


def put_batch_stack(mesh: Mesh, batches, spec=None):
    """Stack k per-step batches into ``[k, ...]`` leaves for a
    ``steps_per_call`` multi-step dispatch, sharded ``P(None, *base)``
    (scan slices the leading axis; each slice splits per ``spec`` —
    default ``P(workers)`` row split, sequence-parallel models also cut
    the time dim).

    Fast path: a single pre-staged window (the para_load window
    producer's output, ``is_device_window``) passes straight through —
    zero consumer-thread work.  Otherwise the stack routes through
    ``stage_window``; per-step batches already staged on device
    (para_load at spc=1 granularity) stack with ``jnp.stack`` so the
    reshard stays a device-side copy."""
    if not isinstance(batches, (list, tuple)):
        # one whole [k, ...] window, not a list of per-step batches: a
        # pre-staged device window passes straight through; a host window
        # (set_window with stage_fn=None) stages here
        return batches if is_device_window(batches) \
            else stage_window(mesh, batches, spec)
    if jax.process_count() == 1 and all(is_device_batch(b) for b in batches):
        window = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    else:
        window = stack_host(batches)
    return stage_window(mesh, window, spec)


def put_batch(mesh: Mesh, batch, spec=None):
    """Place a host batch onto the mesh, split across workers.

    Single-process: ``batch`` is the global batch, device_put shards it.
    Multi-host: ``batch`` is this host's LOCAL shard; the global array is
    stitched from per-process data without cross-host copies.

    ``spec``: optional PartitionSpec for every batch leaf beyond the default
    ``P(workers)`` row split (sequence-parallel models also shard the time
    dim, ``model.batch_spec()``).
    """
    if jax.process_count() > 1:
        from .mesh import make_per_host_array
        sharding = None if spec is None else NamedSharding(mesh, spec)
        # custom specs (sequence parallelism) stitch fine as long as each
        # host's devices cover COMPLETE trailing-axis groups (dp across
        # hosts, sp within a host — the natural pod layout); per-host local
        # data is then this host's worker rows × the full extra dims, which
        # make_array_from_process_local_data validates
        return make_per_host_array(mesh, batch, sharding=sharding)
    sh = NamedSharding(mesh, spec) if spec is not None else \
        batch_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sh), batch)
