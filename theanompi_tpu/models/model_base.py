"""The duck-typed model contract.

Theano-MPI's actual public API is its model contract (SURVEY.md §2.5): a
model exposes ``params``, ``data``, ``compile_iter_fns()``,
``train_iter(count, recorder)``, ``val_iter(count, recorder)``,
``adjust_hyperp(epoch)``, ``scale_lr(size)``, ``epochs``, ``n_subb``.  The
worker loop drives any object with that shape.  :class:`ModelBase` implements
the contract once over the TPU step machinery; concrete models
(``cifar10.py``, ``alex_net.py``, ...) only define their layer stack, data
object, and hyperparameters — mirroring how reference model files were layer
lists plus a module-level hyperparameter dict.

``params`` is the INITIAL parameter tree: device arrays as drawn, a host
(numpy) tree of the same values once ``compile_iter_fns`` has placed the
state.  The parameters being trained are ``step_state["params"]``.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..jax_compat import shard_map
from ..parallel import steps
from ..parallel.mesh import WORKER_AXIS, worker_mesh
from ..utils import checkpoint as ckpt_lib
from ..utils import helper_funcs
from ..utils import numerics as numerics_lib
from ..utils import telemetry
from ..utils.opt import get_optimizer
from . import layers as L


class ModelBase:
    """Implements the reference model contract over compiled SPMD steps."""

    # hyperparameter defaults; concrete models override (these mirror the
    # module-level dicts that served as the reference's config system, §5.6)
    batch_size: int = 128          # per-worker, as in the reference
    epochs: int = 60
    n_subb: int = 1                # sub-batches per comm step (grad accum)
    steps_per_call: int = 1        # full steps per dispatch (any rule —
                                   # cadenced exchanges fuse into the scan)
    learning_rate: float = 0.01
    momentum: float = 0.9
    weight_decay: float = 0.0001
    optimizer: str = "momentum"
    lr_adjust_epochs: tuple = ()   # epochs at which lr /= 10 (step schedule)
    seed: int = 42

    def __init__(self, config: Optional[dict] = None):
        self.config = dict(config or {})
        self.verbose = self.config.get("verbose", True)
        self.rank = self.config.get("rank", 0)
        self.size = self.config.get("size", 1)
        self.mesh = self.config.get("mesh")
        if self.mesh is None:
            self.mesh = worker_mesh(self.config.get("n_workers"),
                                    tp=int(self.config.get("tp", 1)),
                                    pp=int(self.config.get("pp", 1)),
                                    sp=int(self.config.get("sp", 1)))
            self.size = self.mesh.shape[WORKER_AXIS]
            # build_model()'s data object reads size from config — keep it
            # coherent when the model is constructed standalone (no Worker).
            self.config.setdefault("rank", self.rank)
            self.config["size"] = self.size
        for k in ("batch_size", "epochs", "n_subb", "learning_rate", "seed",
                  "optimizer", "momentum", "weight_decay", "steps_per_call"):
            if k in self.config:
                setattr(self, k, self.config[k])
        self.seed = int(self.config.get("seed", self.seed))
        self.current_lr = float(self.learning_rate)

        self.seq: L.Sequential = None
        self.data = None
        self.build_model()            # subclass hook: set self.seq, self.data
        if self.config.get("para_load", False) and self.data is not None:
            # reference's para_load=True flag → background parallel loader.
            # The producer thread stages batches onto the mesh itself
            # (device_put_fn), double-buffered — the TPU analogue of the
            # reference's loader child writing into the trainer's GPU buffer
            # via CUDA IPC: train_iter consumes device-resident batches and
            # the host→device copy overlaps compute.
            from .data.prefetch import PrefetchLoader
            # steps_per_call > 1 goes WINDOW-granular instead of staging
            # per batch: compile_iter_fns wires set_window so the producer
            # stacks+stages whole spc windows off the hot path (avoids a
            # stage-then-restack double copy; docs/design.md §9)
            put = None if int(self.steps_per_call) > 1 \
                else (lambda b: steps.put_batch(self.mesh, b,
                                                self.batch_spec()))
            # para_load_workers > 1: pooled materialization for file-based
            # data (plans stay sequential — bit-identical stream)
            self.data = PrefetchLoader(
                self.data, device_put_fn=put,
                n_workers=int(self.config.get("para_load_workers", 4)))

        key = jax.random.key(self.seed)
        self.params = self.init_params(key)
        self.bn_state = self.init_bn_state()
        self.opt = get_optimizer(self.optimizer, mu=self.momentum,
                                 weight_decay=self.weight_decay) \
            if self.optimizer in ("momentum", "nesterov") \
            else get_optimizer(self.optimizer, weight_decay=self.weight_decay)
        if self.config.get("ema_decay"):
            # EMA shadow params (utils/opt.py ema_wrap); validation and
            # generate() read the shadow.  Composes with tensor/pipeline
            # param specs (the shadow is laid out exactly like the params —
            # steps.state_partition_specs) and sits INSIDE the ZeRO wrapper
            # below: under zero_opt the shadow then tracks each worker's
            # parameter CHUNK — EMA memory shards with the optimizer state,
            # and the full shadow is assembled only at read time.
            from ..utils.opt import ema_wrap
            self.opt = ema_wrap(self.opt, float(self.config["ema_decay"]))
        # the replicated-layout optimizer, BEFORE any chunking wrapper:
        # devprof.update_state_report eval_shapes it to price the
        # replicated-equivalent update plane (the EMA shadow, when on, is
        # honestly part of that plane, so the capture sits after ema_wrap)
        self._replicated_opt = self.opt
        self._zero_layout = None
        if self.config.get("zero_opt", False):
            # ZeRO-1 (parallel/zero.py): optimizer state sharded over the
            # workers axis — per-chip optimizer memory /N, bit-equal updates.
            # Under tensor/pipeline specs the per-device params are already
            # the LOCAL shard: chunk the local flat layout and hand init the
            # model-group shard count so the host template is global-shaped
            # (one chunk per model-group rank, P(workers, <model axes>)).
            assert not getattr(self, "gates_opt_state_by_path", False), (
                "zero_opt flattens the optimizer state into per-worker "
                "chunks, losing the param paths — models that gate "
                "optimizer-state subtrees by path (the GANs' n_critic>1 "
                "cadence) cannot compose with it")
            from ..parallel.zero import zero1
            pspecs = self.param_specs()
            if pspecs is None:
                template, shards, maxes = self.params, 1, ()
            else:
                template = steps.local_param_template(self.params, pspecs,
                                                      self.mesh)
                maxes = tuple(a for a in self.mesh.axis_names
                              if a != WORKER_AXIS)
                shards = 1
                for a in maxes:
                    shards *= self.mesh.shape[a]
            self.opt = zero1(self.opt, self.mesh.shape[WORKER_AXIS],
                             template, model_shards=shards,
                             pspecs=pspecs, model_axes=maxes)
            # layout facts for worker-count-portable resume (load() refit)
            self._zero_layout = {
                "n": self.mesh.shape[WORKER_AXIS], "shards": shards,
                "local_total": helper_funcs.tree_size(template)}

        self._ushard_plan = None
        if self.config.get("update_sharding", False) and \
                str(self.config.get("rule", "bsp")).lower() == "bsp":
            # Leaf-wise update-plane sharding (parallel/update_sharding.py,
            # docs/design.md §23): optimizer moments chunk per leaf over
            # the workers axis, one fused allgather rebuilds full params
            # inside the step.  Wrapped HERE (not at compile time) so
            # `self.opt.init` sees the chunked shapes wherever it is
            # called.  Under a non-BSP `rule` only the exchanger's
            # shardable extra (EASGD/ASGD centers) shards — async rules'
            # moments diverge per worker and must stay local.
            assert not self.config.get("zero_opt", False), (
                "update_sharding IS the generalization of zero_opt "
                "(leaf-wise chunks vs one flat chunk) — enable one, not "
                "both")
            assert not self.config.get("fsdp", False), (
                "fsdp=true already holds optimizer state on the parameter "
                "chunk — drop update_sharding")
            assert not self.config.get("ema_decay"), (
                "update_sharding does not yet carry the EMA shadow's "
                "chunked read path (zero_opt does) — use zero_opt with "
                "ema_decay, or drop one")
            assert not getattr(self, "gates_opt_state_by_path", False), (
                "update_sharding chunks optimizer-state leaves — models "
                "that gate optimizer-state subtrees by path (the GANs' "
                "n_critic>1 cadence) cannot compose with it")
            assert self.param_specs() is None and all(
                self.mesh.shape[a] == 1 for a in self.mesh.axis_names
                if a != WORKER_AXIS), (
                "update_sharding currently supports pure data-parallel "
                "layouts — tensor/pipeline models use zero_opt (the flat "
                "configuration carries model_shards/pspecs)")
            n_w = self.mesh.shape[WORKER_AXIS]
            if n_w > 1:
                from ..parallel import update_sharding
                plan = update_sharding.plan_tree(
                    self.params, n_w,
                    min_bytes=int(self.config.get(
                        "ushard_min_bytes",
                        update_sharding.DEFAULT_MIN_BYTES)))
                if plan.any_sharded:
                    self._ushard_plan = plan
                    self.opt = update_sharding.shard_opt(self.opt, plan)

        self._fsdp = None
        if self.config.get("fsdp", False):
            # FSDP / ZeRO-3 (parallel/fsdp.py): params themselves shard over
            # the workers axis as flat [chunk] shards; the step gathers the
            # full tree transiently and the AD transpose reduce-scatters the
            # grads.  The optimizer (incl. an EMA wrapper above) operates on
            # the chunk natively, so zero_opt is subsumed, not composed.
            assert not self.config.get("zero_opt", False), (
                "fsdp=true subsumes zero_opt (the optimizer state already "
                "lives on the parameter chunk) — drop zero_opt")
            assert self.param_specs() is None, (
                "fsdp shards params over the workers axis; tensor/pipeline "
                "models already shard them over the model axes — unsupported")
            assert all(self.mesh.shape[a] == 1 for a in self.mesh.axis_names
                       if a != WORKER_AXIS), (
                "fsdp currently supports pure data-parallel meshes")
            assert not getattr(self, "gates_opt_state_by_path", False) and \
                type(self).postprocess_grads is ModelBase.postprocess_grads \
                and type(self).postprocess_update is \
                ModelBase.postprocess_update, (
                "fsdp flattens params into per-worker chunks — models that "
                "transform grads/updates tree-wise (the GANs) cannot compose")
            from ..parallel.fsdp import FsdpLayout
            self._fsdp = FsdpLayout(self.params,
                                    self.mesh.shape[WORKER_AXIS])

        self.step_state: Optional[Dict[str, Any]] = None
        self._state_specs = None
        self.train_fn = None
        self.val_fn = None
        self.exchanger = None
        self._ckpt_thread = None
        self._exch_key = jax.random.key(self.seed + 1)
        self._val_params_boxed = None
        self._val_bn_boxed = None
        self.current_info: Dict[str, Any] = {}

    # -- subclass hooks ----------------------------------------------------

    def build_model(self) -> None:
        raise NotImplementedError

    # Simple chain models set self.seq in build_model(); composite models
    # (GoogLeNet's aux heads, ResNet's residual graph) override these three
    # hooks instead and may leave self.seq unset.
    def init_params(self, key):
        assert self.seq is not None, "build_model() must set self.seq or " \
                                     "override init_params/apply_model"
        return self.seq.init(key)

    def init_bn_state(self):
        return self.seq.init_state() if self.seq is not None else {}

    def apply_model(self, params, x, *, train, rng, state):
        """Returns (logits, new_state)."""
        return self.seq.apply(params, x, train=train, rng=rng, state=state)

    def _label_smoothing(self, train: bool) -> float:
        """The smoothing ε the loss should use — the config knob applies to
        the TRAINING loss only (validation scores the clean NLL)."""
        return float(self.config.get("label_smoothing", 0.0)) if train \
            else 0.0

    def stage_input(self, x, crop_off=None):
        """Shared input staging for EVERY loss/metrics path (models with
        custom heads call this too).  The image wire is uint8
        (data/imagenet.py): the cast and the mean subtraction happen here,
        inside the step program — ``float32(pixel) - mean``, one float32
        subtraction, the reference's arithmetic.  Where the mean comes
        from is read off the data object: a scalar, a per-channel vector,
        or a mean image — cut under the batch's shared crop window when
        the batch carries its offsets (``crop_off``: ``int32 [rows, 2]``
        of ``(oy, ox)``, every row alike), else at its center, which is
        what per-image windows have always used.  Float inputs pass
        through untouched."""
        if x.dtype != jnp.uint8:
            return x
        mean = np.asarray(getattr(self.data, "img_mean", 122.0), np.float32)
        if mean.ndim == 3:
            c = x.shape[-2]
            if crop_off is None:
                oy, ox = (mean.shape[0] - c) // 2, (mean.shape[1] - c) // 2
                mean = mean[oy:oy + c, ox:ox + c]
            else:
                mean = jax.lax.dynamic_slice(
                    jnp.asarray(mean), (crop_off[0, 0], crop_off[0, 1], 0),
                    (c, c, mean.shape[2]))
        elif mean.ndim:
            mean = mean.reshape(-1)[:x.shape[-1]]
        return x.astype(jnp.float32) - mean

    def loss_and_metrics(self, params, bn_state, batch, rng, train):
        """Default head: softmax cross-entropy + top-1 error."""
        logits, new_bn = self.apply_model(
            params, self.stage_input(batch["x"], batch.get("crop_off")),
            train=train, rng=rng, state=bn_state)
        cost = L.softmax_cross_entropy(logits, batch["y"],
                                       self._label_smoothing(train))
        err = L.errors(logits, batch["y"])
        return cost, (err, new_bn)

    def param_specs(self):
        """Per-leaf PartitionSpecs over the ``'model'`` mesh axis for tensor
        -parallel models (``parallel/tp.py``), or None for pure data
        parallelism (the whole CNN zoo — the reference's only mode)."""
        return None

    def batch_spec(self):
        """PartitionSpec for batch leaves, or None for the default
        ``P(workers)`` row split.  Sequence-parallel models
        (``parallel/sp.py``) also shard the time dim."""
        return None

    def postprocess_grads(self, grads, count):
        """Traced hook before the exchange: transform gradients."""
        return grads

    def postprocess_update(self, old_params, old_opt, new_params, new_opt,
                           count):
        """Traced hook after the optimizer step: gate or project the update.
        GAN models freeze the generator (params AND optimizer state) off the
        critic cadence; WGAN clips critic weights.  Must return
        ``(params, opt_state)``."""
        return new_params, new_opt

    def val_metrics(self, params, bn_state, batch):
        logits, _ = self.apply_model(
            params, self.stage_input(batch["x"], batch.get("crop_off")),
            train=False, rng=None, state=bn_state)
        cost = L.softmax_cross_entropy(logits, batch["y"])
        return cost, (L.errors(logits, batch["y"]),
                      L.errors_top_x(logits, batch["y"], 5))

    # -- contract: compile -------------------------------------------------

    def compile_iter_fns(self, exchanger=None) -> None:
        """≙ reference ``model.compile_iter_fns()`` → ``theano.function``;
        here: jit the SPMD train/val steps and box the state onto the mesh.

        Nothing compiles here: the jit is lazy.  What this call costs is
        the ring span ``compile.place``; the XLA compile, or the load from
        the persistent cache, is ``compile.xla`` wherever it then happens
        (the first ``train_iter``), through the listener registered here."""
        telemetry.watch_compiles()
        with telemetry.span("compile.place"):
            self._compile_iter_fns(exchanger)

    def _compile_iter_fns(self, exchanger=None) -> None:
        from ..parallel.exchanger import BSP_Exchanger
        self.exchanger = exchanger or BSP_Exchanger(self.config)
        if self._fsdp is not None:
            # the gradient reduction is the all_gather's AD transpose — a
            # plain fp32 sum.  Any OTHER configured strategy (wire casts,
            # compression) would be silently ignored: the exchanger's
            # strategy hook never runs on the fsdp path.
            assert (isinstance(self.exchanger, BSP_Exchanger)
                    and self.exchanger.mode == "grads"
                    and self.exchanger.strategy.name == "allreduce"), (
                "fsdp=true fuses the exchange as all_gather/psum_scatter — "
                "only BSP grads mode with the exact 'allreduce' strategy "
                f"composes; got {type(self.exchanger).__name__} mode="
                f"{getattr(self.exchanger, 'mode', '?')} strategy="
                f"{getattr(getattr(self.exchanger, 'strategy', None), 'name', '?')}")
            # same silently-ignored class of knob: the bucketed wire
            # (parallel/buckets.py) lives in the strategy/exchange_body
            # hooks the fsdp path never runs — a bucketed-looking row
            # measuring a monolithic wire would corrupt the r9 analysis
            assert int(self.config.get("bucket_bytes", 0) or 0) == 0, (
                "fsdp=true has no exchanger wire to bucket (grads arrive "
                "via the all_gather transpose) — drop bucket_bytes")
        if self.config.get("zero_opt", False) or self.config.get("ema_decay") \
                or self._ushard_plan is not None:
            # ZeRO-1 / leaf-wise update sharding assume every worker sees
            # the SAME reduced gradient and holds identical params — true
            # only under BSP grads mode with a real collective; params mode
            # / the 'none' strategy would slice UN-reduced per-worker grads
            # and train silently wrong (and the EMA shadow would track
            # per-worker divergent params), and async rules' workers would
            # never update chunks other ranks own (their canonical/center
            # validation also never reads a shadow).  A sharded-opt model
            # handed a non-BSP exchanger means the config `rule` gate in
            # __init__ disagrees with the exchanger actually compiled —
            # set config['rule'] to the rule in use.
            which = "zero_opt" if self.config.get("zero_opt") else (
                "ema_decay" if self.config.get("ema_decay")
                else "update_sharding")
            assert (isinstance(self.exchanger, BSP_Exchanger)
                    and self.exchanger.mode == "grads"
                    and self.exchanger.strategy.name != "none"), (
                f"{which} requires BSP grads mode with a gradient "
                "collective (identical grads across workers); got "
                f"{type(self.exchanger).__name__} mode="
                f"{getattr(self.exchanger, 'mode', '?')} strategy="
                f"{getattr(getattr(self.exchanger, 'strategy', None), 'name', '?')}")
        self.exchanger.prepare(self.mesh, self)
        n = self.mesh.shape[WORKER_AXIS]

        # the state below is placed from host copies (replicate_tree,
        # chunk_host), and every later reader of self.params wants shapes
        # or a host tree: pull the initial parameters once and let the
        # device copy go, instead of holding it on chip 0 through training
        # (553 MB for VGG-16, the room of two staged batches)
        self.params = jax.device_get(self.params)
        extra = self.exchanger.extra_state_template()
        if self._fsdp is not None:
            # optimizer state lives on THIS worker's flat chunk (identical
            # zeros template per worker — broadcast replicates it; the real
            # per-worker chunks land below via place_boxed)
            opt_state = self.opt.init(
                jnp.zeros((self._fsdp.chunk,), jnp.float32))
            params_init = np.zeros((self._fsdp.chunk,), np.float32)
        else:
            opt_state = self.opt.init(self.params)
            params_init = self.params
        unboxed = {"params": params_init, "opt_state": opt_state,
                   "bn_state": self.bn_state, "extra": extra}
        self._state_specs = None if self.param_specs() is None else \
            steps.state_partition_specs(self, self.exchanger)
        # the read-path jits close over the specs above: rebuild on demand
        self._zero_shadow_jit = self._fsdp_val_jit = None
        self.step_state = {
            k: steps.replicate_tree(
                v, n, self.mesh,
                None if self._state_specs is None else self._state_specs[k])
            for k, v in unboxed.items()}
        if self._fsdp is not None:
            self.step_state["params"] = steps.place_boxed(
                self._fsdp.chunk_host(self.params), self.mesh)
        if getattr(self.exchanger, "update_plan", lambda: None)() is not None:
            # plan-sharded extra (EASGD/ASGD centers under update_sharding):
            # each worker's init chunk is a DIFFERENT window of the center —
            # replicate_tree above broadcast the zero template; overwrite
            # with the genuinely partitioned rows
            self.step_state["extra"] = steps.place_boxed(
                self.exchanger.extra_host_boxed(n), self.mesh)
        spc = int(self.steps_per_call)
        # multi-step dispatch fuses the exchange cadence INTO the scanned
        # step for every rule with a post-step collective (EASGD/ASGD/
        # GoSGD, BSP params mode — build_train_step wraps exchange_body in
        # lax.cond on the in-scan count); BSP grads mode has no post-step
        # hook to begin with.  The worker-loop Python exchange() must then
        # not run the collective a second time: exchange() no-ops while
        # exchanger.fused is set.  Assigned UNCONDITIONALLY so a recompile
        # back to spc=1 clears a stale flag (which would silently disable
        # the rule's exchanges outright).
        self.exchanger.fused = spc > 1 and self.exchanger.has_exchange()
        if spc > 1:
            # fail-loud guard for out-of-tree exchangers still on the
            # pre-round-6 pattern (jitting _exchange_fn directly in
            # prepare() without declaring has_exchange): their cadence
            # would neither fuse nor fire per-step from the spc-strided
            # worker loop — silently undersampled exchanges
            assert not (self.exchanger._exchange_fn is not None
                        and not self.exchanger.has_exchange()), (
                f"{type(self.exchanger).__name__} builds _exchange_fn but "
                "has_exchange() is False — steps_per_call > 1 fuses the "
                "cadence via exchange_body/has_exchange (see "
                "Exchanger._build_exchange_fn); declare them or keep "
                "steps_per_call=1")
            if self.data is not None:
                assert spc <= self.data.n_batch_train, (
                    f"steps_per_call={spc} exceeds n_batch_train="
                    f"{self.data.n_batch_train}: every epoch would train "
                    f"zero steps")
        if hasattr(self.data, "set_window"):
            # para_load + steps_per_call > 1: window-granular staging —
            # the PrefetchLoader producer assembles whole spc windows (k
            # sequential draws, host stack, steps.stage_window) so
            # train_iter dequeues mesh-resident dispatch inputs and the
            # recorder's `stage` bucket goes to ~0.  Re-wired on every
            # compile so a recompile back to spc=1 reverts to per-batch
            # production (a stale window setting would wedge the queue
            # granularity).  para_load_window=false opts out (A/B).
            # The fresh stage_fn closure makes set_window restart a live
            # producer every recompile — deliberate: the closure may bind
            # a new mesh/spec, and queued windows staged under the old
            # one must not survive (the loader rewinds, nothing is lost).
            if spc > 1 and self.config.get("para_load_window", True):
                self.data.set_window(
                    spc, lambda w: steps.stage_window(self.mesh, w,
                                                      self.batch_spec()))
            else:
                self.data.set_window(0)
        self.train_fn = steps.build_train_step(self.mesh, self,
                                               self.exchanger, n_steps=spc)
        # numerics health plane (§25): the dispatch returns a 4th aux
        # output exactly when the build sampled one (same gate as
        # steps.graph_plan — fsdp has no params-shaped replica view)
        self._numerics_on = numerics_lib.enabled(self.config) \
            and self._fsdp is None
        self.numerics_aux = None
        self.val_fn = steps.build_val_step(self.mesh, self)
        self._step_rng = jax.random.key(self.seed + 2)

    # -- abstract input signature ------------------------------------------

    _peek_aval_cache = None

    def _peek_batch_aval(self):
        """Shape/dtype of one train batch WITHOUT disturbing the stream:
        peek the underlying source (bypassing a PrefetchLoader's queue) and
        rewind its cursor — the same round-trip checkpoint resume relies on.

        Memoized: the peek-and-rewind touches the wrapped source directly,
        which is only safe while no PrefetchLoader producer thread is
        drawing from it.  Batch shapes are fixed for the life of the data
        object, so later calls reuse the first one's avals."""
        if self._peek_aval_cache is None:
            inner = getattr(self.data, "_data", None) or self.data
            cursor = inner.get_cursor() if hasattr(inner, "get_cursor") \
                else None
            batch = inner.next_train_batch(0)
            if cursor is not None and hasattr(inner, "set_cursor"):
                inner.set_cursor(cursor)
            self._peek_aval_cache = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                               np.asarray(x).dtype), batch)
        return self._peek_aval_cache

    def _train_input_avals(self, spc: int, exchanger=None):
        """The abstract input signature of one train dispatch at the given
        ``steps_per_call``, from the placed ``step_state`` (shardings
        included) and one peeked batch: ``train_fn.lower(*avals)`` is the
        program the live arguments run (``benchmarks/harness.py`` joins
        trace names to scopes with it).  ``exchanger`` is accepted for
        that caller and not read."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        peek = self._peek_batch_aval()
        bs = self.batch_spec()
        base = tuple(bs) if bs is not None else (WORKER_AXIS,)
        spec = P(*base) if spc == 1 else P(None, *base)
        sh = NamedSharding(self.mesh, spec)
        batch_avals = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape if spc == 1 else (spc,) + a.shape, a.dtype,
                sharding=sh), peek)
        state_avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            self.step_state)
        return (state_avals, batch_avals,
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jax.random.key(0).dtype),
                jax.ShapeDtypeStruct((), jnp.int32))

    # -- contract: iteration -----------------------------------------------

    def train_iter(self, count: int, recorder=None) -> None:
        """One dispatch: one training step, or ``steps_per_call`` of them
        (``count`` then names the LAST step of the call).

        Recorder buckets: ``load`` = waiting on the data source (pure
        dequeue wait under para_load), ``stage`` = consumer-thread host
        stack + ``device_put`` (~0 in window mode, where the producer
        staged the window already), ``train`` = the dispatch itself."""
        k = int(self.steps_per_call)
        # window mode (compile_iter_fns wired set_window): the loader
        # dequeues a whole mesh-resident [k, ...] window
        use_window = k > 1 and getattr(self.data, "window", 0) == k
        if recorder:
            recorder.start()
        if k == 1:
            batch = self.data.next_train_batch(count)
        elif use_window:
            batch = self.data.next_train_window(count)
        else:
            batches = [self.data.next_train_batch(count - k + 1 + j)
                       for j in range(k)]
            batch = batches[0]       # row accounting below
        if recorder:
            recorder.end("load")
            recorder.start()
        if k == 1:
            dev_batch = batch if steps.is_device_batch(batch) \
                else steps.put_batch(self.mesh, batch, self.batch_spec())
        else:
            # put_batch_stack passes a pre-staged device window through
            dev_batch = steps.put_batch_stack(
                self.mesh, batch if use_window else batches,
                self.batch_spec())
        if recorder:
            recorder.end("stage")
            recorder.start()
        # the `train` bracket in three ring spans: the two scalar
        # conversions (a small program each), the step program's call
        # alone, the two means (two more small programs) — stamped with
        # the id of the batch the loader handed over
        bid = getattr(self.data, "last_batch_id", None)
        with telemetry.span("train.args", bid):
            lr, step = jnp.float32(self.current_lr), jnp.int32(count)
        with telemetry.span("train.call", bid):
            out = self.train_fn(self.step_state, dev_batch, lr,
                                self._step_rng, step)
        if getattr(self, "_numerics_on", False):
            # the aux stays device-resident (async dispatch preserved) —
            # the worker materializes it at print cadence, alongside
            # cost/error
            self.step_state, cost, err, self.numerics_aux = out
        else:
            self.step_state, cost, err = out
        with telemetry.span("train.reduce", bid):
            cost, err = jnp.mean(cost), jnp.mean(err)
        if recorder:
            recorder.end("train")
        if self.config.get("sync_each_iter", False):
            # Reference-style blocking loop: t_train above is the host
            # dispatch, and the device-bound remainder lands in the ``wait``
            # bucket (≙ the reference's MPI-wait time) — together they sum
            # to wall time per iteration.
            if recorder:
                recorder.start()
            cost, err = float(cost), float(err)
            if recorder:
                recorder.end("wait")
        # else: device scalars flow to the recorder and materialize at print
        # cadence, keeping dispatch asynchronous (device queue stays full).
        if recorder:
            # local rows, consistently: a device-resident (para_load-staged)
            # batch has the GLOBAL shape, a host batch the per-host shape;
            # a device window's leaves are [k, global_rows, ...]
            if use_window:
                n_images = int(batch["y"].shape[0]) * int(batch["y"].shape[1])
            else:
                n_images = int(batch["y"].shape[0]) * k
            if steps.is_device_batch(batch):
                n_images //= jax.process_count()
            recorder.train_error(count, cost, err, n_images)
        self.current_info.update(cost=cost, error=err)

    def begin_val(self) -> None:
        """Snapshot the parameters validation should score: the canonical
        params for async rules (EASGD center, GoSGD consensus), the replica
        set itself for BSP (already identical)."""
        n = self.mesh.shape[WORKER_AXIS]
        if self.exchanger is not None and hasattr(self.exchanger,
                                                  "canonical_params"):
            canon = self.exchanger.canonical_params(self.step_state)
            pspec = None if self._state_specs is None \
                else self._state_specs["params"]
            self._val_params_boxed = steps.replicate_tree(canon, n, self.mesh,
                                                          pspec)
            # Consistent statistics for the consensus model: score the center
            # with the replica-MEAN running stats, not each worker's divergent
            # local ones (the reference's server validated its own center
            # model end to end).  BN state is tiny — host round-trip is fine
            # (tree_to_host: plain device_get can't span hosts).
            bn = steps.tree_to_host(self.step_state["bn_state"])
            bn_mean = jax.tree.map(lambda x: np.mean(np.asarray(x), axis=0),
                                   bn)
            self._val_bn_boxed = steps.replicate_tree(bn_mean, n, self.mesh)
        elif self._fsdp is not None:
            # FSDP: assemble the full tree on-device from the chunks (the
            # EMA shadow's chunks when enabled and seeded, else the live
            # ones) — the val step then sees the standard boxed params.
            self._val_params_boxed = self._fsdp_val_fn()(self.step_state)
            self._val_bn_boxed = self.step_state["bn_state"]
        else:
            # BSP: validate the EMA shadow when enabled, else the replicas
            if self.config.get("ema_decay"):
                # _ema_host_params handles the sharded layout and the
                # unseeded t==0 edge uniformly; re-box with the model's
                # param specs so tensor/pipeline shards land where the
                # val step expects them
                self._val_params_boxed = steps.replicate_tree(
                    self._ema_host_params(), n, self.mesh,
                    None if self._state_specs is None
                    else self._state_specs["params"])
            else:
                self._val_params_boxed = self.step_state["params"]
            self._val_bn_boxed = self.step_state["bn_state"]

    def val_iter(self, count: int, recorder=None) -> None:
        if self._val_params_boxed is None:
            self.begin_val()
        if recorder:
            recorder.start()
        batch = self.data.next_val_batch(count)
        dev_batch = batch if steps.is_device_batch(batch) \
            else steps.put_batch(self.mesh, batch, self.batch_spec())
        cost, err, err5 = self.val_fn(self._val_params_boxed,
                                      self._val_bn_boxed, dev_batch)
        # per-worker metric vectors span hosts — gather, don't device_get
        cost = float(np.mean(np.asarray(steps.tree_to_host(cost))))
        err = float(np.mean(np.asarray(steps.tree_to_host(err))))
        err5 = float(np.mean(np.asarray(steps.tree_to_host(err5))))
        if recorder:
            recorder.end("val")
            recorder.val_error(count, cost, err, err5)

    def end_val(self) -> None:
        self._val_params_boxed = None
        self._val_bn_boxed = None

    # -- contract: hyperparameters ----------------------------------------

    def adjust_hyperp(self, epoch: int) -> None:
        """LR schedule per epoch.  ``lr_schedule='step'`` (default): decay
        ÷10 at the epochs in ``lr_adjust_epochs`` — the schedule style every
        reference zoo model used.  ``'cosine'``: cosine decay from the base
        LR to ``min_lr_frac``·base over ``epochs`` (the modern LM default).

        ``warmup_epochs`` (config, default 0 = reference behavior) ramps the
        LR-scale factor linearly over the first epochs: the reference's
        linear ``scale_lr(size)`` rule applied instantly, which at high
        worker counts diverges before the first decay (Goyal et al.'s
        gradual-warmup fix postdates it)."""
        base = float(self.learning_rate)
        sched = str(self.config.get("lr_schedule", "step"))
        if sched == "cosine":
            import math
            frac = float(self.config.get("min_lr_frac", 0.1))
            total = max(1, int(self.config.get("epochs", self.epochs)))
            t = min(epoch, total) / total
            lr = base * (frac + (1.0 - frac) * 0.5
                         * (1.0 + math.cos(math.pi * t)))
        else:
            if sched != "step":
                raise ValueError(f"unknown lr_schedule {sched!r}; "
                                 f"have 'step', 'cosine'")
            lr = base
            for e in self.lr_adjust_epochs:
                if epoch >= e:
                    lr /= 10.0
        scale = self._lr_scale
        warmup = int(self.config.get("warmup_epochs", 0))
        if warmup > 0 and epoch < warmup and scale > 1.0:
            scale = 1.0 + (scale - 1.0) * (epoch + 1) / warmup
        self.current_lr = lr * scale

    _lr_scale: float = 1.0

    def scale_lr(self, size: int) -> None:
        """Linear LR scaling by worker count (reference ``scale_lr``)."""
        self._lr_scale = float(size)
        self.current_lr = self.current_lr * size

    def canonical_host_params(self):
        """Host copy of the parameters inference/analysis should use: the
        EASGD center / GoSGD consensus via the exchanger's
        ``canonical_params`` (fed only the params+extra it reads — not the
        optimizer state), replica 0 for BSP, or the init params before
        ``compile_iter_fns``."""
        if self.step_state is None:
            return self.params
        if self.exchanger is not None and hasattr(self.exchanger,
                                                  "canonical_params"):
            state = {k: steps.tree_to_host(self.step_state[k])
                     for k in ("params", "extra")}
            return jax.device_get(self.exchanger.canonical_params(state))
        if self.config.get("ema_decay"):
            return self._ema_host_params()
        if self._fsdp is not None:
            return self._fsdp.host_params_from_chunks(np.asarray(
                steps.tree_to_host(self.step_state["params"])))
        return steps.unbox(jax.device_get(
            steps.tree_to_host(self.step_state["params"])))

    def _ema_host_params(self):
        """The EMA shadow as an unboxed host pytree.  Plain EMA stores the
        full tree; under zero_opt the shadow is SHARDED chunks, gathered and
        unflattened here (read-time only).  Before the first update the
        shadow is unseeded (zeros) — fall back to the live params."""
        if self._fsdp is not None:
            st = self.step_state["opt_state"]
            t = int(np.asarray(jax.device_get(
                steps.tree_to_host(st["t"])))[0])
            src = self.step_state["params"] if t == 0 else st["ema"]
            return self._fsdp.host_params_from_chunks(
                np.asarray(steps.tree_to_host(src)))
        st = self.step_state["opt_state"]
        inner = st if "ema" in st else st["opt"]
        t = int(np.asarray(jax.device_get(
            steps.tree_to_host(inner["t"])))[0])
        if t == 0:
            return steps.unbox(jax.device_get(
                steps.tree_to_host(self.step_state["params"])))
        if "ema" in st:
            # plain EMA (incl. tensor/pipeline specs): the boxed shadow is
            # laid out like the params — device_get assembles the global tree
            return steps.unbox(jax.device_get(
                steps.tree_to_host(st["ema"])))
        # zero_opt layout: assemble on DEVICE with the exact gather the
        # update itself uses (all_gather over workers within each
        # model-group rank) — a host reshape of the boxed chunks would
        # misorder the flat layout under tensor/pipeline sharding.
        # tree_to_host, not device_get: model-sharded leaves span
        # non-addressable devices on multi-host
        return jax.device_get(steps.tree_to_host(
            self._zero_shadow_fn()(self.step_state)))

    def _zero_shadow_fn(self):
        if getattr(self, "_zero_shadow_jit", None) is None:
            from jax.sharding import PartitionSpec as P
            pspecs = self.param_specs()
            out_specs = pspecs if pspecs is not None else \
                jax.tree.map(lambda _: P(), self.params)
            state_spec = self._state_specs or {
                k: P(WORKER_AXIS)
                for k in ("params", "opt_state", "bn_state", "extra")}

            maxes = tuple(a for a in self.mesh.axis_names
                          if a != WORKER_AXIS)

            def body(state):
                params = steps.unbox(state["params"])
                shadow = steps.unbox(state["opt_state"])["opt"]["ema"]
                full = jax.lax.all_gather(shadow, WORKER_AXIS, tiled=True)
                tree = helper_funcs.unflatten_like(params, full)
                # the gather makes leaves worker-invariant (and replicated
                # leaves model-invariant) SEMANTICALLY, but the vma tracking
                # can't prove it — anchor each leaf bit-exactly over the
                # axes its out_spec claims replication on
                if pspecs is None:
                    return jax.tree.map(
                        lambda v: steps.anchor_invariant(
                            v, (WORKER_AXIS,) + maxes), tree)
                return jax.tree.map(
                    lambda s, v: steps.anchor_invariant(
                        v, (WORKER_AXIS,) + tuple(
                            a for a in maxes
                            if not steps.spec_mentions(s, (a,)))),
                    pspecs, tree, is_leaf=steps._is_spec)

            self._zero_shadow_jit = jax.jit(shard_map(
                body, mesh=self.mesh, in_specs=(state_spec,),
                out_specs=out_specs))
        return self._zero_shadow_jit

    def _fsdp_val_fn(self):
        """Jitted on-device assemble of the full boxed params from the FSDP
        chunks — the EMA shadow chunks when enabled AND seeded (``t > 0``),
        else the live ones; the two branches share one traced program via a
        ``where`` on the shadow's step counter."""
        if getattr(self, "_fsdp_val_jit", None) is None:
            from jax.sharding import PartitionSpec as P
            fsdp = self._fsdp
            ema = bool(self.config.get("ema_decay"))
            state_spec = {k: P(WORKER_AXIS)
                          for k in ("params", "opt_state", "bn_state",
                                    "extra")}

            def body(state):
                chunk = steps.unbox(state["params"])
                if ema:
                    st = steps.unbox(state["opt_state"])
                    chunk = jnp.where(st["t"] == 0, chunk, st["ema"])
                tree = fsdp.gather_params(chunk)
                return jax.tree.map(lambda v: v[None], tree)   # box/worker

            self._fsdp_val_jit = jax.jit(shard_map(
                body, mesh=self.mesh, in_specs=(state_spec,),
                out_specs=jax.tree.map(lambda _: P(WORKER_AXIS),
                                       self.params)))
        return self._fsdp_val_jit

    def next_exchange_key(self):
        self._exch_key, sub = jax.random.split(self._exch_key)
        return sub

    # -- contract: persistence --------------------------------------------

    def save(self, ckpt_dir: str, epoch: int, count: int = 0) -> str:
        """Checkpoint the FULL boxed state (every worker's replica + the
        exchanger's extras — diverged async-rule replicas and GoSGD α survive
        a resume), both PRNG keys, and the data cursor.  The reference-style
        per-leaf ``.npy`` snapshot holds the canonical params (the EASGD
        center / GoSGD consensus, ≙ the reference saving the server's
        center; replica 0 for BSP, where replicas are identical)."""
        state = {k: steps.tree_to_host(v) for k, v in self.step_state.items()}
        if hasattr(self.exchanger, "canonical_params"):
            # canonical_params is pure tree algebra (unbox / weighted mean) —
            # feed it the GATHERED host state: the device step_state spans
            # non-addressable shards on multi-host
            params_npy = jax.device_get(
                self.exchanger.canonical_params(state))
        elif self.config.get("ema_decay"):
            # the .npy snapshot holds what inference should use — the shadow
            params_npy = self._ema_host_params()
        elif self._fsdp is not None:
            params_npy = self._fsdp.host_params_from_chunks(
                np.asarray(state["params"]))
        else:
            params_npy = steps.unbox(state["params"])
        # PER-PART dedup: bit-identical parts persist ONE replica instead of
        # n (an 8-chip VGG-16 checkpoint shrinks 8×); parts that genuinely
        # differ per worker (async replicas, EF buffers, ZeRO optimizer
        # chunks) stay boxed.  load() re-shapes from the meta list.
        ident = set(getattr(self.exchanger, "identical_parts", tuple)())
        state = {k: (steps.unbox(v) if k in ident else v)
                 for k, v in state.items()}
        cursor = self.data.get_cursor() \
            if hasattr(self.data, "get_cursor") else None
        import os
        if jax.process_index() != 0:
            # rank 0 writes, as the reference did — concurrent writers on a
            # shared filesystem would corrupt the archive
            return os.path.join(ckpt_dir, f"ckpt_epoch{epoch}.npz")
        extra_meta = {"boxed_parts": sorted(k for k in state
                                            if k not in ident),
                      # lets load() give a targeted error (not a raw shape
                      # mismatch) when per-worker state meets a different
                      # worker count (round-4 review)
                      "n_workers": self.mesh.shape[WORKER_AXIS]}
        if self._fsdp is not None:
            # the chunk layout facts, so a resume on a DIFFERENT worker
            # count can re-partition the flat vector (load() refit path)
            extra_meta["fsdp"] = {"n": self._fsdp.n_workers,
                                  "chunk": self._fsdp.chunk,
                                  "total": self._fsdp.n_total}
        if self._zero_layout is not None:
            extra_meta["zero"] = self._zero_layout
        kwargs = dict(
            rng_keys={"step": self._step_rng, "exch": self._exch_key},
            cursor=cursor, params_npy=params_npy, extra_meta=extra_meta)
        if self.config.get("async_ckpt", False):
            # the device→host gather above is the only part that must block
            # the training loop; the disk write runs on a background thread
            # (one in flight at a time — a newer save joins the older first)
            import threading
            self.wait_pending_ckpt()

            def _write():
                try:
                    ckpt_lib.save_checkpoint(ckpt_dir, state, epoch, count,
                                             **kwargs)
                except BaseException as e:   # surfaced by wait_pending_ckpt
                    self._ckpt_exc = e

            self._ckpt_exc = None
            self._ckpt_thread = threading.Thread(target=_write, daemon=True)
            self._ckpt_thread.start()
            return os.path.join(ckpt_dir, f"ckpt_epoch{epoch}.npz")
        return ckpt_lib.save_checkpoint(ckpt_dir, state, epoch, count,
                                        **kwargs)

    def wait_pending_ckpt(self) -> None:
        """Block until an in-flight async checkpoint write (if any) lands;
        re-raise its failure here — a swallowed write error would let a
        supervisor resume from an older epoch with no signal."""
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._ckpt_thread = None
            exc, self._ckpt_exc = getattr(self, "_ckpt_exc", None), None
            if exc is not None:
                raise RuntimeError("async checkpoint write failed") from exc

    def load(self, ckpt_dir: str, epoch: Optional[int] = None) -> Optional[int]:
        """Restore state (call after ``compile_iter_fns``). Returns the epoch
        restored from, or None.  Restores the boxed per-worker state, the
        PRNG keys, and the data cursor, so training replays bit-identically
        from the save point (tested for BSP and GoSGD)."""
        self.wait_pending_ckpt()    # async_ckpt: never read a mid-write file
        n = self.mesh.shape[WORKER_AXIS]

        def shape_of(x, boxed):
            shape = x.shape if boxed else x.shape[1:]
            return jax.ShapeDtypeStruct(shape, x.dtype)

        # peek at the meta to learn the stored layout (which parts are boxed
        # per-worker state vs one dedup'd replica) before shaping templates
        peek = ckpt_lib.peek_meta(ckpt_dir, epoch)
        if peek is None:
            return None
        if "boxed_parts" in peek:
            boxed_parts = set(peek["boxed_parts"])
        elif peek.get("boxed", False):      # older all-or-nothing flag
            boxed_parts = set(self.step_state)
        else:                               # legacy: always saved unboxed
            boxed_parts = set()
        # Worker-count refit (the BSP elastic-resume story extended to
        # chunked state): FSDP and ZeRO chunking are pure partitions of a
        # padded flat layout, so a checkpoint from n_saved workers
        # re-partitions onto n — shape the load template by the SAVED
        # layout, then re-chunk below.  Chunk-vector leaves re-slice; boxed
        # scalar counters (identical across workers) broadcast one row.
        refit_parts: tuple = ()
        if self._fsdp is not None:
            fs = peek.get("fsdp")
            if fs is not None and int(fs["n"]) != n:
                assert int(fs["total"]) == self._fsdp.n_total, (
                    f"fsdp checkpoint holds {fs['total']} params, model "
                    f"has {self._fsdp.n_total} — different model config")
                refit_parts = ("params", "opt_state")
                n_s = int(fs["n"])
                cur_chunk_shape = (n, self._fsdp.chunk)
                saved_chunk_shape = (n_s, int(fs["chunk"]))
                rechunk = self._fsdp.rechunk
        elif self._zero_layout is not None:
            zs = peek.get("zero")
            if zs is not None and int(zs["n"]) != n:
                from ..parallel import zero as zero_lib
                lay = self._zero_layout
                assert (int(zs["shards"]) == lay["shards"] and
                        int(zs["local_total"]) == lay["local_total"]), (
                    f"zero checkpoint layout {zs} does not match the "
                    f"model's {lay} — different model/mesh config")
                refit_parts = ("opt_state",)       # params dedup portably
                n_s = int(zs["n"])
                shards, local_total = lay["shards"], lay["local_total"]
                cur_chunk_shape = (
                    n, shards * zero_lib.chunk_size(local_total, n))
                saved_chunk_shape = (
                    n_s, shards * zero_lib.chunk_size(local_total, n_s))
                rechunk = (lambda x: zero_lib.rechunk_boxed(
                    x, n, shards, local_total))

        def shape_of_saved(x):
            if x.shape == cur_chunk_shape:
                return jax.ShapeDtypeStruct(saved_chunk_shape, x.dtype)
            assert x.shape == (n,), (
                f"unexpected chunked state leaf shape {x.shape}")
            return jax.ShapeDtypeStruct((n_s,), x.dtype)

        # Per-worker state with NO refit path (exchange-strategy error-
        # feedback buffers, async diverged replicas) cannot cross a
        # worker-count change — fail with the real reason instead of a
        # raw leaf-shape mismatch deep in load_checkpoint (round-4
        # review).  Worker-count-portable layouts: dedup'd replicas
        # (BSP), and the FSDP/ZeRO chunked parts handled by refit above.
        n_saved = peek.get("n_workers")
        if n_saved is not None and int(n_saved) != n:
            stuck = sorted(set(boxed_parts) - set(refit_parts))
            if stuck:
                raise ValueError(
                    f"checkpoint was saved on {n_saved} workers; part(s) "
                    f"{stuck} hold per-worker state (exchange-strategy "
                    f"error feedback / diverged replicas) with no "
                    f"worker-count refit — resume on {n_saved} workers, "
                    f"or use elastic resume with the portable layouts "
                    f"(BSP / ZeRO-1 / FSDP; see docs/api.md)")
        template = {
            k: jax.tree.map(
                (shape_of_saved if k in refit_parts
                 else lambda x: shape_of(x, k in boxed_parts)), v)
            for k, v in self.step_state.items()}
        restored = ckpt_lib.load_checkpoint(ckpt_dir, template, epoch)
        if restored is None:
            return None

        if refit_parts:
            def refit_leaf(x):
                x = np.asarray(x)
                if x.shape == saved_chunk_shape:
                    return rechunk(x)
                return np.broadcast_to(x[:1], (n,) + x.shape[1:]).copy()

            for k in refit_parts:
                restored[k] = jax.tree.map(refit_leaf, restored[k])
        meta = restored.pop("_meta")
        rngs = restored.pop("_rng_keys", None)
        cursor = restored.pop("_cursor", None)
        sp = self._state_specs
        self.step_state = {
            k: (steps.place_boxed(v, self.mesh,
                                  None if sp is None else sp[k])
                if k in boxed_parts else
                steps.replicate_tree(v, n, self.mesh,
                                     None if sp is None else sp[k]))
            for k, v in restored.items()}
        if rngs:
            self._step_rng = rngs.get("step", self._step_rng)
            self._exch_key = rngs.get("exch", self._exch_key)
        if cursor and hasattr(self.data, "set_cursor"):
            self.data.set_cursor(cursor)
        return int(meta["epoch"])

    @property
    def n_params(self) -> int:
        return helper_funcs.tree_size(self.params)
