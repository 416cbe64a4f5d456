#!/usr/bin/env python
"""Benchmark — ONE process, one JSON line on success, non-zero exit on any
failure.

Measures steady-state training throughput (images/sec/chip) of one model
configuration on the chips this process sees — the reference's headline
metric (time per 5120 images, SURVEY.md §6) recast per-chip as
``BASELINE.json`` specifies.  A bare invocation (no BENCH_* env) measures
AlexNet b128 BSP at steps_per_call=4 (see ``_apply_flagship_defaults``);
the metric string records the spc so the number is never mislabeled.

There is no fallback.  Without a TPU the run is refused (exit 4) — also
under ``JAX_PLATFORMS=cpu``, which is ambient in CPU sandboxes and so is
not taken as a request.  ``BENCH_FORCE_CPU=1`` asks for a rehearsal of the
code path on the CPU; its row says so in the metric's name and is never a
device number.  A failed measurement raises; a ``device_kind`` missing from
``PEAK_BF16_FLOPS`` is an error where MFU is asked for.  ``python bench.py`` does not start a second
process that needs the chip (the real-data row generates its dataset in a
NumPy-only child).

Env knobs: ``BENCH_MODEL``
(alexnet|googlenet|vgg16|resnet50|cifar10|transformer_lm|moe_lm),
``BENCH_RULE`` (bsp|easgd|asgd|gosgd — the BASELINE.json staged configs pair
VGG-16 with EASGD and ResNet-50 with GoSGD), ``BENCH_ITERS``,
``BENCH_WARMUP``, ``BENCH_BATCH`` (per-chip batch override),
``BENCH_STRATEGY`` (exchange strategy string), ``BENCH_PRNG``
(rbg|threefry2x32 — default rbg: the TPU hardware RNG; dropout statistics
are unaffected; the chosen impl is recorded in the metric string),
``BENCH_CFG`` (JSON config overrides — transformer dims, tp/pp/sp; also
the opt-in AOT executable store, ``{"compile_cache": "<dir>"}``),
``BENCH_SPC`` (steps_per_call) + ``BENCH_SYNTH_BATCHES``,
``BENCH_BN_DTYPE`` (bn_norm_dtype lever), ``BENCH_MFU`` (=1 adds the MFU
column; ``BENCH_SPC_MFU=0`` disables the spc>1 single-step-flops
derivation), ``BENCH_BUCKET_BYTES`` (bucketed overlap-scheduled wire,
``parallel/buckets.py``: splits every exchange collective into ~N-byte
async start/done buckets; the row JSON then carries ``bucket_bytes`` +
``n_buckets`` — vocabulary pinned as ``devprof.BUCKET_ROW_COLUMNS``),
``BENCH_USHARD`` (=1 enables leaf-wise update-plane sharding,
``parallel/update_sharding.py``; rows carry the
``devprof.USHARD_ROW_COLUMNS`` memory columns; ``BENCH_USHARD_REPORT=1``
adds the columns to control rows), ``BENCH_FUSE`` (=0 forces the jnp
oracles of the compression kernels — the control row of the fused-kernel
A/B), ``BENCH_REAL_DATA`` (=1 drives the whole disk→augment→device
pipeline; + ``BENCH_DATA_DIR``), ``BENCH_WINLOAD`` (=1,
with BENCH_SPC>1: para_load window mode — the producer stacks+stages whole
spc windows off the hot path and the timed loop dequeues mesh-resident
windows), ``BENCH_TRACE`` (=1 captures a ``jax.profiler`` window of
``BENCH_TRACE_ITERS`` extra dispatches AFTER the timed loop — the
measurement itself is never perturbed — and folds the ``utils/devprof``
device-time attribution into the row: ``overlap_ratio`` /
``exposed_comm_secs`` / ``device_compute_secs`` / ``device_comm_secs`` plus
``device_mfu``; ``BENCH_TRACE_DIR`` keeps the raw capture for Perfetto).

JAX's persistent compilation cache is placed by
``theanompi_tpu.utils.jax_cache.configure`` (``JAX_COMPILATION_CACHE_DIR``
if set, else ``<checkout>/.jax_cache``).  Every row JSON carries
``compile_secs`` + ``cache: hit|miss|off`` for the AOT executable store,
which is off unless configured.

Every row JSON also folds in a telemetry summary (utils/telemetry, run
in-memory for the row): ``p50_step_secs``/``p95_step_secs`` (per-iteration
host wall inside the timed loop), ``peak_hbm_bytes`` (device
``memory_stats()`` after the run), and ``min_queue_depth`` (streaming rows:
the lowest prefetch queue depth the consumer saw).

The reference's published numbers are not retrievable (``BASELINE.md``):
``vs_baseline`` is computed against an ESTIMATED 1×K80 AlexNet figure from
the Theano-MPI era (~128 images/sec for batch-128 train+comm on one worker)
— an estimate beside a measurement, not a result.
"""

import json
import os
import subprocess
import sys
import time


# Estimated reference single-K80 AlexNet throughput.  NOT a bare guess:
# derived from the paper's time-per-5120-images shape (~40 s single
# worker => 128 img/s) and cross-checked by FLOP arithmetic against
# 2016-era cuDNN/K80 capability, with a ~90-250 img/s sensitivity band —
# full derivation in BASELINE.md "Derivation of the 128 img/s K80
# anchor".  vs_baseline cells inherit that ~2x band.
K80_ALEXNET_IPS = 128.0


def _force_cpu() -> bool:
    """The explicit request for a CPU rehearsal (dev/test workflows)."""
    return os.environ.get("BENCH_FORCE_CPU") == "1"


# the class-default per-chip batch of each model: sizes the synthetic
# dataset of the window-staging row when BENCH_BATCH is unset
_DEFAULT_BATCH = {"alexnet": 128, "googlenet": 32, "vgg16": 32,
                  "resnet50": 32, "cifar10": 128, "transformer_lm": 16,
                  "moe_lm": 16}



def _bucket_label(nbytes: int) -> str:
    """Label token for one bucket size: 4194304 → '4m', 65536 → '64k',
    else the raw byte count (row labels stay short and unambiguous)."""
    if nbytes % (1 << 20) == 0:
        return f"{nbytes >> 20}m"
    if nbytes % (1 << 10) == 0:
        return f"{nbytes >> 10}k"
    return str(nbytes)


# bf16 peak FLOP/s of one chip, keyed by a substring of ``device_kind``.
# Sources: Google Cloud TPU documentation, per generation ("TPU v5e":
# 197 TFLOP/s bf16).  A device that is not here is an error, not a default.
PEAK_BF16_FLOPS = (("v5 lite", 197e12), ("v5litepod", 197e12),
                   ("v6 lite", 918e12), ("v6e", 918e12), ("v5p", 459e12),
                   ("v5", 459e12), ("v4", 275e12), ("v3", 123e12),
                   ("v2", 45e12))


def _peak_flops(device) -> float:
    """bf16 peak FLOP/s of ``device`` from :data:`PEAK_BF16_FLOPS`; raises
    for a ``device_kind`` the table does not know."""
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in PEAK_BF16_FLOPS:
        if key in kind:
            return peak
    raise ValueError(
        f"no bf16 peak known for device_kind {device.device_kind!r} — add "
        f"it to bench.PEAK_BF16_FLOPS with its source")


def _xla_flops(compiled) -> float | None:
    """Flop count of an AOT-compiled executable via XLA's cost analysis;
    None when unavailable or nonsensical (some backends report -1)."""
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    f = float(ca.get("flops", 0.0))
    return f if f > 0 else None


def _ensure_bench_dataset(n_batches: int, batch_size: int,
                          data_dir: str = None) -> str:
    """Generate (once) a real on-disk batch-file dataset in the reference's
    .hkl layout for the BENCH_REAL_DATA row; ~25 MB per 128-image file.
    Also the shared generator for scripts/loader_bench.py."""
    d = data_dir or os.environ.get(
        "BENCH_DATA_DIR", f"/tmp/bench_imagenet_{batch_size}x{n_batches}")
    # img_mean.npy is written LAST by make_batch_dataset.py — its presence
    # marks a complete dataset; a generation killed mid-write leaves
    # train_hkl/ without it, so wipe and redo
    if os.path.isdir(os.path.join(d, "train_hkl")) and \
            not os.path.exists(os.path.join(d, "img_mean.npy")):
        import shutil
        print(f"bench: {d} is half-generated — regenerating", file=sys.stderr)
        shutil.rmtree(d)
    if not os.path.isdir(os.path.join(d, "train_hkl")):
        repo = os.path.dirname(os.path.abspath(__file__))
        print(f"bench: generating {n_batches}x{batch_size}-image dataset "
              f"at {d}", file=sys.stderr)
        subprocess.run(
            [sys.executable,
             os.path.join(repo, "scripts", "make_batch_dataset.py"),
             "--synthetic", str(n_batches), "--batch-size", str(batch_size),
             "--out", d],
            check=True, stdout=sys.stderr)
    return d


def bench_row_config(environ=None):
    """The ONE BENCH_* → model-config assembly, shared by the inner
    measurement below and ``scripts/prewarm_cache.py``: the prewarmed
    programs are byte-identical to the ones the measurement will request
    from the executable cache only if both venues build the config through
    the same code path (the round-5 lesson — shapes that merely LOOK the
    same forfeit the hit).

    Returns ``(model_name, rule, config, flags)`` where ``config`` has
    every program-shaping key (batch, spc, strategy, dtype levers, BENCH_CFG
    overrides) but NOT the venue keys the caller owns (mesh/size/rank/
    verbose, dataset sizing, para_load wiring); ``flags`` carries
    ``real_data``/``winload``/``prng``.
    """
    env = os.environ if environ is None else environ
    model_name = env.get("BENCH_MODEL", "alexnet")
    rule = env.get("BENCH_RULE", "bsp")
    config: dict = {}
    if env.get("BENCH_BATCH"):
        config["batch_size"] = int(env["BENCH_BATCH"])
    if env.get("BENCH_SYNTH_BATCHES"):
        # the CNN zoo's synthetic data keeps 4 batches by default; spc>4
        # multi-step dispatch needs at least spc distinct batches or
        # compile_iter_fns rejects it (every epoch would train zero steps)
        config["synthetic_batches"] = int(env["BENCH_SYNTH_BATCHES"])
    if env.get("BENCH_CFG"):
        # arbitrary config overrides as JSON (transformer dims etc.)
        config.update(json.loads(env["BENCH_CFG"]))
    if env.get("BENCH_STRATEGY"):
        config["exch_strategy"] = env["BENCH_STRATEGY"]
    if env.get("BENCH_SPC"):
        # multi-step dispatch (BASELINE.md round-3 analysis) — opt-in:
        # measured faster on TPU where host dispatch dominates, but the CPU
        # sim shows the opposite, so the default stays 1 until the TPU
        # numbers justify flipping it (ROADMAP S3).
        # Valid for every rule: async-rule rows (easgd-spcK / gosgd-spcK)
        # fuse their exchange cadence into the scanned dispatch
        config["steps_per_call"] = int(env["BENCH_SPC"])
    if env.get("BENCH_BN_DTYPE"):
        config["bn_norm_dtype"] = env["BENCH_BN_DTYPE"]
    if env.get("BENCH_BUCKET_BYTES"):
        # bucketed overlap-scheduled collectives (parallel/buckets.py):
        # every exchange wire splits into ~N-byte async start/done pairs
        config["bucket_bytes"] = int(env["BENCH_BUCKET_BYTES"])
    if env.get("BENCH_USHARD") == "1":
        # leaf-wise update-plane sharding (parallel/update_sharding.py):
        # optimizer moments + shardable exchanger state chunked over the
        # data axis, one fused allgather rebuilds full params
        config["update_sharding"] = True
    if env.get("BENCH_FUSE") == "0":
        # fused-compression CONTROL rows: force the jnp oracle path for the
        # compression kernels (ops/_pallas_util dispatch) and drop the
        # memoized decision so it re-reads the env.  Applied HERE — the one
        # shared env→config assembly — so prewarm and the measurement agree
        # on the compile_cache `no_pallas` key stamp.
        os.environ["THEANOMPI_TPU_NO_PALLAS"] = "1"
        from theanompi_tpu.ops import _pallas_util
        _pallas_util.reset_dispatch_cache()
    flags = {"real_data": env.get("BENCH_REAL_DATA") == "1",
             "winload": env.get("BENCH_WINLOAD") == "1",
             "prng": env.get("BENCH_PRNG", "rbg")}
    return model_name, rule, config, flags


def bench_row_mesh(row_config):
    """The row's mesh, shaped by its tp/pp/sp/n_workers keys — the one
    assembly the measurement and ``scripts/prewarm_cache.py`` share (a
    hand-copied twin that drifted would silently re-key every program)."""
    from theanompi_tpu.parallel.mesh import worker_mesh
    return worker_mesh(row_config.get("n_workers"),
                       tp=int(row_config.get("tp", 1)),
                       pp=int(row_config.get("pp", 1)),
                       sp=int(row_config.get("sp", 1)))


def bench_model_config(mesh, extra, row_config, **venue):
    """The row's model config: registry extras, then the row's own keys,
    then venue keys the caller owns (dataset sizing, para_load wiring,
    compile_cache) — shared with prewarm for the same drift-proofing
    reason as :func:`bench_row_mesh`."""
    from theanompi_tpu.parallel.mesh import WORKER_AXIS
    return {"mesh": mesh, "size": mesh.shape[WORKER_AXIS], "rank": 0,
            "verbose": False, **extra, **row_config, **venue}


def main() -> int:
    from theanompi_tpu.models.registry import MODELS
    # the ONE env→config assembly (shared with scripts/prewarm_cache.py)
    model_name, rule, row_config, flags = bench_row_config()
    if model_name not in MODELS:
        print(f"unknown BENCH_MODEL {model_name!r}; have {sorted(MODELS)}",
              file=sys.stderr)
        return 2
    iters = max(1, int(os.environ.get("BENCH_ITERS", "20")))
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "5")))
    want_trace = os.environ.get("BENCH_TRACE") == "1"
    trace_iters = max(1, int(os.environ.get("BENCH_TRACE_ITERS", "3")))
    # extra dispatches the post-loop trace window consumes — the ONE value
    # both dataset-provisioning computations below and the capture loop
    # share, so they cannot drift
    trace_extra = trace_iters + 1 if want_trace else 0

    import jax
    if _force_cpu():
        jax.config.update("jax_platforms", "cpu")
    from theanompi_tpu.utils import jax_cache
    jax_cache.configure()
    from theanompi_tpu.base import canonical_prng_impl
    prng = canonical_prng_impl(flags["prng"])
    if prng:
        jax.config.update("jax_default_prng_impl", prng)
    # in-memory telemetry (utils/telemetry — no stream): collects the
    # prefetch queue-depth histogram and compile-cache counters during the
    # row so the row JSON can carry tail/health evidence (p95 step time,
    # peak HBM, min queue depth), not just the mean the `value` field is
    from theanompi_tpu.utils import telemetry
    telem = telemetry.init({"telemetry": True})

    from theanompi_tpu.parallel.exchanger import get_exchanger
    from theanompi_tpu.parallel.mesh import WORKER_AXIS
    from theanompi_tpu.parallel import steps
    import importlib

    # model-parallel bench rows (tp/pp/sp in BENCH_CFG) shape the mesh
    mesh = bench_row_mesh(row_config)
    n_chips = mesh.shape[WORKER_AXIS]
    if not _force_cpu() and jax.devices()[0].platform != "tpu":
        # a CPU-speed row under a device metric's name is worse than no row
        print(f"refusing to measure: platform is "
              f"{jax.devices()[0].platform!r}, not 'tpu' (set "
              "BENCH_FORCE_CPU=1 for an explicit CPU run)", file=sys.stderr)
        return 4
    modelfile, modelclass, extra = MODELS[model_name]
    config = bench_model_config(mesh, extra, row_config)
    real_data = flags["real_data"]
    winload = flags["winload"]
    spc_cfg = int(config.get("steps_per_call", 1))
    if winload:
        # window-granular staging row (ISSUE 2): para_load on, the
        # PrefetchLoader producer stacks+stages whole spc windows off the
        # hot path and the timed loop dequeues mesh-resident windows
        assert spc_cfg > 1, "BENCH_WINLOAD needs BENCH_SPC > 1"
        config["para_load"] = True
        if not real_data:
            # synthetic data: size one epoch to cover the whole timed run
            # — windows stream FRESH batches (spc each), and an exhausted
            # epoch would block the dequeue forever.  Both
            # synthetic knobs: batch-file-family (ImageNet) counts
            # batches, DataBase-family (cifar10) counts images.
            need = (warmup + iters + 2 + trace_extra) * spc_cfg
            config.setdefault("synthetic_batches", need)
            config.setdefault(
                "synthetic_train",
                need * n_chips * int(config.get(
                    "batch_size", _DEFAULT_BATCH.get(model_name, 128))))
    if real_data:
        # verdict #3: drive the TPU from DISK — real batch files through the
        # native augment pass + PrefetchLoader staging to device — so the
        # recorded img/s includes the whole input pipeline, not just compute
        assert spc_cfg == 1 or winload, (
            "BENCH_REAL_DATA measures the streaming pipeline; spc>1 reuses "
            "a staged stack unless BENCH_WINLOAD=1 streams staged windows")
        # each training step consumes `size` batch FILES (one per chip,
        # imagenet.py files_per_step) — scale the dataset so one epoch
        # covers the whole timed run on any mesh size
        config["data_dir"] = _ensure_bench_dataset(
            n_batches=max(32, (warmup + iters + 4 + trace_extra)
                          * spc_cfg) * n_chips,
            batch_size=int(config.get("batch_size", 128)))
        config["para_load"] = True

    import jax.numpy as jnp
    want_mfu = bool(os.environ.get("BENCH_MFU"))

    def measure(cfg):
        """Build + warm up + time one configuration; XLA compilation happens
        at the first warmup call, so any lowering failure lands here."""
        model = getattr(importlib.import_module(modelfile), modelclass)(cfg)
        exchanger = get_exchanger(rule, cfg)
        model.compile_iter_fns(exchanger)
        spc = int(cfg.get("steps_per_call", 1))
        streaming = real_data or winload
        if streaming and spc > 1:
            # window mode (BENCH_WINLOAD): the producer assembles+stages
            # whole [spc, ...] windows in the background; every timed step
            # dequeues a FRESH mesh-resident window
            model.data.shuffle_data(int(cfg.get("seed", 42)))
            dev_batch = model.data.next_train_window(0)
            n_images = int(dev_batch["y"].shape[0]) * int(
                dev_batch["y"].shape[1])
        elif streaming:
            # PrefetchLoader producer: loads .hkl from disk, augments via the
            # native pass, stages to device; every timed step consumes a
            # FRESH batch so the whole pipeline is on the clock
            model.data.shuffle_data(int(cfg.get("seed", 42)))
            dev_batch = model.data.next_train_batch(0)
            n_images = int(dev_batch["y"].shape[0])
        elif spc > 1:
            batches = [model.data.next_train_batch(j) for j in range(spc)]
            dev_batch = steps.put_batch_stack(mesh, batches,
                                              model.batch_spec())
            n_images = int(batches[0]["y"].shape[0]) * spc
        else:
            batch = model.data.next_train_batch(0)
            dev_batch = steps.put_batch(mesh, batch, model.batch_spec())
            n_images = int(batch["y"].shape[0])
        lr = jnp.float32(model.current_lr)
        rng = jax.random.key(0)

        compiled = None
        mfu_this = want_mfu and spc == 1
        if mfu_this:
            # AOT-compile once and reuse the SAME executable for the timed
            # loop and the flop count (a separate lower().compile() after
            # the run would pay a second full XLA compile).  When the
            # executable cache already AOT-compiled the step inside
            # compile_iter_fns, THAT object (possibly a ~ms deserialize)
            # is the one to reuse — cost_analysis works on it either way.
            compiled = getattr(model, "_train_compiled", None)
            if compiled is None:
                compiled = model.train_fn.lower(
                    model.step_state, dev_batch, lr, rng,
                    jnp.int32(0)).compile()
            train_fn = compiled
        else:
            train_fn = model.train_fn

        load_wait = [0.0]

        def step(i):
            if streaming:
                t0 = time.time()
                b = model.data.next_train_window((i + 1) * spc) if spc > 1 \
                    else model.data.next_train_batch(i)
                load_wait[0] += time.time() - t0   # consumer BLOCKED on the
            else:                                  # producer = overlap gap
                b = dev_batch
            # stride the count exactly like the worker loop (1-based,
            # count += spc before the dispatch): the fused in-scan cadence
            # (easgd-spcK / gosgd-spcK rows) fires at its true rate, and
            # the spc=1 rows fire at the SAME phase — no extra step-0
            # exchange skewing the spc1-vs-spcK comparison
            c = (i + 1) * spc
            model.step_state, cost, err = train_fn(
                model.step_state, b, lr, rng, jnp.int32(c))
            exchanger.exchange(None, c)  # rule cadence (no-op when fused
            #                              in-scan or for BSP grads)

        def drain():
            # block on the state, not the cost: the last exchange collective
            # (non-BSP rules) reassigns step_state and would otherwise still
            # be in flight when the clock stops
            jax.block_until_ready(model.step_state["params"])

        for i in range(warmup):
            step(i)
        drain()
        load_wait[0] = 0.0            # only the timed window counts
        step_secs = []                # per-iteration wall inside the timed
        t0 = time.time()              # loop: host dispatch (+ dequeue wait
        for i in range(iters):        # on streaming rows) — its p95 is the
            ts = time.time()          # row's tail-latency evidence
            step(warmup + i)
            step_secs.append(time.time() - ts)
        drain()
        dt = time.time() - t0

        spc1_flops = None
        if want_mfu and not mfu_this and not streaming and \
                os.environ.get("BENCH_SPC_MFU", "1") != "0":
            # XLA's cost_analysis does not reliably scale the scan body by
            # its trip count, so the spc>1 executable can't be read
            # directly.  AFTER the timed window (no extra buffers or
            # compile perturbing the measurement), AOT-compile the SINGLE-
            # step program — a persistent-compile-cache hit when this
            # config's spc=1 row ran earlier; on a cold cache this pays a
            # second compile — purely for its flop count, scaled by spc in
            # the caller.
            try:
                cache = getattr(model, "compile_cache", None)
                if cache is not None and cache.enabled:
                    # route through the executable store via the ONE shared
                    # avals/label/extras composition
                    # (model_base.aot_train_program) — a guaranteed hit
                    # when this config's spc=1 row (or
                    # scripts/prewarm_cache.py) ran earlier, instead of
                    # hoping for an opaque XLA-cache hit
                    compiled1, info1 = model.aot_train_program(
                        cache, spc=1, exchanger=exchanger)
                    print(f"bench: spc1 flop-count program cache: "
                          f"{info1['cache']} "
                          f"({info1.get('compile_secs')}s)", file=sys.stderr)
                    spc1_flops = _xla_flops(compiled1)
                else:
                    single_fn = steps.build_train_step(mesh, model,
                                                       exchanger, n_steps=1)
                    dev1 = steps.put_batch(mesh, batches[0],
                                           model.batch_spec())
                    spc1_flops = _xla_flops(
                        single_fn.lower(model.step_state, dev1, lr, rng,
                                        jnp.int32(0)).compile())
            except Exception as e:
                print(f"mfu for spc>1 unavailable (single-step flop "
                      f"count failed: {e!r})", file=sys.stderr)
        # the row's load-wait evidence is frozen HERE: the trace window
        # below keeps calling step() (streaming rows dequeue more batches
        # after the producer idled through the flop-count gap), and those
        # waits must not contaminate load_wait_share, which divides by the
        # timed-loop-only dt
        timed_load_wait = load_wait[0]
        trace_profile = None
        if want_trace:
            # AFTER the timed window (nothing perturbs the measurement):
            # capture trace_iters extra dispatches and attribute the device
            # timeline — comm vs compute vs EXPOSED comm, the observability
            # ROADMAP item 1's bucketed-overlap work is gated on
            from theanompi_tpu.utils import devprof
            tdir = os.environ.get("BENCH_TRACE_DIR")
            # pipelined rows read the schedule's tick structure out of the
            # raw hop events (devprof.pipeline_schedule_report), so the
            # capture dir must outlive the context manager
            pipe_pp = int(config.get("pp", 1) or 1)
            own_tdir = None
            if pipe_pp > 1 and tdir is None:
                import tempfile
                tdir = own_tdir = tempfile.mkdtemp(prefix="bench_pipe_")
            try:
                with devprof.capture(tdir) as cap:
                    for i in range(trace_iters):
                        step(warmup + iters + i)
                    drain()
                trace_profile = cap.profile
                if trace_profile is None:
                    print("bench: BENCH_TRACE capture produced no usable "
                          "trace", file=sys.stderr)
                elif pipe_pp > 1:
                    rep = devprof.pipeline_schedule_report(
                        devprof.load_dir_events(tdir), pp=pipe_pp,
                        v=int(config.get("pp_interleave", 1) or 1),
                        m=int(config.get("pp_microbatches", 1) or 1))
                    trace_profile["pipeline_bubble_ticks"] = \
                        rep["bubble_fraction_ticks"]
                    trace_profile["pipeline_bubble_time"] = \
                        rep["bubble_fraction"]
                    trace_profile["pipeline_schedule_verified"] = \
                        rep["schedule_verified"]
            except Exception as e:
                print(f"bench: BENCH_TRACE capture failed ({e!r})",
                      file=sys.stderr)
            finally:
                if own_tdir is not None:
                    import shutil
                    shutil.rmtree(own_tdir, ignore_errors=True)
        return (model, spc, n_images, dt, compiled, timed_load_wait,
                spc1_flops, step_secs, trace_profile)

    model, spc, n_images, dt, compiled, load_wait, spc1_flops, \
        step_secs, trace_profile = measure(config)

    ips = n_images * iters / dt
    ips_chip = ips / n_chips

    mfu = None
    peak = _peak_flops(jax.devices()[0]) if want_mfu else None
    if compiled is not None and peak:
        # XLA's flop count for the (per-device, SPMD-partitioned) module vs
        # one chip's bf16 peak → per-chip MFU
        try:
            flops = _xla_flops(compiled)
            if flops:
                mfu = round(flops / (dt / iters) / peak, 4)
        except Exception as e:
            print(f"mfu unavailable: {e}", file=sys.stderr)
    elif spc1_flops and peak:
        # spc>1 rows: flops of ONE step from the separately-compiled spc=1
        # program × spc steps per timed call
        mfu = round(spc1_flops * spc / (dt / iters) / peak, 4)

    # a sequence model's "image" is a sequence — label it honestly, and
    # don't divide sequences/sec by an AlexNet images/sec estimate
    kind = extra.get("sample_kind", "images")
    base_note = ("vs_baseline is vs ESTIMATED-K80 "
                 f"{K80_ALEXNET_IPS:.0f} img/s, not a measured reference"
                 if kind == "images" else
                 "vs_baseline n/a for sequence models")
    bucket_b = int(config.get("bucket_bytes", 0) or 0)
    bucket_note = f", bucket={_bucket_label(bucket_b)}" if bucket_b else ""
    ushard_note = (", ushard (sharded update plane)"
                   if config.get("update_sharding") else "")
    rehearsal = "CPU REHEARSAL, not a device number: " if _force_cpu() else ""
    out = {
        "metric": f"{rehearsal}{kind}_per_sec_per_chip ({model_name} batch "
                  f"{model.batch_size} {rule.upper()}, {n_chips} chip(s), "
                  f"{jax.devices()[0].platform}, prng={prng or 'default'}"
                  f"{', spc=' + str(spc) if spc > 1 else ''}{bucket_note}"
                  f"{ushard_note}"
                  f"{', real-data (disk->native augment->device)' if real_data else ''}"
                  f"{', winload (producer-staged spc windows)' if winload else ''}"
                  f"; {base_note})",
        "value": round(ips_chip, 2),
        "unit": f"{kind}/sec/chip",
        "vs_baseline": round(ips_chip / K80_ALEXNET_IPS, 3)
        if kind == "images" else None,
    }
    # executable-cache evidence (the round-5 verdict's ask): where the
    # train program came from and what the compile cost this row — ~0 via
    # deserialize when prewarm/a previous pass already built it
    cinfo = (getattr(model, "compile_info", None) or {}).get("train", {})
    out["cache"] = cinfo.get("cache", "off")
    out["compile_secs"] = cinfo.get("compile_secs")
    if mfu is not None:
        out["mfu"] = mfu
    if bucket_b:
        # the bucketed-wire columns (devprof.BUCKET_ROW_COLUMNS — the
        # schema-drift checker pins both names against bench.py): the
        # knob and the planner's resulting collectives-per-exchange, so
        # overlap_ratio movements can be read against bucket count
        out["bucket_bytes"] = bucket_b
        try:
            out["n_buckets"] = model.exchanger.n_buckets()
        except Exception as e:
            print(f"bench: n_buckets unavailable ({e!r})", file=sys.stderr)
            out["n_buckets"] = None
    if (config.get("update_sharding") or config.get("zero_opt")
            or os.environ.get("BENCH_USHARD_REPORT") == "1"):
        # the update-plane memory columns (devprof.USHARD_ROW_COLUMNS):
        # measured per-chip update-state bytes vs the replicated-equivalent
        # baseline, so the headline ~N× shrink is read off the row itself.
        # Control rows set BENCH_USHARD_REPORT=1 to carry the columns too
        # (shrink ~1.0) so a join never compares against absence.
        from theanompi_tpu.utils import devprof
        try:
            out.update(devprof.update_state_report(model))
        except Exception as e:
            print(f"bench: update_state_report unavailable ({e!r})",
                  file=sys.stderr)
    strat_cfg = str(config.get("exch_strategy", "") or "")
    if strat_cfg in ("onebit", "topk") or strat_cfg.startswith("powersgd"):
        # the compression-traffic columns (devprof.COMPRESS_ROW_COLUMNS):
        # modeled HBM bytes one exchange moves through the compression
        # pipeline, unfused op graph vs fused kernel pipeline (docs/
        # design.md §24) — a model computed from shapes, to be joined
        # against measured step time (ROADMAP S6)
        from theanompi_tpu.utils import devprof
        try:
            _rep = devprof.compress_traffic_report(model)
            if _rep:
                out.update(_rep)
        except Exception as e:
            print(f"bench: compress_traffic_report unavailable ({e!r})",
                  file=sys.stderr)
    if trace_profile is not None:
        # trace-derived columns (utils/devprof, BENCH_TRACE=1): device
        # compute/comm/EXPOSED-comm time over the traced window and the
        # overlap ratio — plus device_mfu, the device-timeline cross-check
        # of the host-clock cost_analysis `mfu` column above
        from theanompi_tpu.utils import devprof
        flops_per_dispatch = None
        if spc1_flops:
            flops_per_dispatch = spc1_flops * spc
        elif compiled is not None:
            try:
                flops_per_dispatch = _xla_flops(compiled)
            except Exception:
                flops_per_dispatch = None
        out.update(devprof.profile_row_fields(
            trace_profile,
            total_flops=(flops_per_dispatch * trace_iters
                         if flops_per_dispatch else None),
            peak_flops=peak or None))
        # pipelined rows (devprof.PIPELINE_ROW_COLUMNS): the hop-event
        # schedule measurement — tick-count bubble, wall-time bubble,
        # and whether the capture's hop count verified the tick structure
        for col in devprof.PIPELINE_ROW_COLUMNS:
            if col in trace_profile:
                out[col] = trace_profile[col]
    if real_data or winload:
        # overlap evidence (SURVEY §2.8 "input pipeline at AlexNet
        # speeds"): the share of the timed window the consumer spent
        # BLOCKED waiting for the loader; ~0 = the producer kept up
        out["load_wait_share"] = round(load_wait / dt, 4)
    # telemetry fold-in: tails and health, not just the mean.  p95 of the
    # per-iteration wall inside the timed loop (host dispatch + dequeue
    # wait on streaming rows — a straggling loader or a periodic stall
    # shows here while the mean hides it), device peak HBM after the run,
    # and the minimum prefetch queue depth the consumer ever saw.
    if step_secs:
        h = telemetry.Histogram()     # the ONE percentile definition
        for v in step_secs:
            h.observe(v)
        out["p50_step_secs"] = round(h.percentile(50), 5)
        out["p95_step_secs"] = round(h.percentile(95), 5)
        # the EXACT streaming extreme (tracked outside the reservoir):
        # the single worst iteration — the sample an SLO cares about,
        # which a thinned reservoir's percentile can drop
        out["max_step_secs"] = round(h.max, 5)
    try:
        ms = jax.local_devices()[0].memory_stats() or {}
        if "peak_bytes_in_use" in ms:
            out["peak_hbm_bytes"] = int(ms["peak_bytes_in_use"])
    except Exception:
        pass                          # CPU sim: no memory_stats
    qd = telem.hists.get("prefetch.queue_depth")
    if qd is not None and qd.count:
        out["min_queue_depth"] = qd.min
    if getattr(model, "numerics_aux", None) is not None:
        # §25 numerics columns (rows with `numerics` on): the worst-rank
        # grad norm and the cross-rank beacon spread of the LAST sampled
        # step — bench rows carry training-health evidence, not just speed
        from theanompi_tpu.utils import numerics as _numerics
        try:
            _rep = _numerics.host_report(
                jax.device_get(model.numerics_aux))
            if _rep is not None:
                out["grad_norm"] = round(float(_rep["grad_norm"]), 6)
                out["divergence"] = None if _rep["divergence"] is None \
                    else float(_rep["divergence"])
        except Exception as e:
            print(f"bench: numerics report unavailable ({e!r})",
                  file=sys.stderr)
    print(json.dumps(out))
    return 0


def _apply_flagship_defaults() -> None:
    """A bare ``python bench.py`` (no BENCH_* env) measures AlexNet b128
    BSP with steps_per_call=4 multi-step dispatch (BASELINE.md round-3
    analysis: host dispatch is first-order at this size); the metric string
    records it.  ANY config-shaping BENCH_* knob disables the default —
    explicit rows keep their exact semantics; only the truly bare
    invocation gets this config."""
    shaping = ("BENCH_MODEL", "BENCH_RULE", "BENCH_BATCH", "BENCH_STRATEGY",
               "BENCH_CFG", "BENCH_SPC", "BENCH_SYNTH_BATCHES",
               "BENCH_BN_DTYPE", "BENCH_REAL_DATA",
               "BENCH_WINLOAD", "BENCH_BUCKET_BYTES", "BENCH_USHARD",
               "BENCH_FUSE")
    if any(k in os.environ for k in shaping):
        return
    os.environ["BENCH_SPC"] = "4"


if __name__ == "__main__":
    _apply_flagship_defaults()
    sys.exit(main())
