"""Worker main loops.

TPU-native rebuild of Theano-MPI's per-rule worker files
(``theanompi/worker.py``, ``easgd_worker.py`` + ``easgd_server.py``,
``gosgd_worker.py`` — SURVEY.md §2.5, §3.1–3.3): the epoch/batch driver that
calls ``model.train_iter`` → ``exchanger.exchange`` → recorder, runs the
per-epoch validation loop, ``adjust_hyperp``, and checkpointing.

One class per rule, as in the reference; they differ only in which exchanger
they construct and its cadence.  There is no separate EASGD *server* process:
on SPMD TPU the center parameter store is replicated mesh state inside the
EASGD exchanger (SURVEY.md §7 "asynchrony on SPMD hardware") — a chip is not
burned on serving parameters.
"""

from __future__ import annotations

import time
from typing import Optional

from .base import MeshProcess
from .parallel.exchanger import get_exchanger
from .utils import devprof, numerics, telemetry, tracing
from .utils.recorder import Recorder
from .utils.sentry import TrainingSentry
from .utils.watchdog import StallWatchdog


class Worker(MeshProcess):
    """Generic rule-driven worker (≙ reference ``BSP_Worker`` et al.)."""

    rule = "bsp"

    def __init__(self, config: Optional[dict] = None):
        super().__init__(config)
        self.get_internode_comm()
        self.init_device()
        # process-wide telemetry (utils/telemetry): on when record_dir is
        # set (or telemetry=true for in-memory metrics), else the inert
        # no-op; every component reads telemetry.active() lazily
        self.telemetry = telemetry.init(self.config)
        # causal tracing (docs/design.md §17): off unless tracing=true —
        # the exchanger's span stream + the wire propagation both gate on
        # the ONE tracer `enabled` check
        self.tracing = tracing.init(self.config)
        self.recorder = Recorder(self.config)
        self.recorder.telemetry = self.telemetry
        self.exchanger = get_exchanger(self.config.get("rule", self.rule),
                                       self.config)

    def run(self, model) -> Recorder:
        """The reference's ``run(model)`` epoch/batch loop (SURVEY.md §3.1)."""
        config = self.config
        # the compile recorder bucket: building the jit wrappers and
        # placing the state, NOT the XLA compile — the jit is lazy, so that
        # lands in the first train_iter (ring spans compile.place /
        # compile.xla)
        self.recorder.start()
        model.compile_iter_fns(self.exchanger)
        self.recorder.end("compile")
        if config.get("scale_lr", True) and self.size > 1:
            model.scale_lr(self.size)

        start_epoch = 0
        ckpt_dir = config.get("ckpt_dir")
        if ckpt_dir and config.get("resume", False):
            restored = model.load(ckpt_dir)
            if restored is not None:
                start_epoch = restored + 1
                if config.get("record_dir"):
                    # restore BOTH record lists (train + epoch) so the next
                    # save() rewrites the JSONL with the pre-resume lines
                    # intact — without this, Recorder.load()'s lossless
                    # round-trip never runs on the supervised-restart path
                    # it exists for
                    self.recorder.load(config["record_dir"])
                if self.verbose:
                    print(f"resumed from epoch {restored}", flush=True)

        # steps_per_call > 1: each train_iter dispatch covers several
        # steps; an epoch advances count by spc·(n_batch_train // spc)
        # (drop-last windows), NOT n_batch_train — the resume count must
        # replay the strided stream or the per-step rng fold desyncs from
        # the uninterrupted run when spc doesn't divide n_batch_train
        spc = max(1, int(getattr(model, "steps_per_call", 1)))
        count = start_epoch * ((model.data.n_batch_train // spc) * spc)
        epochs = config.get("epochs", model.epochs)
        # Timeline tracing (beyond the reference's wall-clock buckets,
        # SURVEY.md §5): trace_dir enables a jax.profiler capture of
        # trace_iters iterations starting at trace_start — view in
        # TensorBoard / Perfetto.  On a TPU the capture holds device
        # planes only (devprof.profile_options says why); the host side is
        # the span ring's rows of the same interval, written beside it as
        # host_spans.jsonl on the trace's clock.
        trace_dir = config.get("trace_dir")
        trace_start = int(config.get("trace_start", 5))
        trace_iters = max(1, int(config.get("trace_iters", 5)))
        trace_pending = trace_dir is not None
        trace_stop_at = None
        trace_t0_ns = 0

        def _stop_trace():
            nonlocal trace_stop_at
            import jax
            trace_t1_ns = time.time_ns()    # the captured steps end here:
            # what follows (the drain, the trace's export) is not the loop
            jax.block_until_ready(model.step_state["params"])
            jax.profiler.stop_trace()
            trace_stop_at = None
            if self.verbose:
                print(f"profiler trace saved to {trace_dir}", flush=True)
            # device-time attribution (utils/devprof): parse the capture
            # into compute/comm/exposed-comm/overlap, the device's idle
            # time by what each host thread was doing, and feed the
            # device.* gauges — the host-side phase.comm bracket goes
            # blind once collectives overlap backprop; this is the honest
            # breakdown
            try:
                devprof.write_host_spans(trace_dir, trace_t0_ns,
                                         trace_t1_ns)
                prof = devprof.profile_dir(trace_dir)
            except Exception as e:
                prof = None
                print(f"devprof: trace attribution failed ({e!r})",
                      flush=True)
            if prof is not None:
                if telem.enabled:
                    devprof.feed_telemetry(prof, telem)
                if self.verbose:
                    print(devprof.format_profile(prof, top=5), flush=True)
            if sentry is not None:
                # the block_until_ready + trace parse above is dead wall
                # time inside the next record's images/sec window — same
                # discontinuity as the val/ckpt boundary
                sentry.notice_discontinuity()

        t0 = time.time()
        # count strides by spc; leftover batches < spc roll to the next
        # epoch's shuffle, like the reference's drop-last batching.  When
        # compile_iter_fns fused the rule's exchange cadence into the
        # scanned dispatch (exchanger.fused), the Python exchange hook is
        # skipped outright — one XLA dispatch per k-step window covers the
        # steps AND their cadenced exchanges.
        fused = bool(getattr(self.exchanger, "fused", False))
        # failure detection (SURVEY §5): stall_timeout seconds without an
        # iteration completing → off-thread diagnostic (hung collectives /
        # transfers block the main thread inside jax, so detection can't
        # live on it).  0 (default) = off.  stall_action='exit' additionally
        # kills the process (exit code 42) after the dump so a supervisor
        # (launcher --supervise) can restart from the latest checkpoint —
        # only sane when the worker IS a subprocess; the in-process session
        # API should keep the default 'trace'.
        stall_action = str(config.get("stall_action", "trace"))
        assert stall_action in ("trace", "exit"), (
            f"unknown stall_action {stall_action!r}: use 'trace' "
            f"(diagnostic dump only) or 'exit' (kill for supervisor restart)")

        telem = self.telemetry
        # membership lease (parallel/membership.py): when lease_dir is set
        # this worker heartbeats wherever it beats the watchdog (every
        # iteration, every val batch) so an elastic controller can tell
        # dead/wedged from slow at ANY print cadence — the lease throttles
        # itself (min_interval_s), so the per-iteration cost is one
        # time.time() check, not a file write
        lease = None
        if config.get("lease_dir"):
            from .parallel.membership import WorkerLease
            lease = WorkerLease(config["lease_dir"],
                                int(config.get("rank", self.rank)),
                                telemetry_=telem)
            lease.beat(count)
        # training sentry (utils/sentry): NaN/inf + loss-spike + rolling
        # throughput-regression detection over the print-cadence records —
        # anomaly events + a flight dump instead of a silently sick run.
        # Costs nothing per step (it only sees what print_train_info
        # already materialized); on whenever telemetry is, sentry=false
        # opts out.
        sentry = None
        if telem.enabled and config.get("sentry", True):
            sentry = TrainingSentry(config, telem)
        self.sentry = sentry
        # live ops endpoint (utils/tracing, docs/design.md §17): a tiny
        # statusz socket answering health/uptime/current-span/last-events
        # queries over the wire framing, registered in the run dir so
        # scripts/fleetz.py can aggregate the whole fleet.  Idle cost is
        # zero (it only ever reads state other paths already maintain);
        # statusz=false opts out.
        statusz = None
        if telem.enabled and config.get("record_dir") and \
                config.get("statusz", True):
            statusz = tracing.StatuszServer(
                "worker", ident=int(config.get("rank", self.rank)),
                run_dir=config["record_dir"], telemetry_=telem,
                tracer_=self.tracing)
            statusz.start()
        # fleet health plane (utils/fleetmon, docs/design.md §20): a
        # low-rate daemon thread streaming metric snapshots (phase
        # p50/p99, img/s, HBM headroom, queue depth, wire health) to the
        # run's FleetCollector.  Never touches this hot loop — it reads
        # the registry the loop already feeds.
        streamer = None
        if telem.enabled and config.get("metrics_addr"):
            from .utils.fleetmon import MetricStreamer
            streamer = MetricStreamer(
                str(config["metrics_addr"]),
                rank=int(config.get("rank", self.rank)), role="worker",
                interval_s=float(config.get("metrics_interval_s", 1.0)),
                telemetry_=telem)
            streamer.start()

        def on_stall(elapsed, label):
            StallWatchdog._default_handler(watchdog, elapsed, label)
            if telem.enabled:
                # the flight ring holds the beats/phases leading into the
                # hang — dump it whether or not we are about to die
                telem.event("stall", elapsed=round(elapsed, 1), label=label,
                            action=stall_action)
                telem.dump_flight(reason=f"watchdog stall {elapsed:.0f}s "
                                         f"at {label}")
            if stall_action == "exit":
                import os
                if telem.enabled:
                    telem.close()
                print("WATCHDOG: stall_action=exit — terminating for "
                      "supervisor restart", flush=True)
                os._exit(42)

        watchdog = StallWatchdog(float(config.get("stall_timeout", 0)),
                                 on_stall=on_stall)
        if telem.enabled:
            telem.event("train_begin", rule=self.config.get("rule", self.rule),
                        model=type(model).__name__, spc=spc,
                        start_epoch=start_epoch, epochs=epochs,
                        size=self.size)
        try:
            with watchdog:
                for epoch in range(start_epoch, epochs):
                    model.adjust_hyperp(epoch)
                    model.data.shuffle_data(epoch + model.seed)
                    for _ in range(model.data.n_batch_train // spc):
                        count += spc
                        if trace_pending and count >= trace_start:
                            import jax
                            trace_t0_ns = time.time_ns()
                            jax.profiler.start_trace(
                                trace_dir,
                                profiler_options=devprof.profile_options())
                            trace_pending = False
                            # clamp the window to the dispatch stride:
                            # count advances by spc per iteration, so the
                            # old `count + 1 >= stop` check overshot by up
                            # to spc-1 iterations — round trace_iters up
                            # to whole windows instead
                            trace_stop_at = count + max(
                                1, (trace_iters + spc - 1) // spc) * spc
                        # (devprof counts dispatches from the `train`
                        # rows train_iter's recorder bracket leaves in
                        # the span ring)
                        model.train_iter(count, self.recorder)
                        if not fused:
                            self.exchanger.exchange(self.recorder, count)
                        watchdog.beat(f"epoch {epoch} iter {count}")
                        if lease is not None:
                            lease.beat(count)
                        if trace_stop_at is not None and count + spc >= trace_stop_at:
                            _stop_trace()
                        rec = self.recorder.print_train_info(count,
                                                             stride=spc)
                        if rec and telem.enabled:
                            # periodic gauge snapshot at print cadence:
                            # device HBM in-use/peak, host RSS, iteration
                            # rate — the HBM-headroom and throughput
                            # timelines in telemetry_report
                            telem.system_snapshot(
                                iter=count, epoch=epoch,
                                images_per_sec=rec["images_per_sec"])
                        # numerics health plane (§25): materialize the
                        # device aux exactly when cost/error already
                        # materialize — the in-graph sampler added no
                        # host round-trip, and this one rides the print
                        # cadence the run pays anyway
                        n_report = None
                        if rec and telem.enabled and \
                                getattr(model, "numerics_aux",
                                        None) is not None:
                            import jax
                            n_report = numerics.host_report(
                                jax.device_get(model.numerics_aux))
                            numerics.record(
                                telem, n_report,
                                rank=int(config.get("rank", self.rank)))
                        if rec and sentry is not None:
                            sentry.observe_record(rec)
                            if n_report is not None:
                                sentry.observe_numerics(n_report)

                    model.begin_val()
                    for _ in range(model.data.n_batch_val):
                        model.val_iter(count, self.recorder)
                        watchdog.beat(f"epoch {epoch} val @ iter {count}")
                        if lease is not None:
                            lease.beat(count)
                    model.end_val()
                    self.recorder.print_val_info(count)

                    if ckpt_dir:
                        model.save(ckpt_dir, epoch, count)
                    if config.get("record_dir"):
                        self.recorder.save(config["record_dir"])
                    watchdog.beat(f"epoch {epoch} end (ckpt/records saved)")
                    if lease is not None:
                        lease.beat(count, epoch=epoch)
                    if sentry is not None:
                        # the next print record's images/sec spans this
                        # val pass + ckpt + shuffle wall time — not a
                        # throughput regression
                        sentry.notice_discontinuity()
        except BaseException as e:
            # crash: leave the flight-recorder trail (last N events — beats,
            # phase brackets, gauges) next to the records, then re-raise;
            # launcher --supervise sweeps the dumps aside before restarting
            if telem.enabled:
                telem.event("crash", error=repr(e)[:300])
                telem.dump_flight(reason=repr(e)[:200])
                telem.close()
            raise
        finally:
            # async_ckpt: a completed epoch's in-flight write must land even
            # when an exception (or Ctrl-C) unwinds the loop — the daemon
            # writer would otherwise die mid-np.savez, truncating the file
            if hasattr(model, "wait_pending_ckpt"):
                import sys as _sys
                # capture BEFORE the try: inside an except block exc_info
                # reports the caught exception, not the unwinding one
                unwinding = _sys.exc_info()[0] is not None
                try:
                    model.wait_pending_ckpt()
                except Exception as ckpt_exc:
                    if not unwinding:
                        raise       # sole failure: surface it
                    print(f"async checkpoint ALSO failed during unwind: "
                          f"{ckpt_exc!r}", file=_sys.stderr, flush=True)
            if statusz is not None:
                # only a CLEAN exit deregisters: a crash keeps the
                # discovery doc so fleetz lists this worker DOWN
                import sys as _sys2
                statusz.stop(deregister=_sys2.exc_info()[0] is None)
            if streamer is not None:
                # a clean exit retires this rank at the collector; a
                # crash leaves the stream silent so heartbeat_age alerts
                import sys as _sys3
                streamer.stop(final=_sys3.exc_info()[0] is None)
        if trace_stop_at is not None:   # window outlived training: flush it
            _stop_trace()
        if lease is not None:
            lease.release()     # clean departure: 'finished', not a death
        if telem.enabled:
            telem.event("train_end", secs=round(time.time() - t0, 3),
                        epochs=epochs - start_epoch)
            telem.close()       # flush the stream + write the summary sidecar
        if self.verbose:
            print(f"training finished in {time.time() - t0:.1f}s "
                  f"({epochs - start_epoch} epochs)", flush=True)
        return self.recorder


class BSP_Worker(Worker):
    rule = "bsp"


class EASGD_Worker(Worker):
    rule = "easgd"


class ASGD_Worker(Worker):
    rule = "asgd"


class GOSGD_Worker(Worker):
    rule = "gosgd"


WORKERS = {
    "bsp": BSP_Worker,
    "easgd": EASGD_Worker,
    "asgd": ASGD_Worker,
    "gosgd": GOSGD_Worker,
}


def main(argv=None):
    """CLI entry: ``python -m theanompi_tpu.worker <rule> <modelfile>
    <modelclass> [key=value ...]`` — the per-rank command the reference's
    launcher composed into its ``mpirun`` line (SURVEY.md §2.6)."""
    import sys

    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) < 3:
        print("usage: python -m theanompi_tpu.worker <rule> <modelfile> "
              "<modelclass> [key=value ...]")
        return 1
    rule, modelfile, modelclass = argv[:3]
    config = {"rule": rule}
    for kv in argv[3:]:
        k, _, v = kv.partition("=")
        try:
            config[k] = int(v)
        except ValueError:
            try:
                config[k] = float(v)
            except ValueError:
                config[k] = {"true": True, "false": False}.get(v.lower(), v)
    # JAX's persistent compilation cache, at a fixed place (the launcher's
    # in-process path lands here too; the session API leaves it to its host
    # application, and a process pinned to the CPU — by `platform=cpu` here,
    # by JAX_PLATFORMS inside configure — is left without one)
    if config.get("platform") != "cpu":
        from .utils import jax_cache
        jax_cache.configure()
    worker = WORKERS[rule](config)
    model = worker.build_model(modelfile, modelclass)
    worker.run(model)
    return 0


if __name__ == "__main__":
    # a CLI worker owns its process: a fatal signal (supervisor kill,
    # scheduler preemption) dumps the flight recorder before dying.  The
    # in-process session API never installs these — host applications and
    # tests own their handlers (the hooks are no-ops while telemetry is
    # disabled, so installing before config parsing is safe).
    telemetry.install_signal_hooks()
    raise SystemExit(main())
