"""Launcher.

TPU-native rebuild of Theano-MPI's ``theanompi/launcher.py``
(SURVEY.md §2.6): the reference composed an ``mpirun -np N ... python -u -m
theanompi.<worker> <modelfile> <modelclass>`` command line (MPMD for EASGD's
server+workers) with per-rank ``THEANO_FLAGS`` env, spawned it, and forwarded
worker stdout.

On TPU there is nothing to spawn on a single host — one process drives all
local chips — so the local path simply runs the worker in-process.  For a
multi-host TPU pod slice the launcher composes the per-host command lines
(every host runs the SAME program under ``jax.distributed``; rank binding is
automatic), either printing them for ``gcloud compute tpus tpu-vm ssh
--worker=all --command=...`` or executing the local host's share.
"""

from __future__ import annotations

import argparse
import os
import shlex
import subprocess
import sys
from typing import List, Optional


def compose_worker_cmd(rule: str, modelfile: str, modelclass: str,
                       config_kv: List[str],
                       coordinator: Optional[str] = None,
                       num_processes: Optional[int] = None,
                       process_id: Optional[int] = None) -> List[str]:
    """Build the per-host worker command (≙ the reference's mpirun line)."""
    cmd = [sys.executable, "-u", "-m", "theanompi_tpu.worker",
           rule, modelfile, modelclass]
    if coordinator:
        cmd.append(f"coordinator_address={coordinator}")
    if num_processes:
        cmd.append(f"num_processes={num_processes}")
    if process_id is not None:
        cmd.append(f"process_id={process_id}")
    cmd.extend(config_kv)
    return cmd


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="theanompi_tpu.launcher",
        description="Launch distributed training (≙ Theano-MPI's mpirun "
                    "composition). Local: runs in-process over all chips. "
                    "--num-hosts>1: prints/executes per-host commands.")
    p.add_argument("--rule", default="bsp",
                   choices=["bsp", "easgd", "asgd", "gosgd"])
    p.add_argument("--modelfile", default="theanompi_tpu.models.cifar10")
    p.add_argument("--modelclass", default="Cifar10_model")
    p.add_argument("--n-workers", type=int, default=None,
                   help="chips to use on this host (default: all)")
    p.add_argument("--num-hosts", type=int, default=1)
    p.add_argument("--coordinator", default=None,
                   help="host:port of process 0 (multi-host)")
    p.add_argument("--process-id", type=int, default=None,
                   help="this host's index (multi-host exec mode)")
    p.add_argument("--emit-only", action="store_true",
                   help="print the per-host commands instead of executing")
    p.add_argument("--supervise", type=int, default=0, metavar="N",
                   help="run the worker as a supervised subprocess and "
                        "restart it (with resume=true) up to N times on "
                        "crash — pair with ckpt_dir for checkpoint-based "
                        "recovery (single-host); restarts back off "
                        "exponentially (--backoff) and a crash loop "
                        "(--crash-limit failures within --crash-window) "
                        "exits nonzero with the flight-recorder tail")
    p.add_argument("--min-uptime", type=float, default=0.0, metavar="SEC",
                   help="crash-loop guard: a nonzero exit within SEC "
                        "seconds is treated as unrecoverable (config/usage "
                        "error) and is NOT retried; 0 = always retry")
    p.add_argument("--backoff", type=float, default=1.0, metavar="SEC",
                   help="supervised-restart backoff base: delay before "
                        "restart N is min(SEC·2^N, --backoff-max) ±25%% "
                        "jitter (the bench probe-recovery pattern); "
                        "0 = immediate restarts")
    p.add_argument("--backoff-max", type=float, default=30.0, metavar="SEC",
                   help="supervised-restart backoff cap (default 30)")
    p.add_argument("--crash-limit", type=int, default=5, metavar="N",
                   help="crash-loop breaker: N worker failures within "
                        "--crash-window seconds exit nonzero immediately "
                        "with the flight-recorder tail printed instead of "
                        "burning the remaining restarts (default 5)")
    p.add_argument("--crash-window", type=float, default=300.0,
                   metavar="SEC",
                   help="crash-loop breaker window (default 300)")
    p.add_argument("--elastic", type=int, default=0, metavar="N",
                   help="elastic membership mode (easgd/asgd): spawn N "
                        "island workers around a center server under the "
                        "membership controller — dead/preempted workers "
                        "leave and rejoin WITHOUT stopping the run "
                        "(parallel/membership.py; BSP instead uses "
                        "--supervise world restarts)")
    p.add_argument("--elastic-steps", type=int, default=256, metavar="K",
                   help="elastic mode: local steps per worker before a "
                        "clean exit (default 256)")
    p.add_argument("--host-devices", type=int, default=0, metavar="K",
                   help="elastic mode, CPU venue: each worker simulates K "
                        "chips on the cpu backend (0 = the backend JAX "
                        "finds; refused for more than one worker unless "
                        "the platform is pinned to cpu)")
    p.add_argument("--center-proc", action="store_true",
                   help="elastic mode: run the center server as its OWN "
                        "supervised process — crash-atomic snapshots, "
                        "respawn-from-snapshot with backoff, the "
                        "center_down/center_restored event pair; workers "
                        "ride a center outage out on wire retries "
                        "(parallel/wire.py, design.md §15)")
    p.add_argument("--record-dir", default=None, metavar="DIR",
                   help="record/telemetry directory (same as the "
                        "record_dir=DIR config key): recorder dumps, the "
                        "per-rank telemetry_rank*.jsonl event streams, and "
                        "crash flight recordings all land here — report "
                        "with scripts/telemetry_report.py DIR")
    p.add_argument("config", nargs="*", help="key=value model/worker config")
    args = p.parse_args(argv)

    kv = list(args.config)
    if args.n_workers:
        kv.append(f"n_workers={args.n_workers}")
    if args.record_dir and \
            not any(c.startswith("record_dir=") for c in kv):
        kv.append(f"record_dir={args.record_dir}")
    record_dir = next((c.partition("=")[2] for c in kv
                       if c.startswith("record_dir=")), None)
    if record_dir and not any(c.startswith("run_id=") for c in kv):
        # one run id for every host/restart of this launch: per-rank
        # telemetry streams (utils/telemetry) then correlate into one run
        # for scripts/telemetry_report.py
        import time as _t
        kv.append(f"run_id=run{int(_t.time())}")

    if args.num_hosts > 1:
        cmds = [compose_worker_cmd(args.rule, args.modelfile, args.modelclass,
                                   kv, args.coordinator, args.num_hosts, i)
                for i in range(args.num_hosts)]
        if args.emit_only or args.process_id is None:
            print("# run on each TPU host (e.g. via gcloud compute tpus "
                  "tpu-vm ssh --worker=all):")
            for i, c in enumerate(cmds):
                print(f"# host {i}:")
                print(shlex.join(c))
            return 0
        return subprocess.call(cmds[args.process_id])

    if args.elastic > 0:
        # Elastic membership (parallel/membership.py): workers join/leave
        # mid-run; the async center algebra absorbs the churn — no
        # world restart.  BSP has no shrink reaction: refuse early.
        if args.elastic > 1 and args.host_devices == 0 \
                and os.environ.get("JAX_PLATFORMS") != "cpu" \
                and "platform=cpu" not in kv:
            # a chip belongs to one process: the island workers are
            # separate processes and nothing binds each to its own chip
            print(f"launcher: --elastic {args.elastic} starts "
                  f"{args.elastic} worker processes, and on an accelerator "
                  f"host each would claim every chip — refused; pass "
                  f"--host-devices K (CPU venue) or platform=cpu",
                  file=sys.stderr)
            return 2
        from .parallel.membership import parse_kv, run_elastic
        return run_elastic(args.rule, args.modelfile, args.modelclass,
                           parse_kv(kv), args.elastic,
                           steps=args.elastic_steps,
                           host_devices=args.host_devices,
                           center_proc=args.center_proc)

    if args.supervise > 0:
        # Failure recovery (SURVEY §5): the worker runs as a subprocess so a
        # crash (or a watchdog-triggered exit) doesn't take the supervisor
        # down; each restart resumes — after a bounded-backoff wait — from
        # the latest *valid* per-epoch checkpoint (utils/checkpoint's
        # crash-atomic writes + newest-valid fallback make a SIGKILL
        # mid-save unable to brick the resume).
        if not any(c.startswith("ckpt_dir=") for c in kv):
            print("warning: --supervise without ckpt_dir= restarts training "
                  "from scratch each time", file=sys.stderr)
        base = compose_worker_cmd(args.rule, args.modelfile, args.modelclass,
                                  kv)
        import time as _time

        from .parallel.membership import (Backoff, CrashLoopBreaker,
                                          flight_tail_lines)
        backoff = Backoff(base=args.backoff, cap=args.backoff_max) \
            if args.backoff > 0 else None
        breaker = CrashLoopBreaker(limit=args.crash_limit,
                                   window_s=args.crash_window)

        def sweep(attempt: int, rc: int) -> None:
            # a dead worker's flight recordings (utils/telemetry dumps
            # flight_rank*.jsonl into record_dir on crash/stall-exit) are
            # moved aside per attempt, so the restart's own eventual dumps
            # can't overwrite the trail that explains THIS death
            if not record_dir:
                return
            from .utils.telemetry import sweep_flight_dumps
            dest = sweep_flight_dumps(record_dir,
                                      f"attempt{attempt}_rc{rc}")
            if dest:
                print(f"swept flight recordings to {dest}", file=sys.stderr)

        def print_flight_tail() -> None:
            if record_dir:
                for line in flight_tail_lines(record_dir):
                    print(line, file=sys.stderr)

        rc = 1
        for attempt in range(args.supervise + 1):
            cmd = base if attempt == 0 else base + ["resume=true"]
            t0 = _time.monotonic()
            rc = subprocess.call(cmd)
            if rc == 0:
                return 0
            sweep(attempt, rc)
            uptime = _time.monotonic() - t0
            if args.min_uptime and uptime < args.min_uptime:
                print(f"worker exited rc={rc} after only {uptime:.1f}s "
                      f"(< --min-uptime {args.min_uptime}s) — treating as "
                      f"unrecoverable, not retrying", file=sys.stderr)
                return rc
            if breaker.record_failure():
                # systemic failure (bad config, poisoned state, dead
                # backend): retrying just hides it — stop with evidence
                print(f"crash loop: {args.crash_limit} failures within "
                      f"{args.crash_window:.0f}s — giving up (rc={rc})",
                      file=sys.stderr)
                print_flight_tail()
                return rc
            if attempt < args.supervise:
                delay = backoff.delay(attempt) if backoff else 0.0
                print(f"worker exited rc={rc}; restarting in {delay:.1f}s "
                      f"({attempt + 1}/{args.supervise})", file=sys.stderr)
                if delay:
                    _time.sleep(delay)
        print(f"supervised restarts exhausted ({args.supervise}) — "
              f"giving up (rc={rc})", file=sys.stderr)
        print_flight_tail()
        return rc

    # single host: in-process (no spawn needed — the mesh IS the workers)
    from .worker import main as worker_main
    return worker_main([args.rule, args.modelfile, args.modelclass] + kv)


if __name__ == "__main__":
    raise SystemExit(main())
