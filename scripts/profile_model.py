#!/usr/bin/env python
"""Capture a profiler trace of one model's training step and print the
device-time attribution: top op classes, compute vs collective time,
EXPOSED collective time, and the comm/compute overlap ratio.

The reference's perf story was wall-clock section buckets (SURVEY.md
§2.10); the per-op breakdown comes from XLA's profiler.  The capture,
glob walk, and trace parse live in ``theanompi_tpu/utils/devprof.py``
(the shared, tested trace reader — this script used to do the walk
inline); this harness just builds the model, drives a traced window, and
formats the result.

Usage:
    python scripts/profile_model.py [model] [batch] [iters]
        [--rule bsp] [--spc K] [--json OUT]

``--json`` writes the machine-readable profile (the full devprof dict +
run metadata) so BASELINE.md's MFU/bottleneck table regenerates
mechanically instead of by scraping console output.

Env: PROFILE_DIR (trace capture dir, default /tmp/tpu_profile_<model>).
"""

import argparse
import json
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("model", nargs="?", default="resnet50")
    ap.add_argument("batch", nargs="?", type=int, default=0)
    ap.add_argument("iters", nargs="?", type=int, default=10)
    ap.add_argument("--rule", default="bsp",
                    choices=["bsp", "easgd", "asgd", "gosgd"])
    ap.add_argument("--spc", type=int, default=1,
                    help="steps_per_call of the traced dispatch")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write the machine-readable profile here "
                         "('-' for stdout)")
    ap.add_argument("--cfg", default=None, metavar="JSON",
                    help="JSON config overrides (bench.py BENCH_CFG "
                         "conventions — transformer dims, pp/tp/sp, "
                         "pp_interleave...); tp/pp/sp shape the mesh")
    ap.add_argument("--schedule", action="store_true",
                    help="print the per-lane schedule occupancy report "
                         "(devprof.schedule_occupancy) from the capture; "
                         "with pp>1 in --cfg, also the hop-event pipeline "
                         "schedule measurement")
    args = ap.parse_args(argv)
    model_name = args.model
    trace_dir = os.environ.get("PROFILE_DIR",
                               f"/tmp/tpu_profile_{model_name}")

    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import importlib
    from theanompi_tpu.models.registry import MODELS
    from theanompi_tpu.parallel.exchanger import get_exchanger
    from theanompi_tpu.parallel.mesh import WORKER_AXIS, worker_mesh
    from theanompi_tpu.parallel import steps
    from theanompi_tpu.utils import devprof, telemetry

    jax.config.update("jax_default_prng_impl", "rbg")
    overrides = json.loads(args.cfg) if args.cfg else {}
    mesh = worker_mesh(tp=int(overrides.get("tp", 1)),
                       pp=int(overrides.get("pp", 1)),
                       sp=int(overrides.get("sp", 1)))
    modelfile, modelclass, extra = MODELS[model_name]
    config = {"mesh": mesh, "size": mesh.shape[WORKER_AXIS], "rank": 0,
              "verbose": False, **extra, **overrides}
    if args.batch:
        config["batch_size"] = args.batch
    if args.spc > 1:
        config["steps_per_call"] = args.spc
    model = getattr(importlib.import_module(modelfile), modelclass)(config)
    exchanger = get_exchanger(args.rule, config)
    model.compile_iter_fns(exchanger)
    spc = int(config.get("steps_per_call", 1))
    if spc > 1:
        batches = [model.data.next_train_batch(j) for j in range(spc)]
        dev_batch = steps.put_batch_stack(mesh, batches, model.batch_spec())
    else:
        dev_batch = steps.put_batch(mesh, model.data.next_train_batch(0),
                                    model.batch_spec())
    lr = jnp.float32(model.current_lr)
    rng = jax.random.key(0)

    def step(i):
        # 1-based count strided by spc, exactly the worker/bench
        # convention: the fused in-scan exchange cadence fires at its true
        # rate (a 0-based count would run steps down to count0 < 0 and
        # fire a step-0 exchange no real run issues)
        with telemetry.span(devprof.TRAIN_DISPATCH_SPAN):   # counted by devprof
            model.step_state, cost, err = model.train_fn(
                model.step_state, dev_batch, lr, rng,
                jnp.int32((i + 1) * spc))

    for i in range(5):
        step(i)
    jax.block_until_ready(model.step_state["params"])

    with devprof.capture(trace_dir) as cap:
        for i in range(args.iters):
            step(5 + i)
        jax.block_until_ready(model.step_state["params"])
    prof = cap.profile
    if prof is None:
        print(f"no trace capture found under {trace_dir}", file=sys.stderr)
        return 1

    print(f"== {model_name} batch {model.batch_size} {args.rule.upper()}"
          f"{f' spc={spc}' if spc > 1 else ''}: {args.iters} traced "
          f"dispatch(es) on {jax.devices()[0].platform} ==")
    print(devprof.format_profile(prof, top=25))
    if args.schedule:
        # per-lane tick-level occupancy (compute / hop / other-comm /
        # idle strips) — a schedule regression is diagnosable per lane,
        # not just a worse scalar
        events = devprof.load_dir_events(trace_dir)
        print()
        print(devprof.format_schedule(devprof.schedule_occupancy(events)))
        pp = int(config.get("pp", 1) or 1)
        if pp > 1:
            rep = devprof.pipeline_schedule_report(
                events, pp=pp,
                v=int(config.get("pp_interleave", 1) or 1),
                m=int(config.get("pp_microbatches", 1) or 1))
            print(f"pipeline schedule: ticks/pass={rep['ticks_per_pass']} "
                  f"measured_ticks={rep['measured_ticks']} "
                  f"verified={rep['schedule_verified']} "
                  f"bubble_ticks={rep['bubble_fraction_ticks']} "
                  f"bubble_time={rep['bubble_fraction']}")
    if args.json:
        doc = {"model": model_name, "batch_size": int(model.batch_size),
               "rule": args.rule, "spc": spc, "iters": args.iters,
               "platform": jax.devices()[0].platform,
               "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
               "trace_dir": trace_dir, **prof}
        if args.json == "-":
            print(json.dumps(doc))
        else:
            with open(args.json, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
