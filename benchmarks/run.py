#!/usr/bin/env python3
"""The benchmark's command: one cell, one run, one JSON object on the last
line of standard output.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It runs on the machine it is started on and fails without a TPU, naming the
platform it found: there is no CPU fallback and no switch for one (the tests
rehearse ``benchmarks.harness`` on the CPU mesh directly).
"""

import time

T_PROCESS_START = time.time()   # before the heavy imports: they are set-up

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    # what runs before the harness, phase by phase (harness.OUTSIDE_SETUP
    # says which of them `setup_s` leaves out, and why)
    early, last = {}, [T_PROCESS_START]

    def phase(name):
        now = time.time()
        early[name] = now - last[0]
        last[0] = now

    try:
        import jax
        phase("import_jax")

        import theanompi_tpu  # noqa: F401  (the system under test)
        from benchmarks import harness
        phase("import_program")
    except ImportError as e:
        print(f"benchmarks/run.py: cannot import the system under test "
              f"({e}); run from a checkout of the repository",
              file=sys.stderr)
        return 2
    platform = jax.devices()[0].platform    # starts the chip's runtime
    phase("runtime_start")
    if platform != "tpu":
        print(f"benchmarks/run.py: JAX found platform {platform!r}, not a "
              f"TPU; the benchmark measures on the chip only",
              file=sys.stderr)
        return 2
    manifest = harness.load_manifest()
    try:
        run = harness.run_cell(manifest, args.workload, args.seed,
                               args.seconds, bool(args.trace),
                               t_process_start=T_PROCESS_START,
                               early_phases=early)
    except harness.Refused as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    line = harness.result_line(manifest, run, bool(args.trace))
    # for whoever reads the log: where set-up went, and why not `correct`
    print(json.dumps({"setup_phases": run.setup_phases,
                      "reference": run.reference,
                      "first_cost": run.first_cost,
                      "window": vars(run.window),
                      "traced": run.traced and vars(run.traced),
                      "after_window_s": run.after_window_s,
                      "problems": run.problems}), file=sys.stderr)
    # every number `correct` compared beside its limit: the last lines here
    for name, (value, limit) in run.compared.items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
