#!/usr/bin/env python3
"""Readings behind a cell's limits: the numbers ``correct`` compares, over
many seeds in one process, of the cell as it is or of its control.

    python3 benchmarks/readings.py --workload <name> --seeds 12 \\
        [--first-seed 2147483700] [--seconds 1] \\
        [--control compute_dtype=float8_e4m3fn] [--manifest <file>]

One JSON line a seed on standard output: the seed, the platform, `correct`,
every number compared beside its limit, and what the check reported beside
them.  ``--control key=value`` lays worker configuration over the cell's
(the precision below the configuration's, or ``modelfile=``/``modelclass=``
of a model with a fault planted): the run is then the control, which has to
come out not correct.  The benchmark's own runs never call this; it is how
the limits in ``reference/check.py`` were read, and how the PR that brings
a cell reads its own (PERF.md section 4).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147483700)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", action="append", default=[],
                    metavar="KEY=VALUE")
    ap.add_argument("--manifest", default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmarks import harness

    manifest = harness.load_manifest(args.manifest or harness.MANIFEST)
    control = dict(kv.split("=", 1) for kv in args.control)
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        run = harness.run_cell(manifest, args.workload, seed, args.seconds,
                               trace=False, control=control)
        extras = {k: v for k, v in run.reference.items()
                  if k in ("grad_norm_leaf", "change_norm_leaf",
                           "grad_rel_err", "grad_rel_leaf", "ref_losses",
                           "sys_losses", "leaves_nought")}
        print(json.dumps({"seed": seed, "platform": run.device["platform"],
                          "control": control, "correct": run.correct,
                          "compared": run.compared, **extras,
                          "after_window_s": run.after_window_s}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
