#!/usr/bin/env python
"""Pre-build the staged rows' executables into the AOT store — off-line.

The heavy row programs COMPILE for v5e on a 1-vCPU host with no chip
(26–270 s each, round 5).  This serializes the compiled executables through
``theanompi_tpu/utils/compile_cache.py`` — the opt-in content-addressed
store ``model_base.compile_iter_fns`` reads when ``compile_cache`` /
``THEANOMPI_COMPILE_CACHE`` names it — under a key we control.
Drift-proofing: rows come from ``scripts/rows.py`` and each row's config is
assembled by ``bench.bench_row_config`` (the same env→config path
``bench.main`` runs), so the prewarmed program is byte-identical to the one
the measurement will request.  Whether a prewarmed entry was ever hit by a
chip run has not been shown (ROADMAP D2).

Two venues:

* ``--platform cpu`` / ``tpu`` (live backend): builds the model and runs
  ``compile_iter_fns`` with the cache configured — train, val, AND the
  standalone exchange collective all land in the store.  This is also the
  CPU proof path the tests drive.
* ``--platform topology:v5e:2x2x1`` (off-line AOT, no chip needed):
  lowers the train program against a topology mesh with abstract
  state avals (no device placement — topology devices are not
  addressable) and compiles/serializes it.  Already-cached rows are
  skipped from the entry itself (the store IS the done-marker).

    python -u scripts/prewarm_cache.py --rows heavy --cache DIR \
        --platform topology:v5e:2x2x1

A per-row failure prints and skips to the next; a mismatched row only
wastes its cache entry.
"""

from __future__ import annotations

import argparse
import faulthandler
import os
import sys
import time

os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
faulthandler.enable()
faulthandler.dump_traceback_later(600, repeat=True, file=sys.stderr)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rows", default="heavy", metavar="SEL",
                   help="row selector for scripts/rows.py: group tag "
                        "(heavy/r7/r8), 'all', or label[,label...] "
                        "(default: heavy — the long compiles)")
    p.add_argument("--cache", default=None, metavar="DIR",
                   help="executable store dir (default: "
                        "$THEANOMPI_COMPILE_CACHE — the variable the "
                        "measuring run reads)")
    p.add_argument("--platform", default="cpu",
                   help="'cpu'/'tpu' (live backend via compile_iter_fns) "
                        "or 'topology:<name>' e.g. topology:v5e:2x2x1 "
                        "(off-line AOT against a device topology)")
    p.add_argument("--spc1-flops", action="store_true", default=True,
                   help="also prewarm the spc=1 sibling of every spc>1 row "
                        "(bench.py's MFU flop-count program) [default]")
    p.add_argument("--no-spc1-flops", dest="spc1_flops",
                   action="store_false")
    return p.parse_args(argv)


def _configure_jax(prng: str, force_cpu: bool):
    import jax
    if force_cpu:
        # host-side work (param init, synthetic batches) runs on the CPU
        # backend: the off-line venues never claim a chip
        jax.config.update("jax_platforms", "cpu")
    from theanompi_tpu.base import canonical_prng_impl
    impl = canonical_prng_impl(prng)
    if impl:
        jax.config.update("jax_default_prng_impl", impl)
    return jax


def _row_environ(row) -> dict:
    """The env ``bench.main`` will ACTUALLY see for this row: ambient
    BENCH_* exports overlaid by the row's own settings — the semantics of
    ``env K=V ... python bench.py``.  Keying from
    ``row.env`` alone would let any exported BENCH_* (a forgotten
    BENCH_BATCH, a BENCH_BN_DTYPE from an earlier experiment) silently
    re-key every measured program and forfeit every prewarm hit."""
    env = {k: v for k, v in os.environ.items() if k.startswith("BENCH_")}
    env.update(row.env)
    return env


def prewarm_live(row, cache_dir: str, spc1_flops: bool) -> str:
    """Live-backend prewarm: compile_iter_fns with the cache configured —
    exactly what the worker/bench will run, so the hit is tautological."""
    import importlib
    from bench import bench_model_config, bench_row_config, bench_row_mesh
    from theanompi_tpu.models.registry import MODELS
    from theanompi_tpu.parallel.exchanger import get_exchanger
    from theanompi_tpu.utils import compile_cache as cc

    model_name, rule, row_cfg, flags = bench_row_config(_row_environ(row))
    if flags["real_data"]:
        return f"{row.label}: SKIP (realdata rows need the on-disk " \
               f"dataset; the program equals its synthetic sibling)"
    modelfile, modelclass, extra = MODELS[model_name]
    mesh = bench_row_mesh(row_cfg)
    config = bench_model_config(mesh, extra, row_cfg,
                                compile_cache=cache_dir)
    model = getattr(importlib.import_module(modelfile), modelclass)(config)
    exchanger = get_exchanger(rule, config)
    t0 = time.time()
    model.compile_iter_fns(exchanger)
    parts = {k: v.get("cache") for k, v in model.compile_info.items()
             if isinstance(v, dict) and "cache" in v}
    spc = int(model.steps_per_call)
    if spc1_flops and spc > 1:
        # bench.py's spc>1 rows AOT-compile the spc=1 program purely for
        # its flop count — prewarm it through the ONE shared composition
        # (model_base.aot_train_program, the same call bench makes)
        _, info1 = model.aot_train_program(cc.get(cache_dir), spc=1,
                                           exchanger=exchanger)
        parts["spc1_flops"] = info1["cache"]
    return f"{row.label}: {parts} in {time.time() - t0:.1f}s"


def prewarm_topology(row, cache_dir: str, topo_name: str,
                     spc1_flops: bool) -> str:
    """Off-line AOT prewarm: lower against a topology mesh with abstract
    state avals and serialize the compiled executable.  No device
    placement anywhere (topology devices are not addressable)."""
    import importlib
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh
    from bench import bench_model_config, bench_row_config
    from theanompi_tpu.models.registry import MODELS
    from theanompi_tpu.parallel.exchanger import get_exchanger
    from theanompi_tpu.parallel.mesh import WORKER_AXIS
    from theanompi_tpu.utils import compile_cache as cc

    model_name, rule, row_cfg, flags = bench_row_config(_row_environ(row))
    if flags["real_data"]:
        return f"{row.label}: SKIP (realdata — program equals the " \
               f"synthetic sibling)"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topo_name)
    topo_mesh = Mesh(np.array(topo.devices[:1]), (WORKER_AXIS,))
    modelfile, modelclass, extra = MODELS[model_name]
    config = bench_model_config(topo_mesh, extra, row_cfg)
    model = getattr(importlib.import_module(modelfile), modelclass)(config)
    exchanger = get_exchanger(rule, config)
    exchanger.prepare(topo_mesh, model)
    cache = cc.get(cache_dir)
    out = {}
    for spc in sorted({int(model.steps_per_call)} |
                      ({1} if spc1_flops else set())):
        # load=False: nothing to load an executable INTO in this venue —
        # a present entry is the done-marker and is left untouched
        _, info = model.aot_train_program(cache, spc=spc,
                                          exchanger=exchanger, load=False)
        out[f"spc{spc}"] = f"{info['cache']} ({info['compile_secs']:.1f}s)"
    return f"{row.label}: {out}"


def main(argv=None) -> int:
    args = parse_args(argv)
    cache_dir = args.cache or os.environ.get("THEANOMPI_COMPILE_CACHE")
    if not cache_dir:
        print("prewarm: no store named — pass --cache DIR or set "
              "THEANOMPI_COMPILE_CACHE", file=sys.stderr)
        return 2
    topo = None
    if args.platform.startswith("topology:"):
        topo = args.platform.split(":", 1)[1]
    jax = _configure_jax(
        prng=os.environ.get("BENCH_PRNG", "rbg"),
        force_cpu=(topo is not None or args.platform == "cpu"))
    if topo is None and args.platform == "tpu" \
            and jax.devices()[0].platform != "tpu":
        # exiting 0 here would store useless cpu-keyed entries (bench.py
        # refuses the same substitution for the same reason)
        print(f"prewarm: requested platform tpu but backend is "
              f"{jax.devices()[0].platform!r} — refusing", flush=True)
        return 1
    from scripts.rows import rows
    picked = rows(args.rows)
    print(f"prewarm: {len(picked)} row(s) -> {cache_dir} "
          f"(platform={args.platform})", flush=True)
    failed = 0
    for row in picked:
        try:
            if topo is not None:
                msg = prewarm_topology(row, cache_dir, topo,
                                       args.spc1_flops)
            else:
                msg = prewarm_live(row, cache_dir, args.spc1_flops)
            print(msg, flush=True)
        except Exception as e:
            failed += 1
            print(f"{row.label}: FAILED {type(e).__name__}: "
                  f"{str(e)[:300]}", flush=True)
    n = len([f for f in os.listdir(cache_dir)
             if f.endswith(".jexec")]) if os.path.isdir(cache_dir) else 0
    print(f"cache now holds {n} executable(s) in {cache_dir}", flush=True)
    # ANY failed row is a nonzero exit
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
