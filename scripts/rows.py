#!/usr/bin/env python
"""The row manifest: ONE definition of every staged bench config.

Each row is a label plus the ``BENCH_*`` settings that shape it.  A drift
between the program a row measures and the program prewarmed for it
silently forfeits the executable-cache hit (the program key is
content-addressed: a shape that merely LOOKS the same misses), so this
module is the single source both sides consume:

* ``python scripts/rows.py --round 8 --sh`` prints one ``label ENV=V ...``
  line per row, for ``env ENV=V ... python bench.py``;
* ``scripts/prewarm_cache.py`` builds each row's program through
  ``bench.bench_row_config(row.env)`` — the SAME env→config assembly
  ``bench.main`` uses — and compiles it into the executable cache.

Row labels read model[-bN][-rule][-strategy][-spcK][-realdata][-winload]
[-...].  None of these rows has run on a chip since round 3; ROADMAP S1
rebuilds the benchmark's cells from them.
"""

from __future__ import annotations

import argparse
import shlex
import sys
from typing import Dict, List, NamedTuple, Tuple


class Row(NamedTuple):
    label: str
    env: Dict[str, str]          # BENCH_* settings that shape the row
    rounds: Tuple[str, ...]      # matrix rounds / groups this row belongs to


def _r(label: str, rounds: str, **env) -> Row:
    return Row(label, {k: str(v) for k, v in env.items()},
               tuple(rounds.split()))


# "heavy" = the long compiles (26–270 s each measured in round 5) — the
# prewarm default.
ROWS: List[Row] = [
    # -- round-8 canary + acceptance rows (executable-cache proof) --------
    _r("cifar10-b128-spc4", "r8 heavy", BENCH_MODEL="cifar10", BENCH_SPC=4),
    _r("alexnet-b128-spc4", "r8 heavy", BENCH_MODEL="alexnet", BENCH_SPC=4),
    _r("alexnet-b128", "r8 heavy", BENCH_MODEL="alexnet"),
    _r("vgg16-b32", "r8 heavy", BENCH_MODEL="vgg16"),
    _r("resnet50-b32", "r8 heavy", BENCH_MODEL="resnet50"),
    _r("googlenet-b32", "r8 heavy", BENCH_MODEL="googlenet"),
    _r("cifar10-b128", "r8 heavy", BENCH_MODEL="cifar10"),
    # -- batch-headroom + dtype-lever rows (round-5 staging) -------------
    _r("alexnet-b256-spc4", "heavy", BENCH_MODEL="alexnet", BENCH_BATCH=256,
       BENCH_SPC=4),
    _r("alexnet-b256", "heavy", BENCH_MODEL="alexnet", BENCH_BATCH=256),
    _r("resnet50-b32-bnbf16", "heavy", BENCH_MODEL="resnet50",
       BENCH_BN_DTYPE="bfloat16"),
    _r("resnet50-b64", "heavy", BENCH_MODEL="resnet50", BENCH_BATCH=64),
    _r("resnet50-b128", "heavy", BENCH_MODEL="resnet50", BENCH_BATCH=128),
    _r("resnet50-b128-bnbf16", "heavy", BENCH_MODEL="resnet50",
       BENCH_BATCH=128, BENCH_BN_DTYPE="bfloat16"),
    _r("resnet50-b128-spc4", "heavy", BENCH_MODEL="resnet50",
       BENCH_BATCH=128, BENCH_SPC=4),
    _r("googlenet-b128", "heavy", BENCH_MODEL="googlenet", BENCH_BATCH=128),
    _r("googlenet-b128-spc4", "heavy", BENCH_MODEL="googlenet",
       BENCH_BATCH=128, BENCH_SPC=4),
    _r("vgg16-b64", "heavy", BENCH_MODEL="vgg16", BENCH_BATCH=64),
    _r("vgg16-b32-spc4", "heavy", BENCH_MODEL="vgg16", BENCH_SPC=4),
    # -- spc8 scan bodies: the biggest programs per model (round 5/6) ----
    _r("alexnet-b128-spc8", "heavy", BENCH_MODEL="alexnet", BENCH_SPC=8,
       BENCH_SYNTH_BATCHES=8),
    _r("googlenet-b32-spc8", "heavy", BENCH_MODEL="googlenet", BENCH_SPC=8,
       BENCH_SYNTH_BATCHES=8),
    _r("resnet50-b32-spc8", "heavy", BENCH_MODEL="resnet50", BENCH_SPC=8,
       BENCH_SYNTH_BATCHES=8),
    _r("resnet50-b32-spc8-bnbf16", "heavy", BENCH_MODEL="resnet50",
       BENCH_SPC=8, BENCH_SYNTH_BATCHES=8, BENCH_BN_DTYPE="bfloat16"),
    # -- round-6 fused-cadence rows --------------------------------------
    _r("alexnet-b128-easgd-spc8", "r8 heavy", BENCH_MODEL="alexnet",
       BENCH_RULE="easgd", BENCH_SPC=8, BENCH_SYNTH_BATCHES=8),
    _r("vgg16-b32-easgd-spc8", "r8 heavy", BENCH_MODEL="vgg16",
       BENCH_RULE="easgd", BENCH_SPC=8, BENCH_SYNTH_BATCHES=8),
    _r("alexnet-b128-gosgd-spc8", "heavy", BENCH_MODEL="alexnet",
       BENCH_RULE="gosgd", BENCH_SPC=8, BENCH_SYNTH_BATCHES=8),
    # -- round-7 window-staging rows (same programs as their plain-spc
    #    siblings — the executable cache dedups them by content) ---------
    _r("cifar10-b128-spc4-winload", "r7", BENCH_MODEL="cifar10",
       BENCH_SPC=4, BENCH_WINLOAD=1),
    _r("alexnet-b128-spc4-winload", "r7 r8", BENCH_MODEL="alexnet",
       BENCH_SPC=4, BENCH_WINLOAD=1),
    _r("vgg16-b32-easgd-spc8-winload", "r7 r8", BENCH_MODEL="vgg16",
       BENCH_RULE="easgd", BENCH_SPC=8, BENCH_WINLOAD=1,
       BENCH_SYNTH_BATCHES=8),
    _r("alexnet-b128-realdata-spc4-winload", "r7 r8", BENCH_MODEL="alexnet",
       BENCH_SPC=4, BENCH_REAL_DATA=1, BENCH_WINLOAD=1),
    # -- round-9 bucketed-overlap rows (ISSUE 13): every row captures a
    #    BENCH_TRACE window so overlap_ratio / exposed_comm_secs land in
    #    the row JSON, bucketed and monolithic-control alike — the
    #    acceptance comparison is read straight off the BENCH_TRACE
    #    columns at fixed model/rule/spc ----------------------------------
    _r("alexnet-b128-trace", "r9 heavy", BENCH_MODEL="alexnet",
       BENCH_TRACE=1),                           # monolithic BSP control
    _r("alexnet-b128-bucket4m-trace", "r9 heavy", BENCH_MODEL="alexnet",
       BENCH_BUCKET_BYTES=4194304, BENCH_TRACE=1),
    _r("vgg16-b32-onebit-trace", "r9 heavy", BENCH_MODEL="vgg16",
       BENCH_STRATEGY="onebit", BENCH_TRACE=1),  # compressed-wire control
    _r("vgg16-b32-onebit-bucket4m-trace", "r9 heavy", BENCH_MODEL="vgg16",
       BENCH_STRATEGY="onebit", BENCH_BUCKET_BYTES=4194304, BENCH_TRACE=1),
    _r("alexnet-b128-easgd-spc8-trace", "r9 heavy", BENCH_MODEL="alexnet",
       BENCH_RULE="easgd", BENCH_SPC=8, BENCH_SYNTH_BATCHES=8,
       BENCH_TRACE=1),                           # monolithic psum control
    _r("alexnet-b128-easgd-spc8-bucket4m-trace", "r9 heavy",
       BENCH_MODEL="alexnet", BENCH_RULE="easgd", BENCH_SPC=8,
       BENCH_SYNTH_BATCHES=8, BENCH_BUCKET_BYTES=4194304, BENCH_TRACE=1),
    # -- round-10 interleaved-pipeline rows (ISSUE 16): TransformerLM at
    #    depth on a pp=4 'pipe' mesh — fill/drain control vs v∈{2,4}
    #    interleaved virtual stages (pp_interleave), each row tracing so
    #    devprof's bubble_fraction lands in the row JSON next to
    #    predict_scaling's modeled bubble.  n_layer=16 divides pp·v for
    #    every staged v; M=8 microbatches (pp | M, the interleaved
    #    grouping requirement) ------------------------------------------
    _r("transformer_lm-b16-pp4-trace", "r10 heavy",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=16, BENCH_TRACE=1,
       BENCH_CFG='{"d_model":512,"n_head":8,"n_layer":16,"seq_len":512,'
                 '"vocab":32768,"synthetic_train":512,"pp":4,'
                 '"pp_microbatches":8}'),       # fill/drain control
    _r("transformer_lm-b16-pp4-v2-trace", "r10 heavy",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=16, BENCH_TRACE=1,
       BENCH_CFG='{"d_model":512,"n_head":8,"n_layer":16,"seq_len":512,'
                 '"vocab":32768,"synthetic_train":512,"pp":4,'
                 '"pp_microbatches":8,"pp_interleave":2}'),
    _r("transformer_lm-b16-pp4-v4-trace", "r10 heavy",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=16, BENCH_TRACE=1,
       BENCH_CFG='{"d_model":512,"n_head":8,"n_layer":16,"seq_len":512,'
                 '"vocab":32768,"synthetic_train":512,"pp":4,'
                 '"pp_microbatches":8,"pp_interleave":4}'),
    # -- round-11 update-plane-sharding rows (ISSUE 17): TransformerLM on
    #    pure data meshes at N∈{2,4} — replicated control vs leaf-wise
    #    sharded update plane (BENCH_USHARD).  Every row carries the
    #    devprof.USHARD_ROW_COLUMNS memory report (controls via
    #    BENCH_USHARD_REPORT=1, shrink ~1.0) so the headline per-chip
    #    ~N× shrink is read row-vs-row at fixed model/batch/N, and
    #    scripts/predict_scaling.py --json joins the measured
    #    update_state_bytes_per_chip against its analytic model ---------
    _r("transformer_lm-b8-n2", "r11",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=8, BENCH_USHARD_REPORT=1,
       BENCH_CFG='{"d_model":256,"n_head":8,"n_layer":4,"seq_len":128,'
                 '"vocab":8192,"synthetic_train":64,"n_workers":2}'),
    _r("transformer_lm-b8-n2-ushard", "r11",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=8, BENCH_USHARD=1,
       BENCH_CFG='{"d_model":256,"n_head":8,"n_layer":4,"seq_len":128,'
                 '"vocab":8192,"synthetic_train":64,"n_workers":2}'),
    _r("transformer_lm-b8-n4", "r11",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=8, BENCH_USHARD_REPORT=1,
       BENCH_CFG='{"d_model":256,"n_head":8,"n_layer":4,"seq_len":128,'
                 '"vocab":8192,"synthetic_train":64,"n_workers":4}'),
    _r("transformer_lm-b8-n4-ushard", "r11",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=8, BENCH_USHARD=1,
       BENCH_CFG='{"d_model":256,"n_head":8,"n_layer":4,"seq_len":128,'
                 '"vocab":8192,"synthetic_train":64,"n_workers":4}'),
    # -- r12: fused compression kernels (ops/compress.py, ops/factor_pack.py,
    # docs/design.md §24).  Per compression strategy, a `fuse` row (Pallas
    # kernel pipeline, BENCH_FUSE=1) against a control row (jnp oracle path,
    # BENCH_FUSE=0 → THEANOMPI_TPU_NO_PALLAS=1) — identical wire bits, the
    # step-time delta is the kernels' HBM-traffic win.  On the CPU sim both
    # run the oracles (the rows pin wiring + the compress_traffic_report
    # columns); the A/B needs a chip run (ROADMAP S6).
    # scripts/predict_scaling.py joins these against the modeled shrink.
    _r("transformer_lm-b8-onebit-n2", "r12",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=8, BENCH_STRATEGY="onebit",
       BENCH_FUSE=0,
       BENCH_CFG='{"d_model":256,"n_head":8,"n_layer":4,"seq_len":128,'
                 '"vocab":8192,"synthetic_train":64,"n_workers":2}'),
    _r("transformer_lm-b8-onebit-n2-fuse", "r12",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=8, BENCH_STRATEGY="onebit",
       BENCH_FUSE=1,
       BENCH_CFG='{"d_model":256,"n_head":8,"n_layer":4,"seq_len":128,'
                 '"vocab":8192,"synthetic_train":64,"n_workers":2}'),
    _r("transformer_lm-b8-topk-n2", "r12",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=8, BENCH_STRATEGY="topk",
       BENCH_FUSE=0,
       BENCH_CFG='{"d_model":256,"n_head":8,"n_layer":4,"seq_len":128,'
                 '"vocab":8192,"synthetic_train":64,"n_workers":2}'),
    _r("transformer_lm-b8-topk-n2-fuse", "r12",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=8, BENCH_STRATEGY="topk",
       BENCH_FUSE=1,
       BENCH_CFG='{"d_model":256,"n_head":8,"n_layer":4,"seq_len":128,'
                 '"vocab":8192,"synthetic_train":64,"n_workers":2}'),
    _r("transformer_lm-b8-powersgd2-n2", "r12",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=8,
       BENCH_STRATEGY="powersgd2", BENCH_FUSE=0,
       BENCH_CFG='{"d_model":256,"n_head":8,"n_layer":4,"seq_len":128,'
                 '"vocab":8192,"synthetic_train":64,"n_workers":2}'),
    _r("transformer_lm-b8-powersgd2-n2-fuse", "r12",
       BENCH_MODEL="transformer_lm", BENCH_BATCH=8,
       BENCH_STRATEGY="powersgd2", BENCH_FUSE=1,
       BENCH_CFG='{"d_model":256,"n_head":8,"n_layer":4,"seq_len":128,'
                 '"vocab":8192,"synthetic_train":64,"n_workers":2}'),
]


def rows(selector: str = "all") -> List[Row]:
    """Rows for a selector: ``all``, a group/round tag (``r8``, ``heavy``),
    or a comma-separated list of exact labels."""
    if selector == "all":
        return list(ROWS)
    by_label = {r.label: r for r in ROWS}
    if "," in selector or selector in by_label:
        out = []
        for lab in selector.split(","):
            if lab not in by_label:
                raise SystemExit(f"rows.py: unknown row label {lab!r}")
            out.append(by_label[lab])
        return out
    picked = [r for r in ROWS if selector in r.rounds]
    if not picked:
        raise SystemExit(f"rows.py: selector {selector!r} matches nothing "
                         f"(groups: {sorted(set(sum((list(r.rounds) for r in ROWS), [])))})")
    return picked


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--round", default="all", metavar="SEL",
                   help="group tag (r7/r8/heavy), 'all', or label[,label...]")
    p.add_argument("--sh", action="store_true",
                   help="emit one shell line per row: label ENV=V ... "
                        "(for `env K=V ... python bench.py`)")
    p.add_argument("--labels", action="store_true",
                   help="emit labels only")
    args = p.parse_args(argv)
    for r in rows(args.round):
        if args.labels:
            print(r.label)
        elif args.sh:
            print(" ".join([shlex.quote(r.label)] +
                           [f"{k}={shlex.quote(v)}"
                            for k, v in sorted(r.env.items())]))
        else:
            print(f"{r.label:40s} {r.env}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
