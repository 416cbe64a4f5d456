#!/usr/bin/env python
"""Predicted multi-chip scaling efficiency from measured 1-chip rows.

This environment exposes ONE real TPU chip (round-4 verdict: "do not ask
for real multi-chip runs; do ask for the comm-share-derived efficiency
prediction").  This script produces that prediction: for each staged
BASELINE.json config with a measured TPU t_train in the canonical perf
matrix, it models the per-step wire bytes of the config's exchange
strategy analytically (formulas below, from the strategy implementations
in ``theanompi_tpu/parallel/strategies.py`` / ``exchanger.py``), divides
by the TPU v5e ICI link bandwidth, and reports predicted scaling
efficiency at 8 and 32 chips under two bounds:

- ``eff_no_overlap``  = t_step / (t_step + t_comm)   (comm fully exposed)
- ``eff_full_overlap`` = t_step / max(t_step, t_comm) (comm fully hidden)

The truth lands between the bounds; XLA overlaps collectives with
independent compute inside the jitted step, so well-fused configs sit
near the full-overlap bound.  The reference's own headline table
(SURVEY.md §6: time-per-5120-images vs worker count) is the shape this
mirrors.

Each row also reports the ``monolithic`` prediction: one collective's
setup latency ``LAT`` (:data:`COLL_LATENCY_S`) plus ``wire_bytes / BW``,
none of it hidden, and ``pred_exposed_comm_secs`` beside the measured
``exposed_comm_secs`` trace column where a matrix row has one.

Wire-bytes-per-step models (P = param count, b = wire bytes/elem,
N = chips; ring collectives over a 1D ICI ring, per-chip bytes):
- allreduce/ring (BSP fused grads):  2 * (N-1)/N * P * b
- bf16 wire (nccl16/asa16):          same with b=2
- EASGD (sync_freq=f):               2 * (N-1)/N * P * b / f
- ASGD  (sync_freq=f, default 1):    same formula
- GoSGD (exch_prob=p):               p * P * b   (expected send per step)
- topk (ratio=r):                    (N-1) * r * P * 8   (allgather of
                                     (idx,val) pairs from every worker)
- onebit:                            2 * (N-1)/N * P/8  (packed signs)
- powersgd rank r:                   2 * (N-1)/N * (r * sum(rows+cols)
                                     + dense) * 4, where rows/cols follow
                                     PowerSGD's OWN factorization of each
                                     leaf — [prod(shape[:-1]), shape[-1]]
                                     — summed over the leaves its
                                     _compressible gate accepts, and
                                     ``dense`` counts the elements of the
                                     leaves it sends as a plain psum
                                     (round-5 review: the old
                                     shape[0]+size//shape[0] estimate
                                     overstated vgg16 wire bytes ~60×)

ICI bandwidth: TPU v5e has 4 ICI links/chip at ~45 GB/s per direction
(public "How to Scale Your Model" figures); a bidirectional ring uses
two directions -> BW = 90 GB/s effective, with a 2x sensitivity band
reported (45/180) since the achieved fraction depends on topology and
XLA's collective scheduling.

Usage: python scripts/predict_scaling.py [matrix.jsonl ...]
Writes one JSON object to stdout (the watcher redirects it to
scaling_prediction_r5.json) and a human table to stderr.
"""

import glob
import json
import os
import re
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ICI_GBPS = 90e9          # bidirectional 1D-ring effective, v5e (see above)
SENS = (45e9, 180e9)     # sensitivity band
CHIP_COUNTS = (8, 32)

# per-collective setup latency (dispatch + ICI rendezvous — order of the
# ~µs published for small TPU collectives; the 2x band on BW dwarfs its
# uncertainty)
COLL_LATENCY_S = 5e-6


def monolithic_exchange(wire_b: float, t_step: float) -> dict:
    """Exposed-comm prediction for one monolithic exchange: ``wire_b``
    (what actually crosses ICI) behind one collective's setup latency,
    none of it hidden behind compute."""
    t_comm = COLL_LATENCY_S + wire_b / ICI_GBPS
    return {"t_comm_s": round(t_comm, 6),
            "pred_exposed_comm_secs": round(t_comm, 6),
            "eff": round(t_step / (t_step + t_comm), 4)}


def pipeline_bubble(pp: int, v: int, m: int, t_chunk: float = 1.0,
                    t_hop: float = 0.0) -> dict:
    """Pipeline-schedule bubble model (round 10, ISSUE 16).

    The fill/drain GPipe scan idles ``pp−1`` warm-up/drain ticks of an
    ``m + pp − 1``-tick schedule; interleaving ``v`` virtual stages per
    device (``parallel/pipeline.py`` schedule table) keeps each device's
    useful work at ``v·m`` chunk-ticks but each tick is a ``1/v``-depth
    chunk, so the same ``pp−1`` idle ticks sit in a ``v·m + pp − 1``-tick
    schedule — the bubble shrinks by ~``v``.  With per-tick costs:

        busy  = v·m·t_chunk                (useful compute per device)
        span  = (v·m + pp − 1)·(t_chunk + t_hop)
        bubble_fraction = 1 − busy/span

    ``t_hop`` is the per-tick activation-hop cost the schedule pays
    ``v·m + pp − 1`` times instead of ``m + pp − 1`` — the price of
    interleaving, zero when the async hop fully overlaps chunk compute
    (jax_compat.ppermute_start/done under the fused scan).  At
    ``t_hop = 0`` this reduces to the classic ``(pp−1)/(v·m + pp−1)``,
    which is exactly what the measured ``pipeline_bubble_ticks`` column
    (devprof.pipeline_schedule_report) reports when the capture's hop
    count verifies the tick structure."""
    ticks = v * m + pp - 1
    busy = v * m * t_chunk
    span = ticks * (t_chunk + t_hop)
    return {"pp": pp, "v": v, "m": m, "ticks": ticks,
            "warmup_ticks": pp - 1,
            "bubble_fraction": round(1.0 - busy / span, 4)}


# staged r10 pipeline rows -> (matrix label, pp, v, M);
# t_chunk/t_hop default to the uniform-tick model — the measured join
# below reports both the tick-count and wall-time measured bubbles next
# to the prediction
PIPELINE_CONFIGS = [
    ("transformer_lm-b16-pp4-trace",    4, 1, 8),
    ("transformer_lm-b16-pp4-v2-trace", 4, 2, 8),
    ("transformer_lm-b16-pp4-v4-trace", 4, 4, 8),
]


def update_state_bytes_per_chip(replicated_bytes: float, n: int) -> float:
    """Update-plane-sharding memory model (round 11, ISSUE 17): the
    leaf-wise wrapper (``parallel/update_sharding.py``) chunks every
    planned leaf to ``ceil(L/N)`` elements per chip, so per-chip
    update-state bytes are ``replicated/N`` to first order.  The measured
    ``update_state_bytes_per_chip`` (devprof.USHARD_ROW_COLUMNS) sits
    slightly ABOVE this: ceil rounding pads each ragged leaf by at most
    ``N−1`` elements, and sub-threshold leaves (< ``ushard_min_bytes`` or
    < N elements) stay fully replicated per chip."""
    return replicated_bytes / n


# staged r11 update-sharding rows: sharded row joined against its
# replicated control (which carries the same report columns)
# -> (ushard label, control label, N)
USHARD_CONFIGS = [
    ("transformer_lm-b8-n2-ushard", "transformer_lm-b8-n2", 2),
    ("transformer_lm-b8-n4-ushard", "transformer_lm-b8-n4", 4),
]


# staged r12 fused-compression rows: fuse row (Pallas
# kernel pipeline) joined against its forced-oracle control ->
# (fuse label, control label, strategy)
COMPRESS_CONFIGS = [
    ("transformer_lm-b8-onebit-n2-fuse",
     "transformer_lm-b8-onebit-n2", "onebit"),
    ("transformer_lm-b8-topk-n2-fuse",
     "transformer_lm-b8-topk-n2", "topk"),
    ("transformer_lm-b8-powersgd2-n2-fuse",
     "transformer_lm-b8-powersgd2-n2", "powersgd2"),
]


# staged configs (BASELINE.json) -> (matrix row, strategy model, params key)
CONFIGS = [
    ("alexnet-b128",      "allreduce", 4, "alexnet", 128),
    ("googlenet-b32",     "allreduce", 4, "googlenet", 32),
    ("vgg16-b32",         "allreduce", 4, "vgg16", 32),
    ("resnet50-b32",      "allreduce", 4, "resnet50", 32),
    ("cifar10-b128",      "allreduce", 4, "cifar10", 128),
    ("vgg16-b32-easgd",   "easgd",     4, "vgg16", 32),
    ("resnet50-b32-gosgd", "gosgd",    4, "resnet50", 32),
    ("vgg16-b32-topk",    "topk",      4, "vgg16", 32),
    ("vgg16-b32-onebit",  "onebit",    4, "vgg16", 32),
    ("vgg16-b32-powersgd4", "powersgd4", 4, "vgg16", 32),
]

_COUNT_SRC = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")   # counting needs no chip
import importlib
import numpy as np
from theanompi_tpu.models.registry import MODELS
from theanompi_tpu.parallel.strategies import PowerSGD
ps = PowerSGD(4)     # the staged powersgd4 config's rank gates the
                     # compressible set; lower ranks compress a superset
out = {}
for name in sys.argv[1:]:
    modelfile, modelclass, extra = MODELS[name]
    cfg = {"size": 1, "rank": 0, "verbose": False, **extra}
    m = getattr(importlib.import_module(modelfile), modelclass)(cfg)
    leaves = jax.tree.leaves(m.params)
    P = sum(int(l.size) for l in leaves)
    # PowerSGD's factorization of leaf M is [prod(shape[:-1]), shape[-1]]
    # (conv kernels fold every leading dim into rows); it ships
    # r*(rows+cols) per COMPRESSIBLE leaf and a plain dense psum for the
    # rest — mirror exactly that split here
    rc = sum(int(np.prod(np.shape(l)[:-1])) + int(np.shape(l)[-1])
             for l in leaves if ps._compressible(np.shape(l)))
    dense = sum(int(l.size)
                for l in leaves if not ps._compressible(np.shape(l)))
    out[name] = {"params": P, "rows_plus_cols": rc, "powersgd_dense": dense}
print(json.dumps(out))
"""


def _param_counts(models: list) -> dict:
    """Instantiate each model on the CPU backend in a SUBPROCESS (the
    child pins the CPU platform before any backend touch, so it never
    claims a chip the parent may hold) and cache the counts beside the
    repo."""
    cache = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "..", "model_param_counts.json")
    have = {}
    if os.path.exists(cache):
        with open(cache) as f:
            have = json.load(f)
    # powersgd_dense marks the corrected-schema entries (round-5 review);
    # entries cached under the old rows_plus_cols formula recount
    missing = [m for m in models
               if m not in have or "powersgd_dense" not in have[m]]
    if missing:
        r = subprocess.run([sys.executable, "-c", _COUNT_SRC] + missing,
                           capture_output=True, text=True, timeout=1200)
        if r.returncode != 0:
            sys.stderr.write(r.stderr[-2000:])
            raise RuntimeError("param-count subprocess failed")
        have.update(json.loads(r.stdout.strip().splitlines()[-1]))
        with open(cache, "w") as f:
            json.dump(have, f, indent=1, sort_keys=True)
    return have


def wire_bytes(strategy: str, P: int, rows_plus_cols: int, n: int,
               powersgd_dense: int = 0) -> float:
    ring = 2.0 * (n - 1) / n
    if strategy == "allreduce":
        return ring * P * 4
    if strategy == "easgd":
        return ring * P * 4 / 4            # sync_freq default 4
    if strategy == "asgd":
        return ring * P * 4
    if strategy == "gosgd":
        return 0.25 * P * 4                # exch_prob default
    if strategy == "topk":
        return (n - 1) * 0.01 * P * 8      # ratio default, (idx,val)
    if strategy == "onebit":
        return ring * P / 8
    if strategy.startswith("powersgd"):
        r = int(strategy[len("powersgd"):] or 2)
        # low-rank factors for the compressible leaves + full fp32
        # allreduce for the leaves PowerSGD leaves dense
        return ring * (r * rows_plus_cols + powersgd_dense) * 4
    raise ValueError(strategy)


def newest_matrix(paths: list) -> dict:
    """config -> result dict from ``perf_matrix_rN.jsonl`` files, newest
    round wins; rows noted as degraded readings are excluded."""
    def _matrix_round(path: str) -> int:
        m = re.search(r"_r(\d+)", os.path.basename(path))
        return int(m.group(1)) if m else -1

    def _is_degraded(row: dict) -> bool:
        res = row.get("result")
        blob = str(row.get("note", "")) + str(
            res.get("metric", "") if isinstance(res, dict) else "")
        return "degraded" in blob.lower()

    rows: dict = {}
    for path in sorted(paths, key=_matrix_round):
        for line in open(path):
            try:
                row = json.loads(line)
            except ValueError:
                continue
            res = row.get("result")
            if not isinstance(res, dict) or _is_degraded(row):
                continue
            rows[row.get("config", "")] = res
    return rows


def main() -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = sys.argv[1:] or sorted(
        glob.glob(os.path.join(repo, "perf_matrix_*.jsonl")))
    measured = newest_matrix(paths)
    counts = _param_counts(sorted({c[3] for c in CONFIGS}))

    out = {"ici_bw_bytes_per_s": ICI_GBPS, "sensitivity_band": SENS,
           "method": "analytic wire-bytes / ICI-bw anchored to measured "
                     "1-chip t_step; see scripts/predict_scaling.py "
                     "docstring for formulas and bounds", "rows": []}
    hdr = (f"{'config':24} {'ips/chip':>9} {'t_step ms':>9} "
           + "".join(f"{'eff@' + str(n) + ' (no/full ovl)':>22}"
                     for n in CHIP_COUNTS))
    print(hdr, file=sys.stderr)
    for cfg, strat, b, model, batch in CONFIGS:
        res = measured.get(cfg)
        row = {"config": cfg, "strategy": strat, "model": model}
        if not res or "spc" in str(res.get("metric", "")):
            row["measured"] = None
            out["rows"].append(row)
            print(f"{cfg:24} {'--':>9}  (no healthy spc=1 TPU row yet)",
                  file=sys.stderr)
            continue
        ips = float(res["value"])
        t_step = batch / ips
        P = counts[model]["params"]
        rc = counts[model]["rows_plus_cols"]
        dense = counts[model].get("powersgd_dense", 0)
        row.update(measured_ips_per_chip=ips, t_step_s=round(t_step, 6),
                   params=P)
        # measured overlap evidence (trace columns) when the r9
        # matrix row exists — the prediction-vs-trace comparison per row
        m_res = measured.get(cfg + "-trace") or res
        if m_res.get("exposed_comm_secs") is not None:
            row["measured_monolithic"] = {
                "exposed_comm_secs": m_res["exposed_comm_secs"],
                "overlap_ratio": m_res.get("overlap_ratio")}
        cells = ""
        for n in CHIP_COUNTS:
            wb = wire_bytes(strat, P, rc, n, dense)
            t_comm = wb / ICI_GBPS
            no_ovl = t_step / (t_step + t_comm)
            full_ovl = t_step / max(t_step, t_comm)
            row[f"pred_{n}chip"] = {
                "t_comm_s": round(t_comm, 6),
                "eff_no_overlap": round(no_ovl, 4),
                "eff_full_overlap": round(full_ovl, 4),
                "eff_band_low": round(t_step / (t_step + wb / SENS[0]), 4),
                "eff_band_high": round(t_step / (t_step + wb / SENS[1]), 4),
                "monolithic": monolithic_exchange(wb, t_step)}
            cells += f"{no_ovl:>11.3f}/{full_ovl:<10.3f}"
        out["rows"].append(row)
        print(f"{cfg:24} {ips:>9.0f} {t_step * 1e3:>9.2f} {cells}",
              file=sys.stderr)
    # pipeline-schedule rows (round 10): predicted bubble vs the measured
    # devprof columns of the r10 matrix rows — same predicted-vs-measured
    # join the r9 trace rows get above
    out["pipeline_rows"] = []
    print(f"\n{'pipeline row':34} {'pred bubble':>11} {'meas ticks':>10} "
          f"{'meas time':>9} {'verified':>8}", file=sys.stderr)
    for label, pp, v, m in PIPELINE_CONFIGS:
        pred = pipeline_bubble(pp, v, m)
        prow = {"config": label, "predicted": pred, "measured": None}
        res = measured.get(label)
        if res and res.get("pipeline_bubble_ticks") is not None:
            prow["measured"] = {
                k: res.get(k)
                for k in ("pipeline_bubble_ticks", "pipeline_bubble_time",
                          "pipeline_schedule_verified", "bubble_fraction")}
            mt = res["pipeline_bubble_ticks"]
            pb = pred["bubble_fraction"]
            prow["rel_err_ticks"] = (round(abs(mt - pb) / pb, 4)
                                     if pb else None)
            print(f"{label:34} {pb:>11.4f} {mt:>10.4f} "
                  f"{res.get('pipeline_bubble_time') or float('nan'):>9.4f} "
                  f"{str(res.get('pipeline_schedule_verified')):>8}",
                  file=sys.stderr)
        else:
            print(f"{label:34} {pred['bubble_fraction']:>11.4f} "
                  f"{'--':>10}  (no measured r10 row yet)", file=sys.stderr)
        out["pipeline_rows"].append(prow)
    # update-plane-sharding rows (round 11): predicted per-chip update
    # -state bytes (replicated/N, model above) vs the measured devprof
    # columns of the r11 matrix rows — the control row prices the
    # replicated baseline, the ushard row the sharded layout
    out["update_state_rows"] = []
    print(f"\n{'update-sharding row':30} {'pred B/chip':>11} "
          f"{'meas B/chip':>11} {'shrink':>7} {'rel err':>8}",
          file=sys.stderr)
    for label, control, n in USHARD_CONFIGS:
        res, ctl = measured.get(label), measured.get(control)
        urow = {"config": label, "control": control, "n_workers": n,
                "measured": None}
        repl = (res or {}).get("update_state_bytes_replicated") \
            or (ctl or {}).get("update_state_bytes_replicated")
        if repl:
            urow["predicted_bytes_per_chip"] = int(
                update_state_bytes_per_chip(repl, n))
            urow["predicted_shrink"] = float(n)
        if res and res.get("update_state_bytes_per_chip") is not None:
            meas = res["update_state_bytes_per_chip"]
            urow["measured"] = {
                k: res.get(k)
                for k in ("update_state_bytes_per_chip",
                          "update_state_bytes_replicated",
                          "update_state_shrink")}
            if ctl and ctl.get("update_state_bytes_per_chip") is not None:
                urow["control_bytes_per_chip"] = \
                    ctl["update_state_bytes_per_chip"]
            if repl:
                pred = urow["predicted_bytes_per_chip"]
                urow["rel_err"] = (round(abs(meas - pred) / pred, 4)
                                   if pred else None)
                print(f"{label:30} {pred:>11} {meas:>11} "
                      f"{res.get('update_state_shrink') or 0:>7.2f} "
                      f"{urow['rel_err']:>8.4f}", file=sys.stderr)
        else:
            print(f"{label:30} "
                  f"{urow.get('predicted_bytes_per_chip', '--'):>11} "
                  f"{'--':>11}  (no measured r11 row yet)", file=sys.stderr)
        out["update_state_rows"].append(urow)
    # fused-compression rows (round 12): the analytic HBM-traffic model
    # (devprof.compress_traffic_model — the same model whose columns the
    # r12 rows carry, evaluated here at a nominal size: the legacy/fused
    # ratio is a ratio of linear-in-n terms, so it is size-invariant for
    # onebit/topk and shape-ratio-driven for powersgd) joined against the
    # measured fuse/control step-time pair.  The modeled shrink bounds the
    # kernel win; a measured speedup below it means the exchange was not
    # HBM-bound at this problem size, not that the kernels lost.
    # Imported lazily AND fail-soft: the r5 watcher rehearsal runs this
    # script from a bare scratch tree where the package is absent — the
    # compress join is additive reporting, never a reason to crash the
    # prediction chain.
    try:
        from theanompi_tpu.utils.devprof import compress_traffic_model
    except ImportError:
        compress_traffic_model = None
        print("\n(compress rows skipped: theanompi_tpu not importable)",
              file=sys.stderr)
    out["compress_rows"] = []
    if compress_traffic_model is not None:
        print(f"\n{'compress row':34} {'pred shrink':>11} {'pred dec':>8} "
              f"{'row shrink':>10} {'fuse/ctl':>9}", file=sys.stderr)
    for label, control, strat in COMPRESS_CONFIGS:
        if compress_traffic_model is None:
            break
        pred = compress_traffic_model(
            strat.rstrip("0123456789"), 1 << 22, 2,
            leaf_shapes=[(512, 256)] if strat.startswith("powersgd")
            else None)
        crow = {"config": label, "control": control, "strategy": strat,
                "predicted": {k: pred[k] for k in
                              ("compress_hbm_shrink",
                               "compress_decode_shrink")} if pred else None,
                "measured": None}
        res, ctl = measured.get(label), measured.get(control)
        rep = next((r for r in (res, ctl)
                    if r and r.get("compress_hbm_shrink") is not None), None)
        if rep:
            crow["measured"] = {
                k: rep.get(k)
                for k in ("compress_hbm_bytes_legacy",
                          "compress_hbm_bytes_fused", "compress_hbm_shrink",
                          "compress_decode_shrink")}
        if res and ctl and res.get("value") and ctl.get("value"):
            crow["step_speedup"] = round(res["value"] / ctl["value"], 3)
        if crow["measured"] is not None:
            ps = (pred or {}).get("compress_hbm_shrink") or 0
            print(f"{label:34} {ps:>11.3f} "
                  f"{(pred or {}).get('compress_decode_shrink') or 0:>8.3f} "
                  f"{crow['measured']['compress_hbm_shrink'] or 0:>10.3f} "
                  f"{crow.get('step_speedup') or float('nan'):>9.3f}",
                  file=sys.stderr)
        else:
            print(f"{label:34} "
                  f"{(pred or {}).get('compress_hbm_shrink', '--'):>11} "
                  f"{'--':>8}  (no measured r12 pair yet)", file=sys.stderr)
        out["compress_rows"].append(crow)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
