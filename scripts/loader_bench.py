#!/usr/bin/env python
"""Host-side input-pipeline throughput — no accelerator required.

The reference's flagship was its parallel loader feeding real ``.hkl``
batches at AlexNet rates (SURVEY.md §2.8/§7: at 14k img/s that was ~1.1 GB/s
of augmented float32; the wire here is uint8, a quarter of that).  This
measures exactly that capability in isolation:
disk → ``.hkl`` read → native crop/mirror gather →
(optionally) the PrefetchLoader producer — images/sec and GB/s out of the
host pipeline, the ceiling it can feed a chip at.

    python scripts/loader_bench.py [--batches 32] [--batch-size 128]
                                   [--prefetch] [--workers N]

Writes one JSON line; nothing here touches a TPU: the numbers are the host
pipeline's own, not a device metric.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# nothing here needs an accelerator: pin the CPU backend up front so the
# script never claims a chip
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _ensure_dataset(n_batches: int, batch_size: int,
                    data_dir: str = None) -> str:
    """Generate (once) an on-disk batch-file dataset in the reference's
    .hkl layout; ~25 MB per 128-image file."""
    d = data_dir or f"/tmp/loader_bench_imagenet_{batch_size}x{n_batches}"
    # img_mean.npy is written LAST by make_batch_dataset.py — its presence
    # marks a complete dataset; a generation killed mid-write leaves
    # train_hkl/ without it, so wipe and redo
    if os.path.isdir(os.path.join(d, "train_hkl")) and \
            not os.path.exists(os.path.join(d, "img_mean.npy")):
        print(f"loader_bench: {d} is half-generated — regenerating",
              file=sys.stderr)
        shutil.rmtree(d)
    if not os.path.isdir(os.path.join(d, "train_hkl")):
        print(f"loader_bench: generating {n_batches}x{batch_size}-image "
              f"dataset at {d}", file=sys.stderr)
        subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "make_batch_dataset.py"),
             "--synthetic", str(n_batches), "--batch-size", str(batch_size),
             "--out", d],
            check=True, stdout=sys.stderr)
    return d


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batches", type=int, default=32)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--epochs", type=int, default=3,
                   help="timed passes over the shard set")
    p.add_argument("--prefetch", action="store_true",
                   help="pull through the PrefetchLoader producer thread")
    p.add_argument("--workers", type=int, default=1,
                   help="PrefetchLoader materializer pool size (implies "
                        "--prefetch when > 1)")
    p.add_argument("--windows", type=int, default=0, metavar="K",
                   help="window mode A/B (steps_per_call=K dispatch "
                        "inputs): staged-window DEQUEUE latency through "
                        "the PrefetchLoader window producer vs serial "
                        "consumer-side assembly (K draws + stack + put)")
    p.add_argument("--compute-ms", type=float, default=0.0,
                   help="simulated per-window compute between dequeues "
                        "(0 = use the measured serial assembly time, so "
                        "the producer gets the same overlap budget a real "
                        "training dispatch would give it)")
    p.add_argument("--data-dir", default=None)
    args = p.parse_args(argv)

    d = _ensure_dataset(args.batches, args.batch_size, args.data_dir)

    from theanompi_tpu.models.data.imagenet import ImageNet_data

    cfg = {"size": 1, "data_dir": d}
    if args.windows > 1:
        return _bench_windows(args, cfg)
    data = ImageNet_data(cfg, batch_size=args.batch_size)
    if args.prefetch or args.workers > 1:
        from theanompi_tpu.models.data.prefetch import PrefetchLoader
        data = PrefetchLoader(data, n_workers=args.workers)

    # warm the page cache + any lazy native-library build; epoch 0 then
    # CONTINUES from batch 1 (no re-shuffle — that would restart the
    # producer and regenerate the warmup batch inside the timed window)
    data.shuffle_data(0)
    b = data.next_train_batch(0)
    bytes_per_img = b["x"][0].nbytes
    n_imgs = 0
    t0 = time.time()
    for ep in range(args.epochs):
        if ep > 0:
            data.shuffle_data(ep)
        for i in range(1 if ep == 0 else 0, data.n_batch_train):
            batch = data.next_train_batch(i)
            n_imgs += batch["x"].shape[0]
    dt = time.time() - t0
    ips = n_imgs / dt
    out = {
        "metric": "host_loader_images_per_sec"
                  + (f" via PrefetchLoader x{args.workers}"
                     if (args.prefetch or args.workers > 1) else ""),
        "value": round(ips, 1),
        "unit": "images/sec",
        "gb_per_sec_out": round(ips * bytes_per_img / 1e9, 3),
        "images": n_imgs,
        "seconds": round(dt, 2),
        "note": "host pipeline only (disk->.hkl->augment): the rate it can "
                "feed a chip at (ROADMAP S7)",
    }
    print(json.dumps(out))
    return 0


def _bench_windows(args, cfg) -> int:
    """--windows K: the ISSUE-2 A/B in isolation — what does the CONSUMER
    thread pay per ``steps_per_call`` dispatch input?  Serial path: k draws
    + host stack + device_put on the consumer (the pre-window train_iter).
    Window path: the PrefetchLoader producer assembles+stages whole
    windows in the background and the consumer only DEQUEUES — with a
    compute-sized gap between dequeues (as training provides), the
    dequeue latency is the stall the chip actually sees."""
    import jax as _jax

    from theanompi_tpu.models.data.imagenet import ImageNet_data
    from theanompi_tpu.models.data.prefetch import PrefetchLoader
    from theanompi_tpu.parallel import steps
    from theanompi_tpu.parallel.mesh import worker_mesh

    k = args.windows
    mesh = worker_mesh(1)

    def block(w):
        _jax.block_until_ready(_jax.tree_util.tree_leaves(w)[0])

    serial = ImageNet_data(cfg, batch_size=args.batch_size)
    n_windows = serial.n_batch_train // k
    assert n_windows >= 2, (f"--windows {k} needs >= {2 * k} batches "
                            f"(have {serial.n_batch_train})")
    serial.shuffle_data(0)
    # warm: page cache, native-library build, first device_put
    block(steps.put_batch_stack(
        mesh, [serial.next_train_batch(j) for j in range(k)], None))
    t_serial = []
    for ep in range(args.epochs):
        serial.shuffle_data(ep + 1)
        for wi in range(n_windows):
            t1 = time.time()
            batches = [serial.next_train_batch(wi * k + j) for j in range(k)]
            block(steps.put_batch_stack(mesh, batches, None))
            t_serial.append(time.time() - t1)
    serial_ms = 1e3 * sum(t_serial) / len(t_serial)

    compute_s = (args.compute_ms / 1e3) if args.compute_ms > 0 \
        else serial_ms / 1e3
    data = PrefetchLoader(ImageNet_data(cfg, batch_size=args.batch_size),
                          n_workers=args.workers)
    data.set_window(k, lambda w: steps.stage_window(mesh, w, None))
    t_deq = []
    for ep in range(args.epochs):
        data.shuffle_data(ep + 1)
        for wi in range(n_windows):
            t1 = time.time()
            w = data.next_train_window((wi + 1) * k)
            block(w)
            dt = time.time() - t1
            if wi > 0:          # window 0 pays the producer spin-up
                t_deq.append(dt)
            time.sleep(compute_s)     # the producer's overlap budget
    deq_ms = 1e3 * sum(t_deq) / len(t_deq)

    out = {
        "metric": f"staged_window_dequeue_vs_serial_assembly (k={k}, "
                  f"batch {args.batch_size}, pool x{args.workers})",
        "value": round(deq_ms, 3),
        "unit": "ms/window dequeue",
        "serial_assembly_ms": round(serial_ms, 3),
        "window_dequeue_ms": round(deq_ms, 3),
        "consumer_stall_saved_ms": round(serial_ms - deq_ms, 3),
        "compute_ms_between_dequeues": round(compute_s * 1e3, 3),
        "windows": len(t_deq),
        "note": "serial = k draws + stack + put ON the consumer thread "
                "(pre-window train_iter); dequeue = what window-mode "
                "train_iter pays (producer staged off-thread)",
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
