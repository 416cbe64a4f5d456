#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the trainer still starts on the TPU.

One process, no children.  It drives the main training path once through
the session API a user calls (``BSP().init(...).wait()``), with AlexNet at
its published width (227x227x3, 1000 classes, batch 128 per chip, bf16
compute) on synthetic data, random weights from the model's seed:

* BSP: eight train steps, the validation pass, the recorder;
* on more than one chip also EASGD and GoSGD, four steps each, so ``psum``,
  ``pmean`` and ``ppermute`` run on the real interconnect;
* every ``pl.pallas_call`` wrapper left in ``theanompi_tpu/ops``, compiled
  (not interpreted) at the flat lengths and leaf shapes AlexNet's and
  VGG-16's parameters give the exchanger, against its jnp oracle.

It checks that every recorded cost is finite and the first is near
ln(classes), that every parameter leaf is laid out over all chips of the
mesh, and that every chip reports memory in use.  Any failed check raises;
there is no fallback to another platform, and ``trace_dir`` and the AOT
executable store stay off.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script names the platform it found and exits 2 before
running anything.

The phases are plain functions so that ``tests/test_chip_smoke.py`` drives
them at toy size on the 8-device CPU mesh with the same checks.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

ALEXNET = ("theanompi_tpu.models.alex_net", "AlexNet")
VGG16 = ("theanompi_tpu.models.vggnet_16", "VGGNet_16")
N_CLASS = 1000


def require(cond: bool, msg: str) -> None:
    """A smoke check (an ``assert`` would vanish under ``python -O``)."""
    if not cond:
        raise AssertionError(msg)


def device_report() -> dict:
    """Initialise JAX and print what it runs on; returns the device triple
    exactly as JAX reports it."""
    import jax
    import jaxlib
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"chip_smoke: jax {jax.__version__} jaxlib {jaxlib.__version__} "
          f"libtpu {libtpu} | platform {dev['platform']} kind "
          f"{dev['kind']!r} count {dev['count']}", flush=True)
    return dev


# ---------------------------------------------------------------------------
# training phases
# ---------------------------------------------------------------------------

def train_phase(rule_name: str, steps: int, *, modelfile: str,
                modelclass: str, n_class: int, cost_tol: float = 0.25,
                **config) -> dict:
    """``steps`` train steps plus the validation pass through
    ``<Rule>().init(devices=None, ...).wait()`` on every visible device,
    then the layout/finiteness checks.  Returns the phase's timings."""
    import jax
    import numpy as np

    import theanompi_tpu

    t0 = time.time()
    rule = getattr(theanompi_tpu, rule_name.upper())()
    rule.init(devices=None, modelfile=modelfile, modelclass=modelclass,
              epochs=1, synthetic_batches=steps, printFreq=1, **config)
    rec = rule.wait()
    model = rule.model
    jax.block_until_ready(model.step_state)
    total = time.time() - t0

    records = rec._all_records
    costs = [r["cost"] for r in records]
    require(len(costs) == steps, f"{rule_name}: {len(costs)} train records, "
                                 f"expected {steps}")
    require(all(math.isfinite(c) for c in costs),
            f"{rule_name}: non-finite train cost in {costs}")
    require(abs(costs[0] - math.log(n_class)) < cost_tol,
            f"{rule_name}: first cost {costs[0]:.4f} is not near "
            f"ln({n_class}) = {math.log(n_class):.4f}")
    val = rec.epoch_records[-1]
    require(math.isfinite(val["val_cost"]),
            f"{rule_name}: non-finite validation cost {val}")

    devices = list(model.mesh.devices.flat)
    n = len(devices)
    require(n == len(jax.devices()),
            f"{rule_name}: mesh spans {n} of {len(jax.devices())} devices")
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            model.step_state["params"]):
        require(len(leaf.sharding.device_set) == n and leaf.shape[0] == n,
                f"{rule_name}: parameter {jax.tree_util.keystr(path)} "
                f"{leaf.shape} lies on {len(leaf.sharding.device_set)} of "
                f"{n} devices")
    for d in devices:
        stats = d.memory_stats()
        if stats is None and d.platform != "tpu":
            continue            # the CPU backend keeps no memory statistics
        require(stats["bytes_in_use"] > 0, f"{rule_name}: {d} holds nothing")

    # Wall clock between per-step records (printFreq=1 materialises each
    # step's cost, so a record closes only when its step has run).  The
    # first record carries model build, compile and the first step.
    walls = [r["wall"] for r in records]
    step_secs = float(np.median(np.diff(walls))) if steps > 1 else None
    out = {"phase": rule_name, "devices": n, "steps": steps,
           "setup_secs": round(walls[0], 2),
           "step_secs": None if step_secs is None else round(step_secs, 4),
           "total_secs": round(total, 2), "first_cost": round(costs[0], 4),
           "last_cost": round(costs[-1], 4),
           "val_cost": round(val["val_cost"], 4)}
    print(f"chip_smoke: phase {json.dumps(out)}", flush=True)
    return out


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def model_wire_shapes(modelfile: str, modelclass: str):
    """``(n_params, [(rows, cols), ...])`` of one zoo model at its published
    width: what the flattening strategies and PowerSGD see."""
    import importlib

    import jax
    import numpy as np

    cls = getattr(importlib.import_module(modelfile), modelclass)
    model = cls({"n_workers": 1, "verbose": False, "synthetic_batches": 1,
                 "batch_size": 1})
    shapes = [np.shape(p) for p in jax.tree.leaves(model.params)]
    n_params = sum(int(np.prod(s)) for s in shapes)
    mats = {(int(np.prod(s[:-1])), int(s[-1])) for s in shapes if len(s) >= 2}
    return n_params, sorted(mats)


def kernel_cases(n_params: int, matrices, *, interpret: bool,
                 n_workers: int = 4, rank: int = 2):
    """One case per Pallas wrapper in ``ops/`` (the keys of each module's
    ``PALLAS_ORACLES``), at the padded flat length ``n_params`` gives the
    onebit strategy and at the factor shapes ``matrices`` give PowerSGD.
    Yields ``(name, shape, run)``; ``run()`` builds its own inputs from a
    seed, calls the kernel and its oracle, and returns ``(matches,
    worst_abs_diff, kernel_secs, oracle_secs)`` — bit-equal where the CPU
    tests claim bit-equal, a stated tolerance elsewhere."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from theanompi_tpu.ops import compress, factor_pack
    from theanompi_tpu.parallel.strategies import PowerSGD

    # seconds are a device observation: the interpreter's are not taken
    reps = 0 if interpret else 3

    def timed(fn, *args):
        """(result, median seconds) of a jitted call, compile excluded."""
        out = jax.block_until_ready(fn(*args))
        secs = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            secs.append(time.perf_counter() - t0)
        return out, (float(np.median(secs)) if secs else None)

    def worst(a, b):
        """Largest absolute difference of two float arrays."""
        return float(jnp.max(jnp.abs(a - b)))

    def differs(a, b):
        """1.0 if any element differs (packed words compare as bits)."""
        return float(jnp.any(a != b))

    k_flat, k_state, k_bits, k_scale, k_mat = jax.random.split(
        jax.random.key(0), 5)
    lanes = compress.LANES

    # -- onebit: the flat vector padded to PACK_ALIGN -----------------------
    n = n_params + (-n_params) % compress.PACK_ALIGN

    def encode():
        flat = jax.random.normal(k_flat, (n,), jnp.float32)
        state = jax.random.normal(k_state, (n,), jnp.float32)
        (packed, abs2d), t_k = timed(
            lambda a, b: compress._encode_pallas(a, b, interpret),
            flat.reshape(-1, lanes), state.reshape(-1, lanes))
        (packed_o, abs_o), t_o = timed(
            jax.jit(compress.pack_signs_encode_jnp), flat, state)
        diff = max(differs(packed, packed_o),
                   worst(abs2d.reshape(-1), abs_o))
        return diff == 0.0, diff, t_k, t_o
    yield "_encode_pallas", (n,), encode

    def pack():
        flat = jax.random.normal(k_flat, (n,), jnp.float32)
        got, t_k = timed(lambda a: compress._pack_pallas(a, interpret),
                         flat.reshape(-1, lanes))
        want, t_o = timed(jax.jit(compress.pack_signs_jnp), flat)
        diff = differs(got, want)
        return diff == 0.0, diff, t_k, t_o
    yield "_pack_pallas", (n,), pack

    def residual():
        c = jax.random.normal(k_flat, (n,), jnp.float32)
        c = c.at[::97].set(0.0)     # the c == 0 → bit 1 convention
        absc, packed = jnp.abs(c), compress.pack_signs_jnp(c)
        scale = jnp.mean(absc)
        got, t_k = timed(
            lambda a, p, s: compress._residual_pallas(a, p, s, interpret),
            absc.reshape(-1, lanes), packed, scale)
        want, t_o = timed(jax.jit(compress.signed_residual_jnp),
                          absc, packed, scale)
        diff = worst(got.reshape(-1), want)
        return diff == 0.0, diff, t_k, t_o
    yield "_residual_pallas", (n,), residual

    def unpack_wsum():
        # every worker's packed buffer after the all-gather
        all_packed = jax.random.bits(
            k_bits, (n_workers, n // (32 * lanes), lanes), jnp.uint32)
        scales = jnp.abs(jax.random.normal(k_scale, (n_workers,))) + 0.1
        got, t_k = timed(
            lambda p, s: compress._unpack_wsum_pallas(p, s, interpret),
            all_packed, scales)
        want, t_o = timed(jax.jit(compress.unpack_signs_weighted_sum_jnp),
                          all_packed, scales)
        # a sum of n_workers terms of magnitude ~1, in another order
        diff = worst(got.reshape(-1), want)
        return diff <= 1e-6 * n_workers, diff, t_k, t_o
    yield "_unpack_wsum_pallas", (n_workers, n), unpack_wsum

    # -- powersgd: both factor matmuls of every compressible leaf -----------
    psgd = PowerSGD(rank)
    for i, (r, c) in enumerate(matrices):
        if not psgd._compressible((r, c)):
            continue
        for shape, side in (((r, c), "P"), ((c, r), "Q")):
            def matmul_pack(i=i, shape=shape):
                km, kq = jax.random.split(jax.random.fold_in(k_mat, i))
                m = jax.random.normal(km, shape, jnp.float32)
                q = jax.random.normal(kq, (shape[1], rank), jnp.float32)
                rows_pad = factor_pack.pad_rows(shape[0])
                got, t_k = timed(
                    lambda m, q: factor_pack._matmul_pack_pallas(
                        m, q, rows_pad, interpret), m, q)
                # The oracle is asked for full fp32 precision.  The kernel's
                # dot is the MXU's default single bf16 pass (seen on a v5e:
                # worst difference 0.007–0.011·sqrt(K) at every shape), which
                # rounds each N(0,1) operand to 8 bits: a product is off by
                # up to 2^-8 and a K-term sum by about 2^-8·sqrt(K); the
                # worst of thousands of outputs is allowed four times that.
                with jax.default_matmul_precision("highest"):
                    want, t_o = timed(
                        jax.jit(lambda m, q: factor_pack.matmul_pack_jnp(
                            m, q, rows_pad)), m, q)
                diff = worst(got, want)
                ok = diff <= 2.0 ** -6 * math.sqrt(shape[1]) \
                    and not bool(jnp.any(got[shape[0]:]))
                return ok, diff, t_k, t_o
            yield f"_matmul_pack_pallas[{side}]", shape + (rank,), matmul_pack


def kernel_phase(n_params: int, matrices, *, interpret: bool, label: str,
                 **kw) -> list:
    """Run every case of :func:`kernel_cases`; a kernel that does not
    compile raises, one that does not match its oracle fails the check."""
    rows = []
    for name, shape, run in kernel_cases(n_params, matrices,
                                         interpret=interpret, **kw):
        ok, diff, kernel_secs, oracle_secs = run()
        require(ok, f"kernel {name} at {label} {shape} does not match its "
                    f"oracle (worst difference {diff:.3g})")
        row = {"kernel": name, "at": label, "shape": list(shape),
               "worst_diff": float(f"{diff:.3g}")}
        if not interpret:
            row.update(kernel_secs=round(kernel_secs, 5),
                       oracle_secs=round(oracle_secs, 5))
        print(f"chip_smoke: kernel {json.dumps(row)}", flush=True)
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------

def main() -> int:
    t_start = time.time()
    dev = device_report()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found platform {dev['platform']!r}, not "
              f"'tpu' — this script runs on the chip only", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from theanompi_tpu import native
    from theanompi_tpu.ops import _pallas_util
    from theanompi_tpu.utils import jax_cache

    cache_dir = jax_cache.configure() or os.environ.get(jax_cache.ENV_VAR)
    print(f"chip_smoke: compile cache {cache_dir} | "
          f"native augment {native.native_available()} | Pallas dispatch "
          f"{_pallas_util.dispatch_pallas()}", flush=True)
    require(_pallas_util.dispatch_pallas(),
            "ops dispatch chose the jnp oracles on a TPU "
            "(THEANOMPI_TPU_NO_PALLAS set?)")

    alexnet = dict(modelfile=ALEXNET[0], modelclass=ALEXNET[1],
                   n_class=N_CLASS, synthetic_val_batches=2)
    train_phase("bsp", 8, **alexnet)
    if dev["count"] > 1:
        train_phase("easgd", 4, sync_freq=2, **alexnet)
        train_phase("gosgd", 4, **alexnet)

    for name, (modelfile, modelclass) in (("alexnet", ALEXNET),
                                          ("vgg16", VGG16)):
        n_params, matrices = model_wire_shapes(modelfile, modelclass)
        kernel_phase(n_params, matrices, interpret=False, label=name)

    print(f"chip_smoke: all phases passed in {time.time() - t_start:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
