"""Persistent AOT executable cache (``utils/compile_cache``) contracts.

The subsystem's claims, machine-checked on the CPU mesh:

* **round-trip across processes** — a fresh process compiling the same
  model/rule/spc config via ``compile_iter_fns`` reports ``cache: hit``,
  its compile wall time is measurably below the cold path, and its
  training outputs are bit-identical to the fresh compile's (the ISSUE-3
  acceptance evidence);
* **key sensitivity** — spc/rule/mesh/prng/donation each produce a new
  key (a stale executable can never serve a different program);
* **the fallback ladder** — a corrupted blob or a version-drifted entry
  falls back to a fresh compile with ``deserialize_fallbacks``
  incremented, never an error;
* **checkpoint resume hits** — the recompile after ``load()`` (the
  wedge-recovery restart path) deserializes instead of recompiling.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import TinyModel
from theanompi_tpu.parallel.exchanger import get_exchanger
from theanompi_tpu.utils import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = os.path.join(REPO, "tests", "_compile_cache_child.py")


def _run_child(cache_dir, out_path, rule="bsp", spc=2):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run(
        [sys.executable, CHILD, str(cache_dir), str(out_path), rule,
         str(spc)],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert r.returncode == 0, f"child failed:\n{r.stdout}\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_fresh_process_roundtrip_bit_identical(tmp_path):
    """Cold process: miss + fresh compile + serialize.  Warm process: hit,
    faster compile path, BIT-IDENTICAL costs and parameters — the
    deserialized executable IS the program, not an approximation of it."""
    cache = tmp_path / "cache"
    cold = _run_child(cache, tmp_path / "cold.npz")
    warm = _run_child(cache, tmp_path / "warm.npz")
    assert cold["train_cache"] == "miss"
    assert warm["train_cache"] == "hit"
    # the startup-latency claim: the warm build of the train/val/exchange
    # programs must beat the cold one outright, and the cache-timed path
    # (deserialize vs XLA compile — tracing/lowering excluded, both runs
    # pay it) by 2×+ (margin absorbs CI noise; the real ratio is ~10×)
    assert warm["compile_wall"] < cold["compile_wall"], (warm, cold)
    assert warm["compile_secs"] < 0.5 * cold["compile_secs"], (warm, cold)
    a, b = np.load(tmp_path / "cold.npz"), np.load(tmp_path / "warm.npz")
    np.testing.assert_array_equal(a["costs"], b["costs"])
    np.testing.assert_array_equal(a["params"], b["params"])
    # entries + manifest landed
    assert any(f.endswith(".jexec") for f in os.listdir(cache))
    manifest = json.load(open(cache / "manifest.json"))
    assert any(int(v.get("hits", 0)) > 0 for v in manifest.values())


def _train_key(config, rule="bsp", extra_env=None):
    """Key of the train program a given config would request."""
    model = TinyModel(dict(config, verbose=False))
    exch = get_exchanger(rule, model.config)
    model.compile_iter_fns(exch)
    info = model.compile_info["train"]
    assert info["cache"] in ("miss", "hit"), info
    return info["key"]


def test_key_sensitivity(tmp_path):
    """spc / rule / mesh / prng / donation each flip the key."""
    cache = str(tmp_path / "kc")
    base = {"compile_cache": cache, "steps_per_call": 1}
    k_base = _train_key(base)
    assert k_base == _train_key(base), "same config must reproduce its key"
    k_spc = _train_key(dict(base, steps_per_call=2))
    k_rule = _train_key(base, rule="easgd")
    k_mesh = _train_key(dict(base, n_workers=4))
    keys = {"base": k_base, "spc": k_spc, "rule": k_rule, "mesh": k_mesh}
    try:
        jax.config.update("jax_default_prng_impl", "rbg")
        keys["prng"] = _train_key(base)
    finally:
        jax.config.update("jax_default_prng_impl", "threefry2x32")
    vals = list(keys.values())
    assert len(set(vals)) == len(vals), f"key collision: {keys}"

    # donation signature: same function, donated vs not → different keys
    def f(x):
        return x * 2.0

    x = jnp.ones((8,))
    lo_plain = jax.jit(f).lower(x)
    lo_don = jax.jit(f, donate_argnums=(0,)).lower(x)
    assert cc.program_key(lo_plain) != cc.program_key(lo_don)


def _tiny_entry(cache_dir):
    """One small cached program; returns (cache, lowered, key)."""
    cache = cc.CompileCache(str(cache_dir))
    lowered = jax.jit(lambda x: x + 1.0).lower(jnp.ones((16,)))
    compiled, info = cache.get_or_compile(lowered, label="tiny")
    assert info["cache"] == "miss" and info["serialized"], info
    return cache, lowered, info["key"]


def test_corrupted_blob_falls_back(tmp_path):
    cache, lowered, key = _tiny_entry(tmp_path)
    path = os.path.join(cache.cache_dir, key + ".jexec")
    raw = open(path, "rb").read()
    with open(path, "wb") as fh:          # keep the header, garble the body
        fh.write(raw.split(b"\n", 1)[0] + b"\n" + b"\x00garbage\x01" * 64)
    fresh = cc.CompileCache(str(tmp_path))
    compiled, info = fresh.get_or_compile(lowered, label="tiny")
    assert info["cache"] == "deserialize_fallback", info
    assert fresh.counters["deserialize_fallbacks"] == 1
    np.testing.assert_array_equal(
        np.asarray(compiled(jnp.ones((16,)))), np.full((16,), 2.0))
    # the entry was rewritten: next read is a clean hit again
    again = cc.CompileCache(str(tmp_path))
    _, info2 = again.get_or_compile(lowered, label="tiny")
    assert info2["cache"] == "hit", info2


def test_version_mismatch_falls_back(tmp_path):
    cache, lowered, key = _tiny_entry(tmp_path)
    path = os.path.join(cache.cache_dir, key + ".jexec")
    head, body = open(path, "rb").read().split(b"\n", 1)
    header = json.loads(head.decode())
    header["jax"] = "0.0.0-somebody-elses-runtime"
    with open(path, "wb") as fh:
        fh.write(json.dumps(header).encode() + b"\n" + body)
    fresh = cc.CompileCache(str(tmp_path))
    compiled, info = fresh.get_or_compile(lowered, label="tiny")
    assert info["cache"] == "deserialize_fallback", info
    assert "0.0.0" in info["fallback_reason"]
    assert fresh.counters["deserialize_fallbacks"] == 1
    assert compiled is not None


def test_prewarm_header_check_recompiles(tmp_path):
    """``load=False`` trusts an entry only after its header parses: a
    truncated or version-drifted entry is re-prewarmed off-line instead of
    being discovered as a deserialize-fallback on the chip's clock."""
    cache, lowered, key = _tiny_entry(tmp_path)
    path = os.path.join(cache.cache_dir, key + ".jexec")
    with open(path, "wb") as fh:
        fh.write(b"truncated junk, no header")
    fresh = cc.CompileCache(str(tmp_path))
    _, info = fresh.get_or_compile(lowered, label="tiny", load=False)
    assert info["cache"] == "deserialize_fallback", info
    assert fresh.counters["deserialize_fallbacks"] == 1
    assert info["serialized"]                  # entry rewritten in place
    _, info2 = cc.CompileCache(str(tmp_path)).get_or_compile(
        lowered, label="tiny", load=False)
    assert info2["cache"] == "hit", info2      # second prewarm: clean hit


def test_checkpoint_resume_hits_cache(tmp_path):
    """The wedge-recovery restart: a second worker-style build of the SAME
    config (then restoring the checkpoint) deserializes every program —
    train, val, and the standalone exchange collective."""
    cache = str(tmp_path / "cache")
    ckpt = str(tmp_path / "ckpt")
    cfg = {"verbose": False, "compile_cache": cache}
    m1 = TinyModel(dict(cfg))
    ex1 = get_exchanger("easgd", m1.config)
    m1.compile_iter_fns(ex1)
    assert m1.compile_info["train"]["cache"] == "miss"
    m1.data.shuffle_data(0)
    m1.train_iter(1)
    ex1.exchange(None, 1)
    m1.save(ckpt, epoch=0, count=1)

    m2 = TinyModel(dict(cfg))
    ex2 = get_exchanger("easgd", m2.config)
    m2.compile_iter_fns(ex2)
    for fn in ("train", "val", "exchange"):
        assert m2.compile_info[fn]["cache"] == "hit", m2.compile_info
    assert m2.load(ckpt) == 0
    m2.data.shuffle_data(0)
    m2.train_iter(2)                       # deserialized program trains on
    ex2.exchange(None, 2)
    assert np.isfinite(float(m2.current_info["cost"]))


def test_uncreatable_cache_dir_disables(tmp_path):
    """An uncreatable dir (read-only mount, a file in the way) degrades to
    the inert instance instead of crashing the run — the module contract:
    every cache-side error is non-fatal."""
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    c = cc.CompileCache(str(blocker / "cache"))
    assert not c.enabled
    m = TinyModel({"verbose": False,
                   "compile_cache": str(blocker / "cache")})
    m.compile_iter_fns(get_exchanger("bsp", m.config))   # must not raise
    assert m.compile_info["train"]["cache"] == "off"


def test_cache_off_is_lazy_jit():
    """No cache configured → pre-cache behavior: compile_info says 'off'
    and train_fn is still the lazy jit wrapper, not an AOT Compiled."""
    m = TinyModel({"verbose": False})
    m.compile_iter_fns(get_exchanger("bsp", m.config))
    assert m.compile_info["train"]["cache"] == "off"
    assert not isinstance(m.train_fn, jax.stages.Compiled)
    assert not m.compile_cache.enabled


def test_recorder_compile_bucket():
    from theanompi_tpu.utils.recorder import Recorder
    rec = Recorder({"verbose": False, "printFreq": 1})
    rec.start()
    rec.end("compile")
    rec.start()
    rec.end("train")
    rec.train_error(1, 0.5, 0.1, 8)
    rec.print_train_info(1)
    r = rec._all_records[-1]
    assert r["t_compile"] >= 0 and "t_train" in r
    # bucket resets after the print, like every section
    assert rec.t_sec["compile"] == 0.0
    ep = rec.print_val_info(1)
    assert "t_compile" in ep        # cumulative, for resume-goes-to-~0


def test_rows_manifest_consistency():
    """Every manifest row's env round-trips through bench_row_config —
    the drift guard between prewarm shapes and measured shapes."""
    sys.path.insert(0, REPO)
    from scripts.rows import ROWS, rows
    import bench
    assert rows("r8") and rows("heavy")
    labels = [r.label for r in ROWS]
    assert len(set(labels)) == len(labels), "duplicate row labels"
    for row in ROWS:
        # bench_row_config force-exports THEANOMPI_TPU_NO_PALLAS for
        # oracle-control rows — keep that out of the test process
        saved_np = os.environ.get("THEANOMPI_TPU_NO_PALLAS")
        try:
            model_name, rule, config, flags = \
                bench.bench_row_config(row.env)
        finally:
            if saved_np is None:
                os.environ.pop("THEANOMPI_TPU_NO_PALLAS", None)
            else:
                os.environ["THEANOMPI_TPU_NO_PALLAS"] = saved_np
        assert row.label.startswith(model_name), row
        if "BENCH_SPC" in row.env and int(row.env["BENCH_SPC"]) > 1:
            assert config["steps_per_call"] == int(row.env["BENCH_SPC"])


@pytest.mark.slow
def test_prewarm_then_registry_model_hits(tmp_path):
    """scripts/prewarm_cache.py (live CPU venue) then a worker-style
    compile of the same manifest row: the executable store must hit —
    the whole prewarm-then-measure window workflow, minus the TPU."""
    cache = str(tmp_path / "cache")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "scripts",
                                            "prewarm_cache.py"),
         "--rows", "cifar10-b128", "--cache", cache, "--platform", "cpu",
         "--no-spc1-flops"],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "cifar10-b128:" in r.stdout and "FAILED" not in r.stdout

    child = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = ''\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "jax.config.update('jax_default_prng_impl', 'rbg')\n"
        "import importlib, json, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from bench import bench_row_config\n"
        "from scripts.rows import rows\n"
        "from theanompi_tpu.models.registry import MODELS\n"
        "from theanompi_tpu.parallel.exchanger import get_exchanger\n"
        "from theanompi_tpu.parallel.mesh import worker_mesh, WORKER_AXIS\n"
        "row = rows('cifar10-b128')[0]\n"
        "name, rule, cfg, flags = bench_row_config(row.env)\n"
        "mf, mc, extra = MODELS[name]\n"
        "mesh = worker_mesh(None)\n"
        "config = {'mesh': mesh, 'size': mesh.shape[WORKER_AXIS],\n"
        "          'rank': 0, 'verbose': False, **extra, **cfg,\n"
        f"          'compile_cache': {cache!r}}}\n"
        "m = getattr(importlib.import_module(mf), mc)(config)\n"
        "m.compile_iter_fns(get_exchanger(rule, config))\n"
        "print(json.dumps(m.compile_info['train']))\n")
    r2 = subprocess.run([sys.executable, "-c", child], capture_output=True,
                        text=True, timeout=560, env=env, cwd=REPO)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    info = json.loads(r2.stdout.strip().splitlines()[-1])
    assert info["cache"] == "hit", info
