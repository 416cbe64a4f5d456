"""bench.py at the repo root: one process, one JSON row on success, and a
non-zero exit with no ``value`` on any failure — never an old or a CPU
number under the device metric's name."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run_bench(env_extra, timeout=600):
    """The driver's exact invocation shape: ``python bench.py``."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("BENCH_")}
    env.update(env_extra)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    return r, (json.loads(lines[-1]) if lines else None)


TINY = {"BENCH_MODEL": "cifar10", "BENCH_BATCH": "16", "BENCH_ITERS": "2",
        "BENCH_WARMUP": "1"}


def test_refuses_without_a_tpu():
    """No chip, no explicit CPU request: exit 4 naming the platform, and
    nothing on stdout.  ``JAX_PLATFORMS=cpu`` (ambient in CPU sandboxes) is
    not a request."""
    r, out = _run_bench(dict(TINY, JAX_PLATFORMS="cpu"))
    assert r.returncode == 4, r.stderr[-2000:]
    assert "'cpu'" in r.stderr and out is None


def test_cpu_rehearsal_is_labelled_as_such():
    r, out = _run_bench(dict(TINY, BENCH_FORCE_CPU="1"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert set(out) >= {"metric", "value", "unit", "vs_baseline"}
    assert out["value"] > 0
    assert out["metric"].startswith("CPU REHEARSAL") and "cpu" in out["metric"]
    # the AOT executable store is opt-in: nothing configured it
    assert out["cache"] == "off"


def test_failed_measurement_exits_nonzero_and_prints_no_value():
    """A run that cannot be measured (here: more workers than devices) is a
    non-zero exit with the error on stderr and nothing on stdout — the old
    harness answered with a retry at other settings or an old number."""
    r, out = _run_bench(dict(TINY, BENCH_FORCE_CPU="1",
                             BENCH_CFG='{"n_workers": 4096}'))
    assert r.returncode != 0
    assert out is None and '"value"' not in r.stdout
    assert "4096 workers" in r.stderr


def test_unknown_device_kind_is_an_error():
    import bench

    class Dev:
        device_kind = "TPU v9 imaginary"

    with pytest.raises(ValueError, match="v9 imaginary"):
        bench._peak_flops(Dev())

    class V5e:
        device_kind = "TPU v5 lite"

    assert bench._peak_flops(V5e()) == 197e12


def test_flagship_default_is_spc4_and_explicit_rows_untouched(monkeypatch):
    import bench
    for k in list(os.environ):
        if k.startswith("BENCH_"):
            monkeypatch.delenv(k, raising=False)
    bench._apply_flagship_defaults()
    assert os.environ.get("BENCH_SPC") == "4"
    del os.environ["BENCH_SPC"]
    monkeypatch.setenv("BENCH_MODEL", "alexnet")
    bench._apply_flagship_defaults()
    assert "BENCH_SPC" not in os.environ
    monkeypatch.delenv("BENCH_MODEL")
    monkeypatch.setenv("BENCH_REAL_DATA", "1")     # realdata requires spc=1
    bench._apply_flagship_defaults()
    assert "BENCH_SPC" not in os.environ


@pytest.mark.slow
def test_bench_trace_row_carries_overlap_columns():
    """BENCH_TRACE=1 captures a profiler window after the timed loop and
    folds the devprof attribution into the row.  Slow lane: a full bench
    subprocess CPU-compiling train/val/trace programs; the trace-row SCHEMA
    stays tier-1-guarded by the schema-drift checker."""
    r, out = _run_bench(dict(TINY, BENCH_FORCE_CPU="1", BENCH_TRACE="1",
                             BENCH_TRACE_ITERS="2"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["value"] > 0
    from theanompi_tpu.utils import devprof
    assert set(devprof.TRACE_ROW_COLUMNS) <= set(out), sorted(out)
    assert 0.0 <= out["overlap_ratio"] <= 1.0
    assert out["device_mfu"] is None          # no MFU asked for


def test_powersgd_wire_bytes_uses_real_factorization():
    """The wire model must follow PowerSGD's own
    [prod(shape[:-1]), shape[-1]] per-leaf factorization gated by
    _compressible, plus a dense psum term for the rejected leaves."""
    from scripts.predict_scaling import wire_bytes
    with open(os.path.join(REPO, "model_param_counts.json")) as f:
        counts = json.load(f)
    vgg = counts["vgg16"]
    assert 60_000 < vgg["rows_plus_cols"] < 120_000, vgg
    assert vgg["powersgd_dense"] > 0
    wb = wire_bytes("powersgd4", vgg["params"], vgg["rows_plus_cols"], 8,
                    vgg["powersgd_dense"])
    ring = 2.0 * 7 / 8
    assert wb == ring * (4 * vgg["rows_plus_cols"]
                         + vgg["powersgd_dense"]) * 4
    assert wb < 0.05 * wire_bytes("allreduce", vgg["params"], 0, 8)
