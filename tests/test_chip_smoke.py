"""chip_smoke.py off the chip: it must refuse the CPU, and its phases must
hold at toy size on the 8-device CPU mesh under the same checks the chip
run applies (finite costs, the first near ln(classes), parameters laid out
over every device; kernels against their oracles, interpreted here)."""

import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(modelfile="tests.conftest", modelclass="TinyModel", n_class=2,
           batch_size=8, verbose=False)


def test_refuses_the_cpu_and_prints_no_result():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO)
    assert r.returncode == 2, r.stderr[-2000:]
    assert "'cpu'" in r.stderr and "not 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("rule,steps,extra", [
    ("bsp", 4, {}),
    ("easgd", 4, {"sync_freq": 2}),
    ("gosgd", 4, {}),
])
def test_train_phase_at_toy_size_on_the_cpu_mesh(rule, steps, extra):
    out = chip_smoke.train_phase(rule, steps, n_train=8 * 8 * steps,
                                 **TOY, **extra)
    assert out["devices"] == 8 and out["steps"] == steps
    assert abs(out["first_cost"] - math.log(2)) < 0.25
    assert math.isfinite(out["val_cost"])


def test_train_phase_fails_on_a_wrong_first_cost():
    with pytest.raises(AssertionError, match="not near"):
        chip_smoke.train_phase("bsp", 2, n_train=8 * 8 * 2,
                               **dict(TOY, n_class=1000))


def test_kernel_phase_interpreted_at_toy_shapes():
    rows = chip_smoke.kernel_phase(
        1000, [(300, 2500), (20, 4)], interpret=True, label="toy",
        n_workers=3)
    names = {r["kernel"].split("[")[0] for r in rows}
    # every wrapper the ops modules declare is exercised, and nothing is
    # timed off the chip
    from theanompi_tpu.ops import compress, factor_pack
    assert names == set(compress.PALLAS_ORACLES) | \
        set(factor_pack.PALLAS_ORACLES)
    assert not any("kernel_secs" in r for r in rows)
