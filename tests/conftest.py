"""Test harness: simulate an 8-device mesh on CPU.

SURVEY.md §4: the reference had no unit-testable communicator — multi-GPU
correctness was only checkable on a real cluster.  JAX's forced host platform
device count gives every exchanger/rule a real 8-way mesh in CI.

The platform is pinned to the CPU programmatically below, so the suite runs
the same with or without ``JAX_PLATFORMS=cpu`` in the environment.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# NOTE: do NOT enable the persistent XLA compile cache here (the lever
# utils/jax_cache.configure pulls for chip entry points) — serializing the
# big 8-device CPU shard_map executables SEGFAULTED the whole pytest
# process (observed round 6, test_3d_mesh).  configure() leaves a
# CPU-pinned process alone for that reason.

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from theanompi_tpu.models import layers as L  # noqa: E402
from theanompi_tpu.models.data import DataBase  # noqa: E402
from theanompi_tpu.models.model_base import ModelBase  # noqa: E402


class SyntheticData(DataBase):
    """Tiny deterministic 2-class dataset for fast rule/equivalence tests."""

    DIM = 16

    def __init__(self, config=None, batch_size=8, n_train=256, n_val=64):
        super().__init__(config, batch_size)
        rng = np.random.RandomState(7)
        w = rng.randn(self.DIM)

        def make(n, seed):
            r = np.random.RandomState(seed)
            x = r.randn(n, self.DIM).astype(np.float32)
            y = (x @ w > 0).astype(np.int32)
            return x, y

        self.x_train, self.y_train = make(n_train, 11)
        self.x_val, self.y_val = make(n_val, 22)
        self._finalize()


class TinyModel(ModelBase):
    """Minimal MLP following the full model contract — compiles in seconds
    on the CPU mesh, used by rule/equivalence/checkpoint tests."""

    batch_size = 8
    epochs = 2
    n_subb = 1
    learning_rate = 0.05
    momentum = 0.9
    weight_decay = 0.0
    lr_adjust_epochs = ()
    seed = 3

    def build_model(self):
        import jax.numpy as jnp
        cd = self.config.get("compute_dtype", jnp.float32)
        dim = SyntheticData.DIM
        self.seq = L.Sequential([
            L.FC(dim, 32, w_init="he", compute_dtype=cd, name="fc1"),
            L.FC(32, 2, w_init=("normal", 0.01), activation=None,
                 compute_dtype=cd, name="out"),
        ])
        self.data = SyntheticData(self.config, self.batch_size,
                                  n_train=int(self.config.get("n_train", 256)))


class _CrashOnceTrainIter:
    """Fault-injection mixin for supervisor/recovery tests: raises at
    ``crash_at`` once (a marker file records that the crash already
    happened, so the restarted run proceeds)."""

    def train_iter(self, count, recorder=None):
        marker = self.config.get("crash_marker")
        if (marker and count >= int(self.config.get("crash_at", 10 ** 9))
                and not os.path.exists(marker)):
            with open(marker, "w") as f:
                f.write("crashed")
            raise RuntimeError("injected crash for supervisor test")
        super().train_iter(count, recorder)


class CrashOnceModel(_CrashOnceTrainIter, TinyModel):
    pass


from theanompi_tpu.models.transformer_lm import TransformerLM  # noqa: E402


class CrashOnceLM(_CrashOnceTrainIter, TransformerLM):
    """The same fault injection on the transformer — pins that the
    supervisor/resume recovery loop is model-agnostic."""


class SleepyModel(TinyModel):
    """Slows each train_iter by ``iter_sleep`` seconds — gives external
    fault injectors (the chaos harness's SIGKILL-mid-epoch tests) a wide,
    deterministic window to land a signal inside an epoch."""

    def train_iter(self, count, recorder=None):
        import time
        time.sleep(float(self.config.get("iter_sleep", 0.05)))
        super().train_iter(count, recorder)


class AlwaysCrashModel(TinyModel):
    """Crashes at every ``crash_at``-th iteration, every run — the
    systemic failure a crash-loop breaker must stop retrying."""

    def train_iter(self, count, recorder=None):
        if count >= int(self.config.get("crash_at", 1)):
            raise RuntimeError("injected systemic crash (chaos test)")
        super().train_iter(count, recorder)


class HangOnceModel(TinyModel):
    """Fault-injection model for the hang-recovery test: STALLS (sleeps far
    past any stall_timeout) at ``hang_at`` once; the marker file makes the
    restarted run proceed.  The worker's watchdog with stall_action=exit is
    what breaks the hang."""

    def train_iter(self, count, recorder=None):
        import time
        marker = self.config.get("hang_marker")
        if (marker and count >= int(self.config.get("hang_at", 10 ** 9))
                and not os.path.exists(marker)):
            with open(marker, "w") as f:
                f.write("hung")
            time.sleep(300)          # the watchdog must kill us long before
        super().train_iter(count, recorder)


@pytest.fixture(scope="session")
def mesh8():
    from theanompi_tpu.parallel.mesh import worker_mesh
    return worker_mesh(8)


@pytest.fixture(scope="session")
def mesh4():
    from theanompi_tpu.parallel.mesh import worker_mesh
    return worker_mesh(4)
