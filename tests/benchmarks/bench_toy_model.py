"""The toy model of the benchmark's CPU rehearsal: a 3-layer convnet over
the program's own synthetic ImageNet source at a 32-pixel crop.  It exists
only so that the tests can drive ``benchmarks.harness`` end to end in
seconds; it is no cell of the benchmark."""

import jax.numpy as jnp

from theanompi_tpu.models import layers as L
from theanompi_tpu.models.data.imagenet import ImageNet_data
from theanompi_tpu.models.model_base import ModelBase


class ToyNet(ModelBase):
    batch_size = 8
    learning_rate = 0.01
    weight_decay = 0.0005
    n_class = 10

    def build_model(self) -> None:
        cd = self.config.get("compute_dtype", jnp.bfloat16)
        self.seq = L.Sequential([
            L.Conv(3, 8, 3, stride=2, padding=1, w_init=("normal", 0.001),
                   compute_dtype=cd, name="conv1"),           # 32 -> 16
            L.Pool(2, 2, mode="max", name="pool1"),           # -> 8
            L.Flatten(),
            L.FC(8 * 8 * 8, 32, w_init=("normal", 0.01), compute_dtype=cd,
                 name="fc2"),
            L.FC(32, self.n_class, w_init=("normal", 0.01), activation=None,
                 compute_dtype=cd, name="softmax"),
        ])
        self.data = ImageNet_data(self.config, self.batch_size, crop=32)
