"""Process/runtime core.

TPU-native rebuild of Theano-MPI's ``theanompi/lib/base.py``
(SURVEY.md §2.1): the reference's ``MPI_GPU_Process`` did MPI init
(``MPI.COMM_WORLD`` rank/size), GPU device binding via ``THEANO_FLAGS``, and
model import by dotted ``modelfile`` string + ``modelclass`` name, building
the shared ``config`` dict handed to models.

Here one Python process per HOST drives all its local chips; "rank/size" map
to ``jax.process_index()`` / the worker-mesh extent; device binding is
unnecessary (XLA owns the chips); the communicator object is the named-axis
mesh from :mod:`theanompi_tpu.parallel.mesh`.  Method names are kept for
contract parity.
"""

from __future__ import annotations

import importlib
from typing import Optional

import jax

from .parallel.mesh import WORKER_AXIS, init_multihost, worker_mesh


def canonical_prng_impl(impl):
    """Normalize user-facing PRNG names to jax's ('threefry' is accepted as
    an alias for 'threefry2x32')."""
    return {"threefry": "threefry2x32"}.get(impl, impl)


class MeshProcess:
    """≙ reference ``MPI_GPU_Process``."""

    def __init__(self, config: Optional[dict] = None):
        self.config = dict(config or {})
        self.verbose: bool = self.config.get("verbose", True)
        self.mesh = None
        self.rank = 0
        self.size = 1

    def get_internode_comm(self):
        """Bring up the communicator (≙ MPI_Init + COMM_WORLD): multi-host
        control plane if configured, then the 1-D workers mesh."""
        platform = self.config.get("platform")
        if platform:
            # programmatic platform pin (config `platform=cpu`) for
            # launcher-spawned workers, beside the JAX_PLATFORMS env var
            jax.config.update("jax_platforms", platform)
        impl = canonical_prng_impl(self.config.get("prng_impl"))
        if impl:
            # 'rbg' uses the TPU hardware RNG for in-step randomness
            # (dropout, GAN z draws) — measurably cheaper than threefry on
            # AlexNet-sized dropout; default stays threefry (jax's default,
            # fully deterministic across backends).
            jax.config.update("jax_default_prng_impl", impl)
        init_multihost(
            coordinator_address=self.config.get("coordinator_address"),
            num_processes=self.config.get("num_processes"),
            process_id=self.config.get("process_id"),
        )
        # tp>1 (tensor parallelism, parallel/tp.py): n_workers counts
        # data-parallel GROUPS; the mesh gains a 'model' axis and each group
        # spans tp chips.  rank/size semantics (and the data sharding they
        # drive) stay data-parallel.
        self.mesh = worker_mesh(self.config.get("n_workers"),
                                tp=int(self.config.get("tp", 1)),
                                pp=int(self.config.get("pp", 1)),
                                sp=int(self.config.get("sp", 1)))
        self.rank = jax.process_index()
        self.size = self.mesh.shape[WORKER_AXIS]
        self.config.update(rank=self.rank, size=self.size, mesh=self.mesh,
                           verbose=self.verbose and self.rank == 0)
        return self.mesh

    def init_device(self):
        """No-op on TPU (the reference bound THEANO_FLAGS=device=cudaN here);
        kept so session scripts written against the reference API run."""
        return jax.devices()

    def build_model(self, modelfile: str, modelclass: str):
        """Import the model by dotted module path + class name — identical
        contract to the reference's importlib-based model loading."""
        mod = importlib.import_module(modelfile)
        cls = getattr(mod, modelclass)
        return cls(self.config)
