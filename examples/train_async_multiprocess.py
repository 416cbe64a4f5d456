#!/usr/bin/env python
"""Asynchronous EASGD/ASGD across PROCESSES — one shared center over TCP.

The reference ran a dedicated MPI server rank holding the EASGD center;
worker NODES exchanged with it at their own pace.  This session reproduces
that topology without MPI (``parallel/center_server.py``): the first
process serves the center, every other process joins it by address — each
an independent JAX runtime (its own chips, its own compiled programs),
coupled only by the socket.

One-machine demo on the simulated mesh (two terminals, or `&`):

  # terminal 1 — serve the center AND train one island
  TMPI_FORCE_CPU=1 ROLE=server CENTER_PORT=47555 \\
      python examples/train_async_multiprocess.py

  # terminal 2 — a second process joins the same center (ISLAND_BASE must
  # clear the server's islands: it runs ids 0..ISLANDS-1, so base = 2)
  TMPI_FORCE_CPU=1 ROLE=worker CENTER_ADDR=127.0.0.1:47555 ISLAND_BASE=2 \\
      python examples/train_async_multiprocess.py

On a real pod, run ROLE=server on one host and ROLE=worker (with
CENTER_ADDR=<server-host>:<port>) on the rest — one process per host: a
second process on the same TPU host cannot claim chips the first one
holds, so the one-machine demo above is for the CPU mesh only.  RULE=asgd selects the
downpour exchange (accumulate ``sync_freq`` steps, ship the delta, reset
to the returned center) instead of the elastic one.
"""

import os

from _common import setup, n_devices

setup()

from theanompi_tpu import ASGD, EASGD  # noqa: E402

if __name__ == "__main__":
    role = os.environ.get("ROLE", "server")
    rule_name = os.environ.get("RULE", "easgd").lower()
    rule = (ASGD if rule_name == "asgd" else EASGD)()
    kw = dict(
        devices=n_devices(),
        modelfile="theanompi_tpu.models.cifar10",
        modelclass="Cifar10_model",
        async_islands=int(os.environ.get("ISLANDS", 2)),
        island_base=int(os.environ.get("ISLAND_BASE", 0)),
        sync_freq=4,
        run_seconds=float(os.environ.get("RUN_SECONDS", 30)),
        batch_size=32,
        synthetic_train=4096,
    )
    kw["easgd_mode" if rule_name == "easgd" else "asgd_mode"] = "async"
    if role == "server":
        kw.update(center_serve=True,
                  center_port=int(os.environ.get("CENTER_PORT", 0)),
                  # keep serving after this process's islands finish so
                  # late workers can still drain their exchanges
                  center_keep_serving=bool(os.environ.get("KEEP_SERVING")))
    else:
        kw.update(center_addr=os.environ["CENTER_ADDR"])
    rule.init(**kw)
    trainer = rule.wait()
    if role == "server" and hasattr(trainer, "center_address"):
        print("center served at", trainer.center_address, flush=True)
    print(trainer.stats())
    trainer.save("./inc")
    if role == "server" and os.environ.get("KEEP_SERVING"):
        # outlive this process's own islands so late workers (first compile
        # can take tens of seconds) finish their exchanges
        import time
        extra = float(os.environ.get("SERVE_EXTRA", 90))
        print(f"serving the center {extra:.0f}s more for late workers",
              flush=True)
        time.sleep(extra)
        print("final:", trainer.center.updates_by_island)
