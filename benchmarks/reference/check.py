"""The comparison between the system and its plain reference.

The system computes in bfloat16 (8 bits of significand: each rounding is up
to 2**-9 = 0.2% of the value) with float32 accumulation and float32
parameters; the reference is float32 at ``Precision.HIGHEST``.  Through 8 to
16 layers the roundings of the activations add up roughly as a random walk
and arrive at the logits as a few parts in a thousand of the logits' range.
The numbers the chip gave are in PERF.md (Findings, PR 23).

The limits are therefore set on the error relative to the largest reference
logit, at 2%: several times what bf16 gives and far under what a narrower
format would (an 8-bit float with 3 bits of significand rounds by up to
2**-4 = 6% per value; ``tests/benchmarks`` shows a float8 forward pass
failing the limit).  The loss is a mean over the batch of differences of
logits, so its limit is the logit limit times the logits' scale, with a
floor for logits near zero.

Where the reference module gives a training objective (``train_loss``), the
timed path itself is held to it, not a program of the check's own.  The
harness keeps what the compiled step took and gave in its first three steps
(the batches the loader fed, the cost of each, the optimizer's first moment
after one step, the parameters after three), hands the same step and state
to the window, and once the window has closed and the program's state is
freed the reference follows those three steps from the same initial
parameters: float32 at ``Precision.HIGHEST``, plain Adam
(``plain_opt.py``), each chip's rows apart and the mean over chips, as BSP
trains.  Two numbers are compared, each by its worst leaf, and two more
are reported beside them:

* ``grad_norm_gap``: the first gradient as the optimizer got it (Adam's
  ``m_1 / (1 - b1)``), leaf by leaf: ``| |g_sys| - |g_ref| |`` (2-norms)
  over the reference's norm of that leaf or of the median leaf, whichever
  is larger.  The gap of the norms and not the norm of the difference: a
  token that top-k routing sends to another expert under bf16 rounding
  turns a gradient without changing its length much, and is no fault.
  The worst leaf of a sound run is a router's weights.
* ``change_norm_gap``: ``|p_3 - p_0|`` by leaf, the same measure.  Leaves
  whose reference gradient is nought to rounding (under a thousandth of
  the median leaf's: a bias that softmax cancels) move under Adam by
  round-off alone and are left out, by that rule and not by name.  A step
  that returns its state unchanged reads 1, an update made twice about 1.
* ``step_loss_err``, reported and not compared: the worst of the three
  steps' ``|cost - train_loss|`` over the way the reference's loss went
  in them (the sum of its moves, up or down).  It has no limit because no
  limit holds: the first step's cost separates nothing (at the initial
  parameters an 8-bit float reads as bfloat16 does, 0.0005-0.003 against
  0-0.002), and the later two follow Adam's first updates, which are the
  roughest of a run: at toy size sound runs read up to 0.08 and the
  control from 0.36, but at width 512 on the chip the reference's own loss
  rose in 8 of 12 seeds and sound runs read 0.04 to 1.0.
* ``grad_rel_err``, reported and not compared: the leaves the
  configuration names under ``check.grad_leaves``, as ``|g_sys - g_ref| /
  |g_ref|``.  The sharpest of the four and the one that routing flips
  move: 2-16% at toy size, 17-23% at width 512 on the chip, sound.

The limits and the readings they stand between are beside the constants
below and in PERF.md section 4.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference.plain_ops import softmax_loss as plain_softmax_loss  # noqa: F401,E501

LOGIT_REL_TOL = 0.02
LOSS_ABS_FLOOR = 2e-3
TRAIN_STEPS = 3         # of the timed path, followed by the reference
# The training comparison's limits, each between the largest reading of
# sound runs and the smallest of the control and the faults.  Sound: the
# toy token cell in bf16 on 32 seeds on one CPU device, 12 on four, 12 on
# the chip, and a model of width 512 with four routed blocks on 12 on the
# chip.  The control is the program's compute_dtype one precision down,
# float8_e4m3fn (CPU 6 + 4 seeds, chip 3 + 3); the faults are half the
# batch left out (CPU 6, chip 3 + 3), the exchange left out (CPU, 4) and a
# state returned unchanged, which reads 1 in both (PERF.md section 4).
GRAD_NORM_TOL = 0.20    # sound <= 0.083 (width 512 on the chip; toy 0.054,
                        # 0.012 on the chip); half batch >= 0.34, no
                        # exchange >= 0.63, control >= 0.89
CHANGE_NORM_TOL = 0.15  # sound <= 0.042; control 1.0 on every seed (its
                        # gradients underflow to nought); half batch
                        # 0.09-0.26 and no exchange 0.12-0.18 are the
                        # gradient's to catch
LOSS_WENT_FLOOR = 1e-3  # of the first loss: the least the way counts as
GRAD_NOUGHT = 1e-3      # of the median leaf's gradient norm: not compared


def image_batch(config: dict, rng: np.random.RandomState, n: int):
    """``n`` seeded crops in the value range the data object delivers
    (uint8 pixels minus the synthetic source's scalar mean 122) and labels."""
    hw, ch = int(config["input_hw"]), int(config["input_channels"])
    x = rng.randint(0, 256, (n, hw, hw, ch)).astype(np.float32) - 122.0
    y = rng.randint(0, int(config["n_class"]), n).astype(np.int32)
    return x, y


def compare(ref_logits, sys_logits, ref_loss: float, sys_loss: float) -> dict:
    scale = float(np.max(np.abs(ref_logits)))
    logit_err = float(np.max(np.abs(sys_logits - ref_logits))) / scale \
        if scale > 0 else float("inf")
    loss_err = abs(sys_loss - ref_loss)
    loss_tol = max(LOSS_ABS_FLOOR, LOGIT_REL_TOL * scale)
    ok = bool(np.isfinite(sys_logits).all() and logit_err <= LOGIT_REL_TOL
              and loss_err <= loss_tol)
    return {"ok": ok, "logit_rel_err": logit_err, "logit_scale": scale,
            "loss_err": loss_err, "loss_tol": loss_tol,
            "ref_loss": ref_loss, "sys_loss": sys_loss}


def by_path(tree) -> dict:
    """``{"block0/moe/w1": leaf, ...}`` of a parameter tree, as float64."""
    import jax

    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): np.asarray(leaf, np.float64)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def named(leaves: dict, prefixes) -> dict:
    """The leaves whose path starts with one of ``prefixes`` (whole path
    components)."""
    return {k: v for k, v in leaves.items()
            if any(k == p or k.startswith(p + "/") for p in prefixes)}


def follow_steps(train_loss, params, batches, shards: int, lr: float,
                 optimizer: dict) -> dict:
    """The reference's own first steps from ``params`` over ``batches``
    (``(x, y)`` as the loader fed them, all chips' rows): the objective of
    each chip's rows apart and their mean, as BSP averages its replicas'
    gradients, then plain Adam.  Parameters and moments are donated from
    step to step, so the device holds one copy of each and one gradient."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import plain_opt

    hp = plain_opt.hyper(optimizer)

    def loss(p, x, y):
        parts = zip(jnp.split(x, shards), jnp.split(y, shards))
        return sum(train_loss(p, a, b) for a, b in parts) / shards

    def step(p, state, x, y):
        cost, grads = jax.value_and_grad(loss)(p, x, y)
        p, state = plain_opt.adam_update(grads, state, p, lr, **hp)
        return p, state, cost, grads

    step = jax.jit(step, donate_argnums=(0, 1))
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: jnp.array(a, jnp.float32), params)
        state, losses, first = plain_opt.adam_init(p), [], None
        for x, y in batches:
            p, state, cost, grads = step(p, state, x, y)
            losses.append(float(cost))
            if first is None:
                first = by_path(grads)
            del grads
    return {"losses": losses, "first_grad": first, "params": by_path(p)}


def _norms(leaves: dict) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in leaves.items()}


def _worst_gap(ref: dict, got: dict):
    """Worst leaf of ``|got - ref|`` over the larger of the reference's
    reading of that leaf and of the median leaf; a reading that is no
    number is the worst there can be."""
    floor = float(np.median(list(ref.values()))) if ref else 0.0
    gaps = {k: abs(got[k] - r) / max(r, floor) if max(r, floor) > 0
            else float("inf") for k, r in ref.items()}
    worst = max(gaps, key=lambda k: (not np.isfinite(gaps[k]), gaps[k]),
                default=None)
    return (gaps[worst] if worst is not None else float("inf")), worst


def compare_steps(ref: dict, got: dict, params0, grad_leaves=()) -> dict:
    """The timed path's first steps (``got``: ``losses``, ``first_grad`` and
    ``params`` after the last of them, as the harness kept them) against
    the reference's (``ref``, from :func:`follow_steps`); ``params0`` is
    where both began."""
    p0 = by_path(params0)
    got_grad, got_p = by_path(got["first_grad"]), by_path(got["params"])
    # how much of the way the reference's loss went in these steps the
    # program's costs miss
    went = max(float(np.abs(np.diff(ref["losses"])).sum()),
               LOSS_WENT_FLOOR * abs(ref["losses"][0]))
    loss_errs = [abs(a - b) / went
                 for a, b in zip(got["losses"], ref["losses"])]
    loss_err = max(loss_errs) if np.isfinite(loss_errs).all() \
        else float("inf")
    ref_norm = _norms(ref["first_grad"])
    grad_gap, grad_leaf = _worst_gap(ref_norm, _norms(got_grad))
    nought = GRAD_NOUGHT * float(np.median(list(ref_norm.values())))
    moved = [k for k in ref_norm if ref_norm[k] > nought]
    change_gap, change_leaf = _worst_gap(
        _norms({k: ref["params"][k] - p0[k] for k in moved}),
        _norms({k: got_p[k] - p0[k] for k in moved}))
    rel = {k: float(np.linalg.norm(got_grad[k] - g)) / ref_norm[k]
           for k, g in named(ref["first_grad"], grad_leaves).items()
           if ref_norm[k] > nought}
    rel_leaf = max(rel, key=rel.get, default=None)
    ok = bool(np.isfinite(loss_err) and grad_gap <= GRAD_NORM_TOL
              and change_gap <= CHANGE_NORM_TOL)
    return {"ok": ok, "steps": len(ref["losses"]),
            "step_loss_err": loss_err,
            "ref_losses": ref["losses"], "sys_losses": got["losses"],
            "grad_norm_gap": grad_gap, "grad_norm_tol": GRAD_NORM_TOL,
            "grad_norm_leaf": grad_leaf,
            "change_norm_gap": change_gap,
            "change_norm_tol": CHANGE_NORM_TOL,
            "change_norm_leaf": change_leaf,
            "leaves_nought": sorted(set(ref_norm) - set(moved)),
            "grad_rel_err": rel.get(rel_leaf), "grad_rel_leaf": rel_leaf}
