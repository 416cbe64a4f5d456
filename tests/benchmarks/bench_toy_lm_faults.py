"""The toy token model with one fault planted each, where a later change to
the program could plant it: the tests run the cell with one of these in the
timed path's place (``run_cell(..., control={"modelfile": ...})``) and see
``correct`` come out false.  No cell of the benchmark."""

import jax

from theanompi_tpu.models.transformer_lm import MoETransformerLM

SCALED_LEAF = ("block0", "moe", "w1")


class ScaledGradient(MoETransformerLM):
    """One expert weight's gradient reaches the optimizer half too long;
    the parameters, the logits and the cost of the first step are right.
    (A tenth too long reads 0.10 of the leaf's norm, which is within twice
    what a sound run reads where tokens change expert: the limit's room.)"""

    def postprocess_grads(self, grads, count):
        def scale(path, g):
            return 1.5 * g if tuple(k.key for k in path) == SCALED_LEAF \
                else g
        return jax.tree_util.tree_map_with_path(scale, grads)


class HalfBatch(MoETransformerLM):
    """Half of the batch left out, the mean taken over the rest."""

    def loss_and_metrics(self, params, bn_state, batch, rng, train):
        if train:
            batch = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return super().loss_and_metrics(params, bn_state, batch, rng, train)


class StateUnchanged(MoETransformerLM):
    """A step that returns its state as it got it."""

    def postprocess_update(self, old_params, old_opt, new_params, new_opt,
                           count):
        return old_params, old_opt
