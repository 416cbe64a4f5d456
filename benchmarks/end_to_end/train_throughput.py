"""Samples per second per chip: steps completed in the window (it ends in
``block_until_ready``) x global batch / window seconds / chips."""


def read(run):
    w = run.window
    if not w.steps or not w.seconds:
        return None
    return w.steps * run.global_batch / w.seconds / run.cell.chips
