"""Layer: compile.  Host seconds of the first ``train_iter`` through to its
materialised cost (the harness's set-up phase ``first_step``): the lazy jit
compiles the step program here, or loads it from JAX's persistent cache.
Applies to every cell."""


def read(run):
    return run.setup_phases.get("first_step")
