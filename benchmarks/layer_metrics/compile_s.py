"""Layer: compile.  The recorder's ``compile`` bucket: host seconds inside
``compile_iter_fns`` (building the jitted functions and placing the state on
the mesh).  The XLA compile itself is lazy and is NOT in this bucket: see
``first_step_s``.  Applies to every cell."""


def read(run):
    return run.compile_bucket_s
