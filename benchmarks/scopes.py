"""Device time of a named scope of the train program, for the per-layer
readers that take one: ``jax.named_scope`` names joined to the traced
instructions through ``run.scopes`` (``trace.scopes_from_hlo``)."""

from benchmarks import trace


def scope_ms_per_step(run, scope: str):
    """Milliseconds a step of chip 0's time in which an instruction under
    ``scope`` ran (forward, backward and what the backward recomputes
    alike): the union of those instructions' intervals inside the traced
    stretch over the steps the stretch counted.  Every instruction of the
    stretch is taken, not those inside the train program's events on the
    ``XLA Modules`` line: with a while loop of kernels in the step that
    event ends before its instructions do (PERF.md section 7).  None where
    there is no trace, no join, or no such scope in the program."""
    t, window, traced = run.tables, run.trace_window, run.traced
    if t is None or window is None or not t.devices or not run.scopes \
            or traced is None or not traced.steps:
        return None
    ns = trace.scope_busy_ns(t.devices[0].ops, window, run.scopes, scope)
    return ns / traced.steps / 1e6 if ns else None
