"""tpulint suite: paired good/bad fixtures per checker + repo smoke.

Contract (ISSUE 5 / docs/design.md §12): every checker has a failing
fixture producing EXACTLY its expected finding and a passing fixture
producing zero; the whole-repo run matches the committed baseline
exactly (no stale entries, no new findings); the CLI enforces the gate
semantics tier1.sh relies on — and does it all without importing jax.
"""

import json
import os
import subprocess
import sys

import pytest

from theanompi_tpu.analysis import core
from theanompi_tpu.analysis.checkers import schema_drift as sd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "scripts", "lint.py")


def lint_snippet(tmp_path, name, code, only):
    (tmp_path / name).write_text(code)
    return core.run_lint(str(tmp_path), paths=[name], only=[only])


# ---------------------------------------------------------------------------
# trace-purity
# ---------------------------------------------------------------------------

TRACE_BAD = """
import time
import numpy as np
import jax
from jax import lax

def build(model):
    def body(carry, x):
        t = time.time()
        carry = carry + np.random.rand()
        print("mid-trace")
        if carry:
            carry = carry + x.item()
        return carry, jax.device_get(x)
    return lax.scan(body, 0.0, model)
"""

TRACE_GOOD = """
import time
import numpy as np
import jax
from jax import lax

def host_loop(model):
    # host side: clocks / numpy RNG / print are all fine here
    t = time.time()
    noise = np.random.rand()
    print("host", t)

    def body(carry, x):
        return carry + x, x
    out, _ = lax.scan(body, noise, model)
    return out, time.time() - t
"""


def test_trace_purity_bad_fixture(tmp_path):
    found = lint_snippet(tmp_path, "bad.py", TRACE_BAD, "trace-purity")
    msgs = [f.message for f in found]
    assert len(found) == 6, msgs
    assert any("time.time" in m for m in msgs)
    assert any("numpy.random" in m for m in msgs)
    assert any("print" in m for m in msgs)
    assert any("tracer-typed name `carry`" in m for m in msgs)
    assert any(".item()" in m for m in msgs)
    assert any("jax.device_get" in m for m in msgs)
    assert all(f.check == "trace-purity" for f in found)


def test_trace_purity_good_fixture(tmp_path):
    assert lint_snippet(tmp_path, "good.py", TRACE_GOOD,
                        "trace-purity") == []


def test_trace_purity_keyword_passed_body(tmp_path):
    """A scan body passed by keyword (`lax.scan(f=body, ...)`) is traced
    all the same."""
    code = (
        "import time\n"
        "from jax import lax\n"
        "def build(xs):\n"
        "    def body(carry, x):\n"
        "        t = time.time()\n"
        "        return carry, x\n"
        "    return lax.scan(f=body, init=0.0, xs=xs)\n")
    found = lint_snippet(tmp_path, "x.py", code, "trace-purity")
    assert len(found) == 1 and "time.time" in found[0].message


def test_trace_purity_decorator_jit(tmp_path):
    """@jax.jit / @functools.partial(jax.jit, ...) trace the decorated
    function — the repo's pallas kernels use exactly this shape."""
    code = (
        "import functools\n"
        "import time\n"
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    t = time.time()\n"
        "    return x\n"
        "@functools.partial(jax.jit, static_argnums=(1,))\n"
        "def g(x, n):\n"
        "    print(n)\n"
        "    return x\n")
    found = lint_snippet(tmp_path, "x.py", code, "trace-purity")
    msgs = [f.message for f in found]
    assert len(found) == 2, msgs
    assert any("time.time" in m for m in msgs)
    assert any("print" in m for m in msgs)


def test_trace_purity_catches_injection_into_real_steps(tmp_path):
    """The acceptance scenario: a time.time() injected into the repo's
    actual microbatch scan body must fail the gate."""
    src = open(os.path.join(REPO, "theanompi_tpu", "parallel",
                            "steps.py")).read()
    bad = src.replace(
        "    def body(carry, mb):\n"
        "        acc_g, acc_c, acc_e, bn, key = carry",
        "    def body(carry, mb):\n"
        "        t0 = time.time()\n"
        "        acc_g, acc_c, acc_e, bn, key = carry").replace(
        "import functools", "import functools\nimport time")
    assert bad != src, "steps.py scan body changed shape; update fixture"
    # keep the repo-relative package shape so the resolver sees the
    # same relative imports steps.py really uses
    pkg = tmp_path / "theanompi_tpu" / "parallel"
    pkg.mkdir(parents=True)
    (pkg / "steps.py").write_text(bad)
    found = core.run_lint(str(tmp_path),
                          paths=["theanompi_tpu/parallel/steps.py"],
                          only=["trace-purity"])
    assert len(found) == 1 and "time.time" in found[0].message


# ---------------------------------------------------------------------------
# rng-discipline
# ---------------------------------------------------------------------------

RNG_BAD = """
import jax

def draw(key):
    a = jax.random.normal(key, (4,))
    b = jax.random.uniform(key, (4,))
    return a + b
"""

RNG_GOOD = """
import jax

def draw(key, count):
    key, sub = jax.random.split(key)
    a = jax.random.normal(sub, (4,))
    # fold_in with distinct data is the sanctioned multi-stream pattern
    b = jax.random.uniform(jax.random.fold_in(key, 1), (4,))
    c = jax.random.normal(jax.random.fold_in(key, 2), (4,))
    for i in range(3):
        step = jax.random.fold_in(key, count + i)
        a = a + jax.random.normal(step, (4,))
    return a + b + c
"""

RNG_BAD_LOOP = """
import jax

def draw(key, n):
    out = 0.0
    for i in range(n):
        out = out + jax.random.normal(key, ())
    return out
"""


def test_rng_discipline_bad_fixture(tmp_path):
    found = lint_snippet(tmp_path, "bad.py", RNG_BAD, "rng-discipline")
    assert len(found) == 1
    assert "key `key` consumed again" in found[0].message
    assert found[0].check == "rng-discipline"


def test_rng_discipline_loop_fixture(tmp_path):
    found = lint_snippet(tmp_path, "badloop.py", RNG_BAD_LOOP,
                         "rng-discipline")
    assert len(found) == 1
    assert "inside a loop" in found[0].message


def test_rng_discipline_good_fixture(tmp_path):
    assert lint_snippet(tmp_path, "good.py", RNG_GOOD,
                        "rng-discipline") == []


def test_rng_discipline_exclusive_arms_are_not_reuse(tmp_path):
    """Only one arm of a conditional expression (or a short-circuit
    chain) ever runs — a draw in each is not key reuse."""
    code = (
        "import jax\n"
        "def draw(key, c, d):\n"
        "    a = jax.random.normal(key) if c else jax.random.uniform(key)\n"
        "    b = d or jax.random.normal(key)\n"
        "    return a, b\n")
    # NOTE: `key` genuinely IS consumed on both lines 3 and 4 here —
    # but each consumption is inside an exclusive/conditional position,
    # so neither pairing is provably reached twice
    assert lint_snippet(tmp_path, "x.py", code, "rng-discipline") == []


def test_rng_discipline_nested_def_in_loop_is_own_scope(tmp_path):
    """A helper defined inside a loop gets fresh key parameters per
    call — its draws are not 'consumed inside a loop'."""
    code = (
        "import jax\n"
        "def outer(n):\n"
        "    fns = []\n"
        "    for i in range(n):\n"
        "        if i:\n"
        "            def inner(k2):\n"
        "                return jax.random.normal(k2)\n"
        "            fns.append(inner)\n"
        "    return fns\n")
    assert lint_snippet(tmp_path, "x.py", code, "rng-discipline") == []


def test_rng_discipline_both_arms_then_reuse_is_flagged(tmp_path):
    """A key consumed in BOTH arms of a conditional IS definitely
    consumed — a later unconditional draw is reuse."""
    code = (
        "import jax\n"
        "def draw(key, c):\n"
        "    a = jax.random.normal(key) if c else jax.random.uniform(key)\n"
        "    b = jax.random.normal(key)\n"
        "    return a, b\n")
    found = lint_snippet(tmp_path, "x.py", code, "rng-discipline")
    assert len(found) == 1 and found[0].line == 4


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

DONATION_BAD = """
import jax

def run(state, batch):
    step = jax.jit(lambda s, b: s, donate_argnums=(0,))
    new_state = step(state, batch)
    return new_state, state["params"]
"""

DONATION_GOOD = """
import jax

def run(state, batch):
    step = jax.jit(lambda s, b: s, donate_argnums=(0,))
    # the sanctioned shape: consume and rebind in one statement
    state = step(state, batch)
    return state, state["params"]
"""


def test_donation_safety_bad_fixture(tmp_path):
    found = lint_snippet(tmp_path, "bad.py", DONATION_BAD,
                         "donation-safety")
    assert len(found) == 1
    assert "`state` read after being donated" in found[0].message


def test_donation_safety_good_fixture(tmp_path):
    assert lint_snippet(tmp_path, "good.py", DONATION_GOOD,
                        "donation-safety") == []


def test_donation_safety_argnames_maps_through_lambda(tmp_path):
    """donate_argnames against an inline lambda maps names to slots —
    the donated arg is flagged, the non-donated one is not."""
    code = (
        "import jax\n"
        "def run(state, batch):\n"
        "    step = jax.jit(lambda b, s: s, donate_argnames='s')\n"
        "    out = step(batch, state)\n"
        "    return out, batch.shape, state['params']\n")
    found = lint_snippet(tmp_path, "x.py", code, "donation-safety")
    assert len(found) == 1
    assert "`state` read after being donated" in found[0].message


def test_donation_safety_module_level_jit_seen_in_functions(tmp_path):
    """`f = jax.jit(g, donate_argnums=0)` at module level, called inside
    a function — the common layout — must still flag read-after-donate."""
    code = (
        "import jax\n"
        "def g(s):\n"
        "    return s\n"
        "f = jax.jit(g, donate_argnums=0)\n"
        "def h(state):\n"
        "    out = f(state)\n"
        "    return out, state['params']\n")
    found = lint_snippet(tmp_path, "x.py", code, "donation-safety")
    assert len(found) == 1
    assert "`state` read after being donated" in found[0].message


def test_donation_safety_unresolvable_spec_is_skipped(tmp_path):
    """A donation spec the checker cannot resolve statically (argnames
    against an opaque callee, non-literal argnums) must not guess an
    index — guessing flags the WRONG argument."""
    code = (
        "import jax\n"
        "def run(f, state, batch, idx):\n"
        "    step = jax.jit(f, donate_argnames='s')\n"
        "    step2 = jax.jit(f, donate_argnums=idx)\n"
        "    out = step(batch, state)\n"
        "    out2 = step2(batch, state)\n"
        "    return out, out2, batch.shape\n")
    assert lint_snippet(tmp_path, "x.py", code, "donation-safety") == []


# ---------------------------------------------------------------------------
# shard-rebuild-dominance
# ---------------------------------------------------------------------------

USHARD_BAD = """
from theanompi_tpu.parallel.update_sharding import slice_chunk

def step(flat, rank, chunk, lr, grads):
    my_p = slice_chunk(flat, rank, chunk)
    new_p = my_p - lr * grads
    return new_p
"""

USHARD_GOOD = """
from theanompi_tpu.parallel.update_sharding import (all_gather_chunks,
                                                    slice_chunk)

def step(flat, rank, chunk, lr, grads):
    my_p = slice_chunk(flat, rank, chunk)
    new_p = my_p - lr * grads
    full = all_gather_chunks(new_p, "workers")
    return full
"""

USHARD_BRANCH_BAD = """
from theanompi_tpu.parallel.update_sharding import (all_gather_chunks,
                                                    slice_chunk)

def step(flat, rank, chunk, gather):
    my_p = slice_chunk(flat, rank, chunk)
    if gather:
        my_p = all_gather_chunks(my_p, "workers")
    return my_p
"""

USHARD_EXEMPT_GOOD = """
from theanompi_tpu.parallel.update_sharding import shard_tree

def reshard_extra(extra, plan, rank):
    # a named producer helper: returning chunks is its JOB
    return shard_tree(extra, plan, rank)
"""


def test_shard_rebuild_bad_fixture(tmp_path):
    """A chunk laundered through arithmetic and returned without its
    rebuild: under donate_argnums the caller's full buffer silently
    becomes a 1/N local shard."""
    found = lint_snippet(tmp_path, "bad.py", USHARD_BAD,
                         "shard-rebuild-dominance")
    assert len(found) == 1
    assert "`new_p` holds a worker-local shard" in found[0].message
    assert "allgather rebuild" in found[0].message


def test_shard_rebuild_good_fixture(tmp_path):
    assert lint_snippet(tmp_path, "good.py", USHARD_GOOD,
                        "shard-rebuild-dominance") == []


def test_shard_rebuild_branch_does_not_dominate(tmp_path):
    """A rebuild INSIDE one arm of an `if` does not dominate the return
    — the no-gather path still escapes the shard."""
    found = lint_snippet(tmp_path, "x.py", USHARD_BRANCH_BAD,
                         "shard-rebuild-dominance")
    assert len(found) == 1
    assert "`my_p`" in found[0].message


def test_shard_rebuild_exempts_named_producers(tmp_path):
    """The schema's own producer helpers (shard_*/reshard_*/slice_*/
    chunk_*) return chunks by design — never flagged."""
    assert lint_snippet(tmp_path, "x.py", USHARD_EXEMPT_GOOD,
                        "shard-rebuild-dominance") == []


# ---------------------------------------------------------------------------
# compat-boundary
# ---------------------------------------------------------------------------

COMPAT_BAD = """
import jax
from jax import lax
from jax.experimental.shard_map import shard_map

def build(f, mesh):
    g = jax.shard_map
    h = lax.pvary
    return shard_map, g, h
"""

COMPAT_GOOD = """
import jax
from theanompi_tpu.jax_compat import shard_map

def build(f, mesh, specs):
    return jax.jit(shard_map(f, mesh=mesh, in_specs=specs,
                             out_specs=specs))
"""


def test_compat_boundary_bad_fixture(tmp_path):
    found = lint_snippet(tmp_path, "bad.py", COMPAT_BAD, "compat-boundary")
    msgs = [f.message for f in found]
    assert len(found) == 3, msgs
    assert any("jax.experimental.shard_map" in m for m in msgs)
    assert any("jax.shard_map" in m for m in msgs)
    assert any("jax.lax.pvary" in m for m in msgs)


def test_compat_boundary_good_fixture(tmp_path):
    assert lint_snippet(tmp_path, "good.py", COMPAT_GOOD,
                        "compat-boundary") == []


def test_compat_boundary_exempts_the_shim(tmp_path):
    found = lint_snippet(tmp_path, "jax_compat.py", COMPAT_BAD,
                         "compat-boundary")
    assert found == []


def test_compat_boundary_catches_from_import_of_banned_name(tmp_path):
    """`from jax import shard_map` binds the banned name with no
    Attribute node — the import itself must be the finding."""
    code = ("from jax import shard_map\n"
            "from jax.lax import pvary\n"
            "from jax import lax\n")        # `lax` itself is fine
    found = lint_snippet(tmp_path, "x.py", code, "compat-boundary")
    msgs = [f.message for f in found]
    assert len(found) == 2, msgs
    assert any("jax.shard_map" in m for m in msgs)
    assert any("jax.lax.pvary" in m for m in msgs)


# ---------------------------------------------------------------------------
# telemetry-hot-path
# ---------------------------------------------------------------------------

TELEMETRY_BAD = """
from theanompi_tpu.utils import telemetry

def hot_loop(n):
    tm = telemetry.active()
    for i in range(n):
        tm.counter("iters")
"""

TELEMETRY_GOOD = """
from theanompi_tpu.utils import telemetry

def hot_loop(n, rec=None):
    tm = telemetry.active()
    for i in range(n):
        if tm.enabled:
            tm.counter("iters")
        if rec and tm.enabled:
            tm.observe("loop.i", i)
"""


def test_telemetry_hot_path_bad_fixture(tmp_path):
    # checker keys on hot-path basenames — name the fixture worker.py
    found = lint_snippet(tmp_path, "worker.py", TELEMETRY_BAD,
                         "telemetry-hot-path")
    assert len(found) == 1
    assert "unguarded telemetry call `tm.counter" in found[0].message


def test_telemetry_hot_path_good_fixture(tmp_path):
    assert lint_snippet(tmp_path, "worker.py", TELEMETRY_GOOD,
                        "telemetry-hot-path") == []


TELEMETRY_RING = """
from theanompi_tpu.utils import telemetry

def hot_loop(n, q):
    tm = telemetry.active()
    for i in range(n):
        with telemetry.span("load.dequeue") as sp:
            sp.batch = q.get()
        telemetry.count("input.dequeues")
        %s
"""


def test_telemetry_hot_path_ring_needs_no_guard_registry_still_does(tmp_path):
    """PR 25: ``telemetry.span``/``count`` are always on by contract (a
    bounded cost, not a guard); a registry call beside them is still a
    finding unless it sits under ``.enabled``."""
    from theanompi_tpu.analysis.checkers import telemetry_hot_path as thp
    assert {"span", "count"} <= thp.ALWAYS_ON
    assert not thp.ALWAYS_ON & thp.RECORDING
    assert lint_snippet(tmp_path, "prefetch.py", TELEMETRY_RING % "pass",
                        "telemetry-hot-path") == []
    found = lint_snippet(tmp_path, "prefetch.py",
                         TELEMETRY_RING % 'tm.counter("prefetch.dequeues")',
                         "telemetry-hot-path")
    assert len(found) == 1
    assert "unguarded telemetry call `tm.counter" in found[0].message
    found = lint_snippet(tmp_path, "prefetch.py",
                         TELEMETRY_RING % 'telemetry.active().event("x")',
                         "telemetry-hot-path")
    assert len(found) == 0 or "event" in found[0].message


TRACING_BAD = """
from theanompi_tpu.utils import tracing

def island_loop(n, center):
    tr = tracing.active()
    for i in range(n):
        rnd = tr.begin("round", count=i)
        rnd.end()
"""

TRACING_GOOD = """
from theanompi_tpu.utils import tracing

def island_loop(n, center):
    tr = tracing.active()
    for i in range(n):
        rnd = tr.begin("round", count=i) if tr.enabled else None
        if rnd is not None:
            rnd.end()
"""

EMIT_BAD = """
from theanompi_tpu.utils import telemetry, tracing

def request(trace, op):
    tm = telemetry.active()
    tracing.emit_wire_span(tm, trace, op, dt=0.1)
"""

EMIT_GOOD = """
from theanompi_tpu.utils import telemetry, tracing

def request(trace, op):
    tm = telemetry.active()
    if trace is not None and tm.enabled:
        tracing.emit_wire_span(tm, trace, op, dt=0.1)
"""


def test_span_emission_unguarded_begin_is_a_finding(tmp_path):
    """Round 16: the span API is part of the hot-path contract — an
    unguarded `Tracer.begin` in a hot file (async_easgd joined the set)
    is a finding; the `... if tr.enabled else None` idiom is the guard."""
    found = lint_snippet(tmp_path, "async_easgd.py", TRACING_BAD,
                         "telemetry-hot-path")
    assert len(found) == 1
    assert "tr.begin" in found[0].message


def test_span_emission_guarded_begin_is_clean(tmp_path):
    assert lint_snippet(tmp_path, "async_easgd.py", TRACING_GOOD,
                        "telemetry-hot-path") == []


def test_module_level_emit_helpers_are_recording_calls(tmp_path):
    """`tracing.emit_wire_span(...)` resolves to the tracing module —
    unguarded in wire.py (now a hot file) it is a finding; the
    `trace is not None and tm.enabled` conjunction guards."""
    found = lint_snippet(tmp_path, "wire.py", EMIT_BAD,
                         "telemetry-hot-path")
    assert len(found) == 1
    assert "emit_wire_span" in found[0].message
    assert lint_snippet(tmp_path, "wire.py", EMIT_GOOD,
                        "telemetry-hot-path") == []


FLEETMON_BAD = """
from theanompi_tpu.utils import fleetmon, telemetry

def eval_loop(alerts):
    tm = telemetry.active()
    for a in alerts:
        fleetmon.emit_alert(tm, a)
"""

FLEETMON_GOOD = """
from theanompi_tpu.utils import fleetmon, telemetry

def eval_loop(alerts):
    tm = telemetry.active()
    for a in alerts:
        if tm.enabled:
            fleetmon.emit_alert(tm, a)
"""


def test_fleetmon_emission_api_is_a_recording_call(tmp_path):
    """Round 18: the checker knows the fleet-health emission API —
    `fleetmon.emit_alert(...)` unguarded in a hot file (fleetmon.py
    itself joined the set) is a finding; the enabled guard clears it."""
    found = lint_snippet(tmp_path, "fleetmon.py", FLEETMON_BAD,
                         "telemetry-hot-path")
    assert len(found) == 1
    assert "emit_alert" in found[0].message
    assert lint_snippet(tmp_path, "fleetmon.py", FLEETMON_GOOD,
                        "telemetry-hot-path") == []


def test_telemetry_hot_path_only_applies_to_hot_files(tmp_path):
    # the same unguarded call in a non-hot-path file is NOT a finding
    assert lint_snippet(tmp_path, "report_tool.py", TELEMETRY_BAD,
                        "telemetry-hot-path") == []


def test_telemetry_hot_path_early_return_guard(tmp_path):
    """`if not tm.enabled: return` dominates the rest of the block —
    the most common Python guard shape must not be flagged."""
    code = (
        "from theanompi_tpu.utils import telemetry\n"
        "def hot_loop(n):\n"
        "    tm = telemetry.active()\n"
        "    if not tm.enabled:\n"
        "        return\n"
        "    tm.counter('iters')\n"
        "    tm.observe('n', n)\n")
    assert lint_snippet(tmp_path, "worker.py", code,
                        "telemetry-hot-path") == []


def test_telemetry_hot_path_elif_guard(tmp_path):
    """An `elif tm.enabled:` arm guards its own body."""
    code = (
        "from theanompi_tpu.utils import telemetry\n"
        "def hot_loop(rec):\n"
        "    tm = telemetry.active()\n"
        "    if rec:\n"
        "        pass\n"
        "    elif tm.enabled:\n"
        "        tm.counter('iters')\n")
    assert lint_snippet(tmp_path, "worker.py", code,
                        "telemetry-hot-path") == []


def test_telemetry_hot_path_or_guard_is_not_dominance(tmp_path):
    """`if other or tm.enabled:` reaches its body with telemetry off —
    mentioning `.enabled` somewhere is not domination."""
    code = (
        "from theanompi_tpu.utils import telemetry\n"
        "def hot_loop(other):\n"
        "    tm = telemetry.active()\n"
        "    if other or tm.enabled:\n"
        "        tm.counter('iters')\n"
        "    if tm.enabled or other.enabled:\n"
        "        tm.gauge('x', 1)\n")    # every alternative guards: ok
    found = lint_snippet(tmp_path, "worker.py", code,
                         "telemetry-hot-path")
    assert len(found) == 1 and "tm.counter" in found[0].message


def test_telemetry_hot_path_early_return_without_exit_still_flags(tmp_path):
    """A negated-enabled If whose body does NOT end control flow must
    not guard what follows."""
    code = (
        "from theanompi_tpu.utils import telemetry\n"
        "def hot_loop(n):\n"
        "    tm = telemetry.active()\n"
        "    if not tm.enabled:\n"
        "        n = 0\n"
        "    tm.counter('iters')\n")
    found = lint_snippet(tmp_path, "worker.py", code,
                         "telemetry-hot-path")
    assert len(found) == 1


# ---------------------------------------------------------------------------
# schema-drift
# ---------------------------------------------------------------------------

def test_schema_drift_good_live_modules():
    """The real modules must be in sync (this IS the absorbed guard)."""
    from theanompi_tpu.utils import recorder, telemetry
    assert sd.live_drift_errors(recorder, telemetry) == []


def test_schema_drift_span_vocabulary_good_and_bad():
    """PR 25: the span ring's vocabulary is guarded in both directions."""
    from theanompi_tpu.utils import telemetry
    assert sd.span_vocabulary_errors(telemetry) == []
    site = ("from ..utils import telemetry\n"
            "def f(q, name):\n"
            "    with telemetry.span('load.dequeue'):\n"
            "        pass\n"
            "    with telemetry.span('made.up'):\n"
            "        pass\n"
            "    telemetry.count('input.dequeues')\n"
            "    telemetry.count(name)\n")
    errors = sd.span_vocabulary_errors(telemetry, sources={"x.py": site})
    msgs = [m for _, m in errors]
    assert any("'made.up') is not in telemetry.SPANS" in m for m in msgs)
    assert any("needs a literal name" in m for m in msgs)
    # every declared name without a site in these sources is reported too
    assert any("lists 'train.call' but no telemetry.span" in m
               for m in msgs)
    assert any("lists 'input.bytes_put' but no telemetry.count" in m
               for m in msgs)
    # compile.* spans are written by the monitoring listener, not a site
    assert not any("'compile.xla'" in m for m in msgs)

    class Clash:
        PHASES = telemetry.PHASES
        SPANS = telemetry.SPANS + ("train", "bogus.head")
        COUNTS = telemetry.COUNTS
        COMPILE_EVENTS = telemetry.COMPILE_EVENTS

    msgs = [m for _, m in sd.span_vocabulary_errors(Clash)]
    assert any("repeats recorder phases ['train']" in m for m in msgs)
    assert any("'bogus.head'" in m and "neither a phase" in m for m in msgs)


def test_schema_drift_bad_fixture(monkeypatch):
    """A drifted SECTIONS list must produce a finding."""
    from theanompi_tpu.utils import recorder, telemetry

    class FakeRecorder:
        SECTIONS = tuple(telemetry.PHASES) + ("rogue",)
        RECORD_KEYS = recorder.RECORD_KEYS
        Recorder = recorder.Recorder

    errors = sd.live_drift_errors(FakeRecorder, telemetry)
    assert any("SECTIONS" in msg for _, msg in errors)


def test_schema_drift_tracing_probe_good_and_bad():
    """Round 16: the live tracing probe — real modules in sync; a report
    stand-in that cannot assemble spans (or tracks the wrong vocabulary)
    fails the gate; a span emitter drifting from SPAN_FIELDS fails."""
    import importlib.util

    from theanompi_tpu.utils import telemetry, tracing
    report_path = os.path.join(REPO, "scripts", "telemetry_report.py")
    spec = importlib.util.spec_from_file_location("_sd_test_report",
                                                  report_path)
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    assert sd.tracing_schema_errors(tracing, telemetry, report) == []

    class BlindReport:
        # tracks neither span nor statusz, assembles nothing
        TRACKED_EVENTS = ("phase",)
        TRACE_COMPONENTS = ()

        @staticmethod
        def assemble_traces(events):
            return []

    errors = sd.tracing_schema_errors(tracing, telemetry, BlindReport)
    msgs = [m for _, m in errors]
    assert any("TRACKED_EVENTS" in m for m in msgs)
    assert any("round(s)" in m for m in msgs), msgs
    assert any("TRACE_COMPONENTS" in m for m in msgs)


# ---------------------------------------------------------------------------
# framework behaviors: suppression, baseline, runner
# ---------------------------------------------------------------------------

def test_inline_suppression(tmp_path):
    code = RNG_BAD.replace(
        "    b = jax.random.uniform(key, (4,))",
        "    b = jax.random.uniform(key, (4,))"
        "  # tpulint: disable=rng-discipline")
    assert lint_snippet(tmp_path, "bad.py", code, "rng-discipline") == []


def test_previous_line_suppression(tmp_path):
    code = RNG_BAD.replace(
        "    b = jax.random.uniform(key, (4,))",
        "    # tpulint: disable=rng-discipline\n"
        "    b = jax.random.uniform(key, (4,))")
    assert lint_snippet(tmp_path, "bad.py", code, "rng-discipline") == []


def test_suppression_is_check_specific(tmp_path):
    code = RNG_BAD.replace(
        "    b = jax.random.uniform(key, (4,))",
        "    b = jax.random.uniform(key, (4,))"
        "  # tpulint: disable=trace-purity")
    assert len(lint_snippet(tmp_path, "bad.py", code,
                            "rng-discipline")) == 1


def test_baseline_roundtrip_deterministic(tmp_path):
    (tmp_path / "bad.py").write_text(RNG_BAD)
    findings = core.run_lint(str(tmp_path), paths=["bad.py"],
                             only=["rng-discipline"])
    bl = tmp_path / "baseline.json"
    core.save_baseline(str(bl), findings)
    first = bl.read_text()
    entries = core.load_baseline(str(bl))
    assert entries[0]["justification"] == "TODO: justify"
    # justification edits survive a regeneration; output is byte-stable
    entries[0]["justification"] = "grandfathered: fixture"
    core.save_baseline(str(bl), findings, entries)
    again = core.load_baseline(str(bl))
    assert again[0]["justification"] == "grandfathered: fixture"
    core.save_baseline(str(bl), findings, again)
    assert json.loads(bl.read_text())["entries"] == again
    assert bl.read_text() != first  # only the justification changed
    new, matched, stale = core.compare_baseline(findings, again)
    assert new == [] and stale == [] and len(matched) == 1


def test_baseline_matches_on_message_not_line(tmp_path):
    (tmp_path / "bad.py").write_text(RNG_BAD)
    findings = core.run_lint(str(tmp_path), paths=["bad.py"],
                             only=["rng-discipline"])
    moved = [dict(check=f.check, path=f.path, line=f.line + 40,
                  message=f.message, justification="ok")
             for f in findings]
    new, matched, stale = core.compare_baseline(findings, moved)
    assert new == [] and stale == []


def test_parse_error_is_a_finding(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    found = core.run_lint(str(tmp_path), paths=["broken.py"],
                          only=["rng-discipline"])
    assert len(found) == 1 and found[0].check == "parse-error"


# ---------------------------------------------------------------------------
# whole-repo smoke + CLI gate semantics
# ---------------------------------------------------------------------------

def test_repo_matches_committed_baseline_exactly():
    """The committed baseline is exact: no new findings, no stale
    entries, and every entry carries a real justification."""
    findings = core.run_lint(REPO)
    entries = core.load_baseline(
        os.path.join(REPO, core.BASELINE_NAME))
    new, matched, stale = core.compare_baseline(findings, entries)
    assert new == [], [f.render() for f in new]
    assert stale == [], stale
    assert all(not e["justification"].startswith("TODO")
               for e in entries), "baseline entries need justifications"


def test_cli_runs_clean_without_jax():
    """scripts/lint.py on the repo: exit 0, and jax must never load
    (the synthetic-parent bootstrap contract)."""
    env = dict(os.environ, TPULINT_ASSERT_NO_JAX="1")
    proc = subprocess.run(
        [sys.executable, LINT, "--check-baseline"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_fails_on_new_finding(tmp_path):
    (tmp_path / "steps.py").write_text(TRACE_BAD)
    proc = subprocess.run(
        [sys.executable, LINT, "--root", str(tmp_path), "steps.py"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "trace-purity" in proc.stdout


def test_cli_check_baseline_fails_on_stale_entry(tmp_path):
    (tmp_path / "clean.py").write_text("x = 1\n")
    bl = tmp_path / core.BASELINE_NAME
    bl.write_text(json.dumps({"version": 1, "entries": [{
        "check": "rng-discipline", "path": "gone.py", "line": 1,
        "message": "key `k` consumed again", "justification": "stale"}]}))
    base = [sys.executable, LINT, "--root", str(tmp_path)]
    # full-repo default mode: stale entry is a warning, not a failure
    proc = subprocess.run(base, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "stale" in proc.stderr
    # tier-1 mode: the committed baseline must be exact
    proc = subprocess.run(base + ["--check-baseline"],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1


def test_cli_rejects_nonexistent_explicit_path(tmp_path):
    """A typo'd path must error (exit 2), not report 'linted clean'."""
    proc = subprocess.run(
        [sys.executable, LINT, "--root", str(tmp_path), "no_such.py"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "no such path" in proc.stderr


def test_cli_nags_on_todo_justification(tmp_path):
    """A TODO-justified baseline entry nags on every run, not only on
    the --update-baseline that wrote it."""
    (tmp_path / "bad.py").write_text(RNG_BAD)
    findings = core.run_lint(str(tmp_path), paths=["bad.py"],
                             only=["rng-discipline"])
    core.save_baseline(str(tmp_path / core.BASELINE_NAME), findings)
    proc = subprocess.run(
        [sys.executable, LINT, "--root", str(tmp_path), "bad.py",
         "--only", "rng-discipline"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "needs a justification" in proc.stderr


def test_cli_json_output(tmp_path):
    (tmp_path / "bad.py").write_text(RNG_BAD)
    proc = subprocess.run(
        [sys.executable, LINT, "--root", str(tmp_path), "bad.py",
         "--json"], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    out = json.loads(proc.stdout)
    assert out["new"] and out["new"][0]["check"] == "rng-discipline"


def test_cli_list_checks():
    proc = subprocess.run(
        [sys.executable, LINT, "--list-checks"], capture_output=True,
        text=True, timeout=120)
    assert proc.returncode == 0
    for name in ("trace-purity", "rng-discipline", "donation-safety",
                 "compat-boundary", "telemetry-hot-path", "schema-drift"):
        assert name in proc.stdout


def test_cli_update_baseline_refuses_partial_run(tmp_path):
    """A partial run sees a slice of the findings; writing the baseline
    from it would silently drop every entry outside the slice."""
    (tmp_path / "bad.py").write_text(RNG_BAD)
    proc = subprocess.run(
        [sys.executable, LINT, "--root", str(tmp_path), "bad.py",
         "--update-baseline"], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "full run" in proc.stderr
    assert not (tmp_path / core.BASELINE_NAME).exists()


def test_cli_unknown_checker_is_usage_error():
    proc = subprocess.run(
        [sys.executable, LINT, "--only", "no-such-check"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2


def test_project_only_run_skips_repo_parse(tmp_path):
    """`--only schema-drift` reads no files: an unrelated syntax error
    must not turn the shim's in-sync exit 0 into a bogus failure."""
    (tmp_path / "broken.py").write_text("x = (\n")
    found = core.run_lint(str(tmp_path), paths=["broken.py"],
                          only=["schema-drift"])
    assert [f for f in found if f.check == "parse-error"] == []


def test_project_level_findings_honor_suppression(tmp_path):
    """The suppression contract covers check_project findings too."""

    class _ProjProbe(core.Checker):
        name = "proj-probe"
        description = "test-only"
        reads_files = True

        def check_project(self, files):
            return [core.Finding(self.name, "probe.py", 2, 0, "hit")]

    core.CHECKERS[_ProjProbe.name] = _ProjProbe()
    try:
        (tmp_path / "probe.py").write_text(
            "x = 1\ny = 2  # tpulint: disable=proj-probe\n")
        assert core.run_lint(str(tmp_path), paths=["probe.py"],
                             only=["proj-probe"]) == []
        (tmp_path / "probe.py").write_text("x = 1\ny = 2\n")
        found = core.run_lint(str(tmp_path), paths=["probe.py"],
                              only=["proj-probe"])
        assert len(found) == 1 and found[0].check == "proj-probe"
    finally:
        del core.CHECKERS[_ProjProbe.name]


def test_shim_still_guards_schema(tmp_path):
    """The deprecated check_schema_drift.py shim execs the lint CLI and
    keeps the old exit-code contract."""
    shim = os.path.join(REPO, "scripts", "check_schema_drift.py")
    proc = subprocess.run([sys.executable, shim], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "deprecated" in proc.stderr


# ---------------------------------------------------------------------------
# whole-program engine: cross-file closure + call-graph resolution
# ---------------------------------------------------------------------------

def _write_pkg(tmp_path, files):
    """Lay out a package tree and return the repo-relative paths."""
    rels = []
    for rel, code in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(code)
        rels.append(rel)
    return rels


def test_trace_purity_cross_file_host_clock(tmp_path):
    """The ISSUE-6 motivating case: a host clock TWO modules away from
    the scan body must be visible to the closure."""
    rels = _write_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/clock.py": (
            "import time\n"
            "def stamp():\n"
            "    return time.time()\n"),
        "pkg/mid.py": (
            "from .clock import stamp\n"
            "def helper(x):\n"
            "    return x + stamp()\n"),
        "pkg/body.py": (
            "from jax import lax\n"
            "from .mid import helper\n"
            "def build(xs):\n"
            "    def body(c, x):\n"
            "        return helper(c), x\n"
            "    return lax.scan(body, 0.0, xs)\n"),
    })
    found = core.run_lint(str(tmp_path), paths=rels,
                          only=["trace-purity"])
    assert len(found) == 1, [f.render() for f in found]
    assert found[0].path == "pkg/clock.py"
    assert "time.time" in found[0].message


def test_trace_purity_cross_file_good(tmp_path):
    """The same helper chain WITHOUT the host clock stays silent, and a
    host-side caller of the clock helper is not flagged."""
    rels = _write_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/clock.py": (
            "import time\n"
            "def stamp():\n"
            "    return time.time()\n"),
        "pkg/body.py": (
            "from jax import lax\n"
            "from .clock import stamp\n"
            "def host_loop(xs):\n"
            "    t0 = stamp()\n"         # host side: fine
            "    def body(c, x):\n"
            "        return c + x, x\n"
            "    return lax.scan(body, 0.0, xs), stamp() - t0\n"),
    })
    assert core.run_lint(str(tmp_path), paths=rels,
                         only=["trace-purity"]) == []


def test_trace_purity_method_override_reached_cross_file(tmp_path):
    """`self.exchange_body` passed to shard_map must close over a
    SUBCLASS override defined in another file."""
    rels = _write_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/base.py": (
            "from theanompi_tpu.jax_compat import shard_map\n"
            "class Base:\n"
            "    def build(self, mesh, spec):\n"
            "        return shard_map(self.exchange_body, mesh=mesh,\n"
            "                         in_specs=(spec,), out_specs=spec)\n"
            "    def exchange_body(self, state):\n"
            "        return state\n"),
        "pkg/sub.py": (
            "import time\n"
            "from .base import Base\n"
            "class Sub(Base):\n"
            "    def exchange_body(self, state):\n"
            "        t = time.time()\n"
            "        return state\n"),
    })
    found = core.run_lint(str(tmp_path), paths=rels,
                          only=["trace-purity"])
    assert len(found) == 1 and found[0].path == "pkg/sub.py", \
        [f.render() for f in found]


def test_rng_discipline_cross_file_reuse(tmp_path):
    """A helper that spends its key parameter makes two same-key calls
    of it reuse — even when the helper lives in another module."""
    rels = _write_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/draws.py": (
            "import jax\n"
            "def draw(key, shape):\n"
            "    return jax.random.normal(key, shape)\n"),
        "pkg/use.py": (
            "from .draws import draw\n"
            "def run(key):\n"
            "    a = draw(key, (4,))\n"
            "    b = draw(key, (4,))\n"
            "    return a + b\n"),
    })
    found = core.run_lint(str(tmp_path), paths=rels,
                          only=["rng-discipline"])
    assert len(found) == 1 and found[0].path == "pkg/use.py", \
        [f.render() for f in found]
    assert "key `key` consumed again" in found[0].message


def test_rng_discipline_cross_file_fold_in_helper_ok(tmp_path):
    """A helper that only DERIVES (fold_in) does not consume — two
    calls with one key are the sanctioned multi-stream pattern."""
    rels = _write_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/draws.py": (
            "import jax\n"
            "def derive(key, n):\n"
            "    return jax.random.fold_in(key, n)\n"),
        "pkg/use.py": (
            "from .draws import derive\n"
            "def run(key):\n"
            "    return derive(key, 1), derive(key, 2)\n"),
    })
    assert core.run_lint(str(tmp_path), paths=rels,
                         only=["rng-discipline"]) == []


def test_donation_safety_cross_file_donating_import(tmp_path):
    """`from train import step` where train.py jits with donation:
    read-after-donate at the importing call site."""
    rels = _write_pkg(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/train.py": (
            "import jax\n"
            "def g(s):\n"
            "    return s\n"
            "step = jax.jit(g, donate_argnums=0)\n"),
        "pkg/use.py": (
            "from .train import step\n"
            "def run(state):\n"
            "    out = step(state)\n"
            "    return out, state['params']\n"),
    })
    found = core.run_lint(str(tmp_path), paths=rels,
                          only=["donation-safety"])
    assert len(found) == 1 and found[0].path == "pkg/use.py", \
        [f.render() for f in found]
    assert "`state` read after being donated" in found[0].message


def test_engine_resolves_every_exchange_body_override():
    """Repo-wide: the call graph must see the whole exchange_body
    override family (the checkers build on exactly this)."""
    from theanompi_tpu.analysis.engine import ProgramIndex
    files = core.collect_files(REPO, ["theanompi_tpu"])
    index = ProgramIndex(files)
    recs = index.method_records(
        ("theanompi_tpu.parallel.exchanger", "Exchanger"),
        "exchange_body")
    owners = {r.class_name for r in recs}
    assert {"Exchanger", "BSP_Exchanger", "EASGD_Exchanger",
            "ASGD_Exchanger", "GOSGD_Exchanger"} <= owners, owners
    # and the symmetry checker enumerates the same family
    from theanompi_tpu.analysis.checkers.exchange_symmetry import \
        ExchangeSymmetryChecker
    bodies = ExchangeSymmetryChecker()._exchange_bodies(index)
    assert {r.class_name for r in bodies} >= {
        "BSP_Exchanger", "EASGD_Exchanger", "ASGD_Exchanger",
        "GOSGD_Exchanger"}


# ---------------------------------------------------------------------------
# collective-discipline
# ---------------------------------------------------------------------------

def test_collective_discipline_axis_typo(tmp_path):
    code = (
        "from jax import lax\n"
        "def exchange(x):\n"
        "    return lax.pmean(x, 'workerz')\n")
    found = lint_snippet(tmp_path, "x.py", code, "collective-discipline")
    assert len(found) == 1
    assert "undeclared mesh axis 'workerz'" in found[0].message


def test_collective_discipline_axis_constant_prop(tmp_path):
    """The `axis, alpha = WORKER_AXIS, self.alpha` tuple-assign shape
    (exchanger.py) resolves through constant propagation."""
    code = (
        "from jax import lax\n"
        "from theanompi_tpu.parallel.mesh import WORKER_AXIS\n"
        "def good(x, alpha):\n"
        "    axis, a = WORKER_AXIS, alpha\n"
        "    return lax.psum(x, axis)\n"
        "def bad(x, alpha):\n"
        "    axis, a = 'workerz', alpha\n"
        "    return lax.psum(x, axis)\n")
    found = lint_snippet(tmp_path, "x.py", code, "collective-discipline")
    assert len(found) == 1 and found[0].line == 8, \
        [f.render() for f in found]


def test_collective_discipline_same_file_mesh_declares_axis(tmp_path):
    """An axis declared by a literal Mesh(...) in the same file is
    valid vocabulary (tests declare ('workers', 'seq') meshes)."""
    code = (
        "import numpy as np\n"
        "import jax\n"
        "from jax import lax\n"
        "from jax.sharding import Mesh\n"
        "mesh = Mesh(np.array(jax.devices()), ('rows', 'cols'))\n"
        "def f(x):\n"
        "    return lax.psum(x, 'rows')\n")
    assert lint_snippet(tmp_path, "x.py", code,
                        "collective-discipline") == []


def test_collective_discipline_rank_branch(tmp_path):
    code = (
        "from jax import lax\n"
        "def exchange(x):\n"
        "    rank = lax.axis_index('workers')\n"
        "    if rank == 0:\n"
        "        x = lax.psum(x, 'workers')\n"
        "    return x\n")
    found = lint_snippet(tmp_path, "x.py", code, "collective-discipline")
    assert len(found) == 1
    assert "divergence hazard" in found[0].message


def test_collective_discipline_rank_branch_via_helper(tmp_path):
    """The hazard is interprocedural: a branch calling a helper whose
    SUMMARY issues collectives is flagged too."""
    code = (
        "import jax\n"
        "from jax import lax\n"
        "def reduce_all(x):\n"
        "    return lax.psum(x, 'workers')\n"
        "def exchange(x):\n"
        "    if jax.process_index() == 0:\n"
        "        x = reduce_all(x)\n"
        "    return x\n")
    found = lint_snippet(tmp_path, "x.py", code, "collective-discipline")
    assert len(found) == 1
    assert "reduce_all" in found[0].message


def test_collective_discipline_uniform_branch_ok(tmp_path):
    """Static-config branches (mesh size, flags) are NOT rank taint."""
    code = (
        "from jax import lax\n"
        "def exchange(x, n, use_ring):\n"
        "    rank = lax.axis_index('workers')\n"
        "    y = x + rank\n"                       # data use: fine
        "    if n > 1 and use_ring:\n"
        "        y = lax.psum(y, 'workers')\n"
        "    return y\n")
    assert lint_snippet(tmp_path, "x.py", code,
                        "collective-discipline") == []


def test_collective_discipline_start_done_pairing(tmp_path):
    code = (
        "from jax import lax\n"
        "def overlap(x):\n"
        "    t = lax.psum_start(x, 'workers')\n"
        "    return x\n"
        "def balanced(x):\n"
        "    t = lax.psum_start(x, 'workers')\n"
        "    return lax.psum_done(t)\n")
    found = lint_snippet(tmp_path, "x.py", code, "collective-discipline")
    assert len(found) == 1 and found[0].line == 3
    assert "unbalanced async collective pair" in found[0].message


def test_collective_discipline_discarded_ticket(tmp_path):
    """The pairing probe: a start whose ticket hits the floor is
    flagged even when another pair balances the scope's counts."""
    code = (
        "from jax import lax\n"
        "def exchange(xs):\n"
        "    lax.psum_start(xs[0], 'workers')\n"       # discarded!
        "    t = lax.psum_start(xs[1], 'workers')\n"
        "    a = lax.psum_done(t)\n"
        "    b = lax.psum_done(t)\n"                   # counts balance...
        "    return a + b\n")
    found = lint_snippet(tmp_path, "x.py", code, "collective-discipline")
    assert len(found) == 1 and found[0].line == 3
    assert "leaked in-flight collective" in found[0].message


def test_collective_discipline_bucket_loop_balanced_ok(tmp_path):
    """Starts collected into a ticket list, dones drained from it —
    balanced, clean."""
    code = (
        "from theanompi_tpu.jax_compat import ppermute_start, "
        "ppermute_done\n"
        "def exchange(vecs, perm):\n"
        "    tickets = [ppermute_start(v, 'workers', perm) for v in vecs]\n"
        "    return [ppermute_done(t) for t in tickets]\n")
    assert lint_snippet(tmp_path, "x.py", code,
                        "collective-discipline") == []


def test_collective_discipline_shim_module_exempt():
    """The shim-definition module is the pairing boundary: each half
    wraps its one-sided lax call by construction — no findings on the
    real file."""
    found = core.run_lint(REPO, paths=["theanompi_tpu/jax_compat.py"],
                          only=["collective-discipline"])
    assert found == [], [f.render() for f in found]


def test_injection_dropped_done_in_buckets(tmp_path):
    """Live injection: unwrap the pipeline hop's ticket by hand instead
    of through its ppermute_done and the checker must catch the
    unbalanced pair; the unmodified file is clean."""
    clean = core.run_lint(REPO,
                          paths=["theanompi_tpu/parallel/pipeline.py"],
                          only=["collective-discipline"])
    assert clean == [], [f.render() for f in clean]
    rel = _inject(tmp_path, "theanompi_tpu/parallel/pipeline.py",
                  "            state = jc.ppermute_done(ticket)",
                  "            state = ticket.value")
    found = core.run_lint(str(tmp_path), paths=[rel],
                          only=["collective-discipline"])
    assert any("unbalanced async collective pair" in f.message
               and "ppermute_start" in f.message for f in found), \
        [f.render() for f in found]


def test_injection_dropped_done_in_onebit_strategy(tmp_path):
    """Live injection: a discarded ppermute_start before the pipeline's
    real one is a leaked in-flight collective, though another pair
    balances the scope's counts."""
    rel = _inject(tmp_path, "theanompi_tpu/parallel/pipeline.py",
                  "            ticket = jc.ppermute_start(",
                  "            jc.ppermute_start(out, axis, "
                  "list(sched.perm))\n"
                  "            ticket = jc.ppermute_start(")
    found = core.run_lint(str(tmp_path), paths=[rel],
                          only=["collective-discipline"])
    assert any("leaked in-flight collective" in f.message
               for f in found), [f.render() for f in found]


def test_collective_discipline_dead_ticket(tmp_path):
    """The round-10 dead-ticket probe: a ticket assigned from a start
    and never read is flagged even when the scope's counts balance
    (a typo'd done consuming the wrong ticket twice)."""
    code = (
        "from jax import lax\n"
        "def hop(a, b):\n"
        "    t1 = lax.ppermute_start(a, 'pipe', [(0, 1)])\n"
        "    t2 = lax.ppermute_start(b, 'pipe', [(0, 1)])\n"   # dead!
        "    x = lax.ppermute_done(t1)\n"
        "    y = lax.ppermute_done(t1)\n"                # counts balance
        "    return x + y\n")
    found = lint_snippet(tmp_path, "x.py", code, "collective-discipline")
    assert len(found) == 1 and found[0].line == 4
    assert "dropped hop ticket" in found[0].message
    assert "`t2`" in found[0].message


def test_collective_discipline_consumed_ticket_ok(tmp_path):
    """The healthy per-slot hop shape (pipeline.py scan body): ticket
    started and awaited — clean."""
    code = (
        "from theanompi_tpu.jax_compat import ppermute_start, "
        "ppermute_done\n"
        "def hop(x, perm):\n"
        "    ticket = ppermute_start(x, 'pipe', perm)\n"
        "    return ppermute_done(ticket)\n")
    assert lint_snippet(tmp_path, "x.py", code,
                        "collective-discipline") == []


def test_injection_stripped_hop_done_in_pipeline(tmp_path):
    """Live injection (the ISSUE 16 schedule-slot gate): strip the ONE
    ppermute_done from the real interleaved scan body — the per-slot
    hop ticket is started and never awaited — and the checker must
    fire; the unmodified file is clean."""
    clean = core.run_lint(REPO,
                          paths=["theanompi_tpu/parallel/pipeline.py"],
                          only=["collective-discipline"])
    assert clean == [], [f.render() for f in clean]
    rel = _inject(tmp_path, "theanompi_tpu/parallel/pipeline.py",
                  "            state = jc.ppermute_done(ticket)",
                  "            state = out")
    found = core.run_lint(str(tmp_path), paths=[rel],
                          only=["collective-discipline"])
    assert any("unbalanced async collective pair" in f.message
               and "ppermute_start" in f.message for f in found), \
        [f.render() for f in found]


def test_injection_wrong_ticket_in_pipeline(tmp_path):
    """The harder schedule-slot failure: the done consumes the WRONG
    value so start/done counts still balance — only the dead-ticket
    probe sees the leaked per-slot hop."""
    rel = _inject(tmp_path, "theanompi_tpu/parallel/pipeline.py",
                  "            state = jc.ppermute_done(ticket)",
                  "            state = jc.ppermute_done(out)")
    found = core.run_lint(str(tmp_path), paths=[rel],
                          only=["collective-discipline"])
    assert any("dropped hop ticket" in f.message
               and "`ticket`" in f.message for f in found), \
        [f.render() for f in found]


# ---------------------------------------------------------------------------
# sharding-schema
# ---------------------------------------------------------------------------

def test_sharding_schema_bad_axis_in_spec(tmp_path):
    code = (
        "from jax.sharding import PartitionSpec as P\n"
        "SPEC = P('workerz', None)\n")
    found = lint_snippet(tmp_path, "x.py", code, "sharding-schema")
    assert len(found) == 1
    assert "undeclared mesh axis 'workerz'" in found[0].message


def test_sharding_schema_good_specs(tmp_path):
    """Declared axes, tuple entries, None, and star-constructions
    (the steps.stage_window P(None, *base) shape) all pass."""
    code = (
        "from jax.sharding import PartitionSpec as P\n"
        "A = P('workers', None)\n"
        "B = P(('workers', 'model'), 'seq')\n"
        "def stage(base):\n"
        "    return P(None, *base)\n")
    assert lint_snippet(tmp_path, "x.py", code, "sharding-schema") == []


def test_sharding_schema_in_specs_arity(tmp_path):
    code = (
        "from jax.sharding import PartitionSpec as P\n"
        "from theanompi_tpu.jax_compat import shard_map\n"
        "def build(mesh):\n"
        "    def per_worker(state, batch, lr):\n"
        "        return state\n"
        "    return shard_map(per_worker, mesh=mesh,\n"
        "                     in_specs=(P(), P()), out_specs=P())\n")
    found = lint_snippet(tmp_path, "x.py", code, "sharding-schema")
    assert len(found) == 1
    assert "2 spec(s)" in found[0].message
    assert "3 positional parameter(s)" in found[0].message


def test_sharding_schema_out_specs_arity(tmp_path):
    code = (
        "from jax.sharding import PartitionSpec as P\n"
        "from theanompi_tpu.jax_compat import shard_map\n"
        "def build(mesh):\n"
        "    def per_worker(state):\n"
        "        return state, 1.0, 2.0\n"
        "    return shard_map(per_worker, mesh=mesh,\n"
        "                     in_specs=(P(),), out_specs=(P(), P()))\n")
    found = lint_snippet(tmp_path, "x.py", code, "sharding-schema")
    assert len(found) == 1
    assert "returns 3 value(s)" in found[0].message


def test_sharding_schema_matching_arity_ok(tmp_path):
    code = (
        "from jax.sharding import PartitionSpec as P\n"
        "from theanompi_tpu.jax_compat import shard_map\n"
        "def build(mesh):\n"
        "    def per_worker(state, batch):\n"
        "        return state, batch\n"
        "    return shard_map(per_worker, mesh=mesh,\n"
        "                     in_specs=(P('workers'), P('workers')),\n"
        "                     out_specs=(P('workers'), P('workers')))\n")
    assert lint_snippet(tmp_path, "x.py", code, "sharding-schema") == []


# ---------------------------------------------------------------------------
# exchange-symmetry
# ---------------------------------------------------------------------------

SYMMETRY_BAD = """
from jax import lax
from theanompi_tpu.parallel.exchanger import Exchanger

class Skippy(Exchanger):
    def exchange_body(self, state, key, count):
        if state.get("skip"):
            return state
        return {k: lax.pmean(v, "workers") for k, v in state.items()}
"""

SYMMETRY_BAD_ONE_ARM = """
from jax import lax
from theanompi_tpu.parallel.exchanger import Exchanger

class OneArm(Exchanger):
    def exchange_body(self, state, key, count):
        if count % 2:
            state = {k: lax.psum(v, "workers") for k, v in state.items()}
        return state
"""

SYMMETRY_GOOD = """
from jax import lax
from theanompi_tpu.parallel.exchanger import Exchanger

class Clean(Exchanger):
    def exchange_body(self, state, key, count):
        reduced = {k: lax.pmean(v, "workers") for k, v in state.items()}
        if count % 2:
            reduced = {k: v * 2 for k, v in reduced.items()}
        return reduced
"""


def test_exchange_symmetry_early_return(tmp_path):
    found = lint_snippet(tmp_path, "x.py", SYMMETRY_BAD,
                         "exchange-symmetry")
    assert len(found) == 1
    assert "early exit" in found[0].message
    assert "pmean" in found[0].message


def test_exchange_symmetry_one_armed_branch(tmp_path):
    found = lint_snippet(tmp_path, "x.py", SYMMETRY_BAD_ONE_ARM,
                         "exchange-symmetry")
    assert len(found) == 1
    assert "diverges across `if` arms" in found[0].message


def test_exchange_symmetry_good_subclass(tmp_path):
    assert lint_snippet(tmp_path, "x.py", SYMMETRY_GOOD,
                        "exchange-symmetry") == []


def test_exchange_symmetry_repo_rules_clean():
    """The four live rules already satisfy the invariant."""
    found = core.run_lint(REPO, paths=["theanompi_tpu/parallel"],
                          only=["exchange-symmetry"])
    assert found == [], [f.render() for f in found]


# ---------------------------------------------------------------------------
# acceptance injections against the REAL files (tmp copies)
# ---------------------------------------------------------------------------

def _inject(tmp_path, rel, old, new):
    src = open(os.path.join(REPO, rel)).read()
    assert old in src, f"{rel} changed shape; update the injection"
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src.replace(old, new))
    return rel


def test_injection_axis_typo_in_exchanger(tmp_path):
    rel = _inject(tmp_path, "theanompi_tpu/parallel/exchanger.py",
                  "axis, alpha = WORKER_AXIS, self.alpha",
                  "axis, alpha = 'workerz', self.alpha")
    found = core.run_lint(str(tmp_path), paths=[rel],
                          only=["collective-discipline"])
    assert any("undeclared mesh axis 'workerz'" in f.message
               for f in found), [f.render() for f in found]


def test_injection_rank_conditional_psum_in_strategies(tmp_path):
    rel = _inject(
        tmp_path, "theanompi_tpu/parallel/strategies.py",
        "        if wd is None:\n"
        "            out = jax.tree.map(lambda g: lax.psum(g, axis) * inv"
        ", tree)",
        "        rank = lax.axis_index(axis)\n"
        "        if wd is None:\n"
        "            if rank == 0:\n"
        "                tree = jax.tree.map(lambda g: lax.psum(g, axis),"
        " tree)\n"
        "            out = jax.tree.map(lambda g: lax.psum(g, axis) * inv"
        ", tree)")
    found = core.run_lint(str(tmp_path), paths=[rel],
                          only=["collective-discipline"])
    assert any("divergence hazard" in f.message for f in found), \
        [f.render() for f in found]


def test_injection_wrong_length_in_specs_in_steps(tmp_path):
    rel = _inject(tmp_path, "theanompi_tpu/parallel/steps.py",
                  "in_specs=(state_spec, batch_spec, P(), P(), P()),",
                  "in_specs=(state_spec, batch_spec, P(), P()),")
    found = core.run_lint(str(tmp_path), paths=[rel],
                          only=["sharding-schema"])
    assert any("4 spec(s)" in f.message and "5 positional" in f.message
               for f in found), [f.render() for f in found]


# ---------------------------------------------------------------------------
# result cache (.tpulint_cache/)
# ---------------------------------------------------------------------------

def _lint_cli(root, *extra, env_extra=None):
    env = dict(os.environ, **(env_extra or {}))
    return subprocess.run(
        [sys.executable, LINT, "--root", str(root), *extra],
        capture_output=True, text=True, timeout=300, env=env)


def test_cache_warm_run_identical_and_fast(tmp_path):
    """Cold vs warm: identical findings, warm under a second, and the
    status line says which happened."""
    (tmp_path / "bad.py").write_text(RNG_BAD)
    cold = _lint_cli(tmp_path, "bad.py", "--format", "json")
    assert json.loads(cold.stdout)["cache"] == "miss"
    import time as _time
    t0 = _time.monotonic()
    warm = _lint_cli(tmp_path, "bad.py", "--format", "json")
    elapsed = _time.monotonic() - t0
    w = json.loads(warm.stdout)
    assert w["cache"] == "hit"
    assert w["findings"] == json.loads(cold.stdout)["findings"]
    assert cold.returncode == warm.returncode == 1
    # interpreter startup dominates; the run itself must be trivial
    assert elapsed < 5.0, elapsed
    assert (tmp_path / ".tpulint_cache").is_dir()


def test_cache_invalidates_on_content_change(tmp_path):
    (tmp_path / "f.py").write_text("x = 1\n")
    assert json.loads(_lint_cli(tmp_path, "f.py", "--format",
                                "json").stdout)["cache"] == "miss"
    (tmp_path / "f.py").write_text(RNG_BAD)
    out = json.loads(_lint_cli(tmp_path, "f.py", "--format",
                               "json").stdout)
    assert out["cache"] == "miss"
    assert out["findings"], "edited file must re-lint, not hit"


def test_cache_no_cache_flag(tmp_path):
    (tmp_path / "f.py").write_text("x = 1\n")
    _lint_cli(tmp_path, "f.py")
    out = json.loads(_lint_cli(tmp_path, "f.py", "--no-cache",
                               "--format", "json").stdout)
    assert out["cache"] == "off"


def test_cache_key_depends_on_analysis_sources():
    """Editing any analysis/ source changes the fingerprint — the
    auto-invalidation the cache's soundness rests on."""
    from theanompi_tpu.analysis import cache as cm
    fp = cm.analysis_fingerprint()
    h1 = cm.tree_key(fp, ["a"], [], [("f.py", "sha")])
    h2 = cm.tree_key(fp + "x", ["a"], [], [("f.py", "sha")])
    h3 = cm.tree_key(fp, ["a", "b"], [], [("f.py", "sha")])
    h4 = cm.tree_key(fp, ["a"], [], [("f.py", "sha2")])
    assert len({h1, h2, h3, h4}) == 4


def test_cache_repo_gate_warm_subsecond():
    """The acceptance criterion: a cached re-run of the unchanged repo
    completes in < 1s (process time minus interpreter startup) and is
    finding-identical to the cold run."""
    import time as _time
    cold = subprocess.run(
        [sys.executable, LINT, "--format", "json"], cwd=REPO,
        capture_output=True, text=True, timeout=300)
    t0 = _time.monotonic()
    warm = subprocess.run(
        [sys.executable, LINT, "--format", "json"], cwd=REPO,
        capture_output=True, text=True, timeout=300)
    elapsed = _time.monotonic() - t0
    w, c = json.loads(warm.stdout), json.loads(cold.stdout)
    assert w["cache"] == "hit"
    assert w["findings"] == c["findings"]
    assert elapsed < 2.5, f"warm repo lint took {elapsed:.2f}s"


# ---------------------------------------------------------------------------
# --format json fingerprints + TODO-nag collapse
# ---------------------------------------------------------------------------

def test_json_format_stable_fingerprints(tmp_path):
    (tmp_path / "bad.py").write_text(TRACE_BAD)
    out = json.loads(_lint_cli(tmp_path, "bad.py", "--format",
                               "json").stdout)
    f = out["findings"][0]
    assert set(f) >= {"check", "path", "line", "col", "message",
                      "fingerprint"}
    # stable = line-insensitive (for messages that don't quote a line):
    # shifting the file moves the finding but keeps the fingerprint
    (tmp_path / "bad.py").write_text("\n\n\n" + TRACE_BAD)
    out2 = json.loads(_lint_cli(tmp_path, "bad.py", "--format",
                                "json").stdout)
    assert out2["findings"][0]["fingerprint"] == f["fingerprint"]
    assert out2["findings"][0]["line"] != f["line"]


def test_todo_nag_collapses_to_summary(tmp_path):
    """Two TODO entries: default output is ONE summary line carrying
    the count; --verbose restores the per-entry list.  Never silent."""
    (tmp_path / "bad.py").write_text(RNG_BAD + RNG_BAD_LOOP)
    findings = core.run_lint(str(tmp_path), paths=["bad.py"],
                             only=["rng-discipline"])
    assert len(findings) == 2
    core.save_baseline(str(tmp_path / core.BASELINE_NAME), findings)
    proc = _lint_cli(tmp_path, "bad.py", "--only", "rng-discipline")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    nag_lines = [l for l in proc.stderr.splitlines()
                 if "justification" in l]
    assert len(nag_lines) == 1, proc.stderr
    assert "2 baseline entries" in nag_lines[0]
    verbose = _lint_cli(tmp_path, "bad.py", "--only", "rng-discipline",
                        "--verbose")
    v_lines = [l for l in verbose.stderr.splitlines()
               if "needs a justification" in l]
    assert len(v_lines) == 2, verbose.stderr


# ---------------------------------------------------------------------------
# precommit entry point
# ---------------------------------------------------------------------------

def test_precommit_lint_script_clean_and_failing(tmp_path):
    """scripts/precommit_lint.sh lints exactly the staged in-scope
    files of a scratch clone: clean stage exits 0, a staged finding
    exits 1, out-of-scope stages are ignored."""
    import shutil
    repo = tmp_path / "r"
    (repo / "scripts").mkdir(parents=True)
    (repo / "theanompi_tpu").mkdir()
    shutil.copy(os.path.join(REPO, "scripts", "precommit_lint.sh"),
                repo / "scripts" / "precommit_lint.sh")
    shutil.copy(LINT, repo / "scripts" / "lint.py")
    # the launcher needs the analysis package under the scratch root
    shutil.copytree(os.path.join(REPO, "theanompi_tpu", "analysis"),
                    repo / "theanompi_tpu" / "analysis")
    shutil.copy(os.path.join(REPO, "theanompi_tpu", "jax_compat.py"),
                repo / "theanompi_tpu" / "jax_compat.py")
    # the schema-drift live probe imports these for real (devprof/sentry
    # feed the round-12 device-schema probes; the checker skips them
    # gracefully when a partial tree omits them)
    (repo / "theanompi_tpu" / "utils").mkdir()
    for m in ("__init__.py", "recorder.py", "telemetry.py", "devprof.py",
              "sentry.py"):
        shutil.copy(os.path.join(REPO, "theanompi_tpu", "utils", m),
                    repo / "theanompi_tpu" / "utils" / m)

    def git(*a):
        return subprocess.run(["git", *a], cwd=repo, capture_output=True,
                              text=True, timeout=60)

    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    sh = ["bash", "scripts/precommit_lint.sh"]

    # nothing staged in scope
    (repo / "NOTES.md").write_text("x\n")
    git("add", "NOTES.md")
    p = subprocess.run(sh, cwd=repo, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0 and "no staged python files" in p.stdout

    # a staged clean file
    (repo / "theanompi_tpu" / "ok.py").write_text("x = 1\n")
    git("add", "theanompi_tpu/ok.py")
    p = subprocess.run(sh, cwd=repo, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr

    # a staged finding fails the hook
    (repo / "theanompi_tpu" / "bad.py").write_text(RNG_BAD)
    git("add", "theanompi_tpu/bad.py")
    p = subprocess.run(sh, cwd=repo, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "rng-discipline" in p.stdout


def test_collective_discipline_axis_name_kwarg_typo(tmp_path):
    """A typo'd axis passed as `axis_name=` on a COLLECTIVE must not
    self-whitelist (review finding: kwarg harvesting is for binders)."""
    code = (
        "from jax import lax\n"
        "def exchange(x):\n"
        "    return lax.pmean(x, axis_name='workerz')\n")
    found = lint_snippet(tmp_path, "x.py", code, "collective-discipline")
    assert len(found) == 1
    assert "undeclared mesh axis 'workerz'" in found[0].message


def test_exchange_symmetry_exiting_arm_issues_collective(tmp_path):
    """The mirror of SYMMETRY_BAD: the EXITING arm reduces and the
    fall-through does not — same divergence, must be flagged."""
    code = (
        "from jax import lax\n"
        "from theanompi_tpu.parallel.exchanger import Exchanger\n"
        "class Mirror(Exchanger):\n"
        "    def exchange_body(self, state, key, count):\n"
        "        if state.get('skip'):\n"
        "            return {k: lax.pmean(v, 'workers')\n"
        "                    for k, v in state.items()}\n"
        "        return state\n")
    found = lint_snippet(tmp_path, "x.py", code, "exchange-symmetry")
    assert len(found) == 1, [f.render() for f in found]
    assert "pmean" in found[0].message


def test_exchange_symmetry_config_assert_not_flagged(tmp_path):
    """A raising guard before the collectives is a loud uniform abort,
    not a silent divergence — no finding."""
    code = (
        "from jax import lax\n"
        "from theanompi_tpu.parallel.exchanger import Exchanger\n"
        "class Guarded(Exchanger):\n"
        "    def exchange_body(self, state, key, count):\n"
        "        if not state:\n"
        "            raise ValueError('empty state')\n"
        "        return {k: lax.pmean(v, 'workers')\n"
        "                for k, v in state.items()}\n")
    assert lint_snippet(tmp_path, "x.py", code, "exchange-symmetry") == []


def test_precommit_lints_staged_blob_not_worktree(tmp_path):
    """Stage a violation, fix the worktree WITHOUT re-staging: the hook
    must still fail — the commit would contain the staged violation."""
    import shutil
    repo = tmp_path / "r"
    (repo / "scripts").mkdir(parents=True)
    (repo / "theanompi_tpu").mkdir()
    shutil.copy(os.path.join(REPO, "scripts", "precommit_lint.sh"),
                repo / "scripts" / "precommit_lint.sh")
    shutil.copy(LINT, repo / "scripts" / "lint.py")
    shutil.copytree(os.path.join(REPO, "theanompi_tpu", "analysis"),
                    repo / "theanompi_tpu" / "analysis")
    shutil.copy(os.path.join(REPO, "theanompi_tpu", "jax_compat.py"),
                repo / "theanompi_tpu" / "jax_compat.py")
    (repo / "theanompi_tpu" / "utils").mkdir()
    for m in ("__init__.py", "recorder.py", "telemetry.py"):
        shutil.copy(os.path.join(REPO, "theanompi_tpu", "utils", m),
                    repo / "theanompi_tpu" / "utils" / m)

    def git(*a):
        return subprocess.run(["git", *a], cwd=repo, capture_output=True,
                              text=True, timeout=60)

    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    (repo / "theanompi_tpu" / "f.py").write_text(RNG_BAD)
    git("add", "theanompi_tpu/f.py")
    (repo / "theanompi_tpu" / "f.py").write_text("x = 1\n")  # fixed, unstaged
    p = subprocess.run(["bash", "scripts/precommit_lint.sh"], cwd=repo,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "rng-discipline" in p.stdout
    # re-stage the fix: clean
    git("add", "theanompi_tpu/f.py")
    p = subprocess.run(["bash", "scripts/precommit_lint.sh"], cwd=repo,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr


# ---------------------------------------------------------------------------
# host-concurrency pass (round 15): thread-role inference + four checkers
# ---------------------------------------------------------------------------

RACE_BAD = """
import threading

class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.items = []
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        self.count = self.count + 1
        self.items.append(self.count)

    def bump(self):
        self.count = 0

    def snapshot(self):
        return list(self.items)

    def stop(self):
        self._thread.join(timeout=1)
"""

RACE_GOOD = """
import threading

class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.items = []
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        with self._lock:
            self.count = self.count + 1
            self.items.append(self.count)

    def bump(self):
        with self._lock:
            self.count = 0

    def snapshot(self):
        with self._lock:
            return list(self.items)

    def stop(self):
        self._thread.join(timeout=1)
"""


def test_shared_state_race_bad_fixture(tmp_path):
    found = lint_snippet(tmp_path, "bad.py", RACE_BAD, "shared-state-race")
    msgs = [f.message for f in found]
    assert len(found) == 2, msgs
    assert any("`count`" in m and "no common lock" in m for m in msgs)
    assert any("`items`" in m and "iteration/copy" in m for m in msgs)


def test_shared_state_race_good_fixture(tmp_path):
    assert lint_snippet(tmp_path, "good.py", RACE_GOOD,
                        "shared-state-race") == []


def test_shared_state_race_init_writes_are_happens_before(tmp_path):
    """__init__ writes never conflict — construction precedes start()."""
    code = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self.flag = False\n"
        "        threading.Thread(target=self._go, daemon=True).start()\n"
        "    def _go(self):\n"
        "        self.flag = True\n")
    assert lint_snippet(tmp_path, "x.py", code, "shared-state-race") == []


def test_shared_state_race_needs_instance_sharing(tmp_path):
    """A thread that constructs its OWN instance of a class does not
    conflict with main-thread users of other instances (the per-island
    private ModelBase shape)."""
    code = (
        "import threading\n"
        "class Model:\n"
        "    def compile(self):\n"
        "        self.train_fn = 1\n"
        "class Island:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._run, daemon=True)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        m = Model()\n"
        "        m.compile()\n"
        "    def stop(self):\n"
        "        self._t.join(timeout=1)\n"
        "def main_path():\n"
        "    m = Model()\n"
        "    m.compile()\n")
    assert lint_snippet(tmp_path, "x.py", code, "shared-state-race") == []


LOCK_ORDER_BAD = """
import threading

class Pair:
    def __init__(self):
        self._a_lock = threading.Lock()
        self._b_lock = threading.Lock()

    def ab(self):
        with self._a_lock:
            with self._b_lock:
                pass

    def ba(self):
        with self._b_lock:
            with self._a_lock:
                pass
"""

LOCK_ORDER_GOOD = LOCK_ORDER_BAD.replace(
    "        with self._b_lock:\n            with self._a_lock:",
    "        with self._a_lock:\n            with self._b_lock:")


def test_lock_ordering_cycle_fixture(tmp_path):
    found = lint_snippet(tmp_path, "bad.py", LOCK_ORDER_BAD,
                         "lock-ordering")
    assert len(found) == 1, [f.message for f in found]
    assert "lock-order cycle" in found[0].message
    assert "_a_lock" in found[0].message and "_b_lock" in found[0].message


def test_lock_ordering_consistent_order_clean(tmp_path):
    assert lint_snippet(tmp_path, "good.py", LOCK_ORDER_GOOD,
                        "lock-ordering") == []


def test_lock_ordering_nonreentrant_self_deadlock(tmp_path):
    code = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def outer(self):\n"
        "        with self._lock:\n"
        "            self.inner()\n"
        "    def inner(self):\n"
        "        with self._lock:\n"
        "            pass\n")
    found = lint_snippet(tmp_path, "x.py", code, "lock-ordering")
    assert found and all("self-deadlock" in f.message for f in found)
    # the reentrant version is the sanctioned idiom (telemetry registry)
    rcode = code.replace("threading.Lock()", "threading.RLock()")
    assert lint_snippet(tmp_path, "y.py", rcode, "lock-ordering") == []


SIGNAL_BAD = """
import signal
import threading
import time

_state_lock = threading.Lock()

def _handler(signum, frame):
    time.sleep(0.1)
    with _state_lock:
        pass
    t = threading.Thread(target=_work, daemon=True)
    t.start()

def _work():
    pass

signal.signal(signal.SIGTERM, _handler)
"""

SIGNAL_GOOD = """
import signal
import threading

_halt = threading.Event()

def _handler(signum, frame):
    _halt.set()

signal.signal(signal.SIGTERM, _handler)
"""


def test_signal_safety_bad_fixture(tmp_path):
    found = lint_snippet(tmp_path, "bad.py", SIGNAL_BAD, "signal-safety")
    msgs = [f.message for f in found]
    assert any("time.sleep" in m for m in msgs), msgs
    assert any("NON-reentrant lock" in m for m in msgs), msgs
    assert any("spawns a thread" in m for m in msgs), msgs


def test_signal_safety_good_fixture(tmp_path):
    assert lint_snippet(tmp_path, "good.py", SIGNAL_GOOD,
                        "signal-safety") == []


def test_signal_safety_telemetry_recording_flagged(tmp_path):
    code = (
        "import signal\n"
        "from theanompi_tpu.utils import telemetry\n"
        "tm = telemetry.active()\n"
        "def _handler(signum, frame):\n"
        "    tm.event('sig')\n"
        "signal.signal(signal.SIGTERM, _handler)\n")
    found = lint_snippet(tmp_path, "x.py", code, "signal-safety")
    assert len(found) == 1 and "reentrant call" in found[0].message


def test_signal_safety_sanctioned_hook_is_exempt():
    """The live telemetry.py fatal-signal hook records by design (it is
    terminal) — the repo-wide run must not flag it."""
    found = core.run_lint(REPO, paths=["theanompi_tpu/utils/telemetry.py"],
                          only=["signal-safety"])
    assert found == [], [f.render() for f in found]


DAEMON_BAD = """
import threading

class Owner:
    def start(self):
        self._pump = threading.Thread(target=self._run_pump)
        self._pump.start()

    def _run_pump(self):
        pass

class BadThread(threading.Thread):
    def __init__(self):
        super().__init__()
        self._stop = threading.Event()

    def run(self):
        pass
"""

DAEMON_GOOD = """
import threading

class Owner:
    def start(self):
        self._pump = threading.Thread(target=self._run_pump, daemon=True)
        self._pump.start()

    def _run_pump(self):
        pass

    def stop(self):
        self._pump.join(timeout=1)

class GoodThread(threading.Thread):
    def __init__(self):
        super().__init__(daemon=True)
        self._halt = threading.Event()

    def run(self):
        pass

    def stop(self):
        self._halt.set()
        self.join(timeout=1)
"""


def test_daemon_discipline_bad_fixture(tmp_path):
    found = lint_snippet(tmp_path, "bad.py", DAEMON_BAD,
                         "daemon-discipline")
    msgs = [f.message for f in found]
    assert any("non-daemon Thread" in m for m in msgs), msgs
    assert any("`self._stop`" in m and "shadowing" in m
               for m in msgs), msgs
    assert any("non-daemon and never joins itself" in m
               for m in msgs), msgs


def test_daemon_discipline_good_fixture(tmp_path):
    assert lint_snippet(tmp_path, "good.py", DAEMON_GOOD,
                        "daemon-discipline") == []


def test_daemon_discipline_escaping_started_thread_needs_join(tmp_path):
    code = (
        "import threading\n"
        "class P:\n"
        "    def __init__(self):\n"
        "        self._threads = []\n"
        "    def start(self):\n"
        "        t = threading.Thread(target=self._go, daemon=True)\n"
        "        t.start()\n"
        "        self._threads.append(t)\n"
        "    def _go(self):\n"
        "        pass\n")
    found = lint_snippet(tmp_path, "x.py", code, "daemon-discipline")
    assert len(found) == 1 and "never joined" in found[0].message
    fixed = code + (
        "    def stop(self):\n"
        "        for t in self._threads:\n"
        "            t.join(timeout=1)\n")
    assert lint_snippet(tmp_path, "y.py", fixed,
                        "daemon-discipline") == []


# -- engine thread-role inference -------------------------------------------

ENGINE_ROLES = """
import atexit
import signal
import threading

class Prod:
    def start(self):
        self._t = threading.Thread(target=self._producer, daemon=True)
        self._t.start()

    def _producer(self):
        self._helper()

    def _helper(self):
        pass

    def consume(self):
        pass

    def stop(self):
        self._t.join(timeout=1)

class Mon(threading.Thread):
    def run(self):
        pass

def _on_exit():
    pass

def _on_sig(s, f):
    pass

atexit.register(_on_exit)
signal.signal(signal.SIGTERM, _on_sig)
"""


def test_engine_thread_roles_and_main_exclusion(tmp_path):
    from theanompi_tpu.analysis.engine import MAIN_ROLE, ProgramIndex
    (tmp_path / "roles.py").write_text(ENGINE_ROLES)
    sf = core.SourceFile(str(tmp_path), "roles.py")
    index = ProgramIndex([sf])
    kinds = {r.kind for r in index.thread_roles()}
    assert kinds == {"thread", "thread-subclass", "atexit", "signal"}
    by_qual = {r.qualname: r for r in index.records.values()
               if not r.qualname.startswith("roles.<lambda>")}
    rm = index.role_map()
    prod_roles = rm[id(by_qual["roles.Prod._producer"].node)]
    help_roles = rm[id(by_qual["roles.Prod._helper"].node)]
    # the producer and its exclusive helper run ONLY on the spawned
    # thread — a spawn reference is not a main-role call edge
    assert MAIN_ROLE not in prod_roles and MAIN_ROLE not in help_roles
    assert any(r.startswith("thread:") for r in prod_roles)
    assert prod_roles <= help_roles
    # the public surface stays main
    assert MAIN_ROLE in rm[id(by_qual["roles.Prod.consume"].node)]
    assert MAIN_ROLE in rm[id(by_qual["roles.Prod.start"].node)]


def test_engine_spawn_sites_resolve_tuple_loop_targets(tmp_path):
    """The ChaosProxy pump-pair shape: Thread targets bound by a for
    loop over a literal tuple of methods must resolve."""
    from theanompi_tpu.analysis.engine import ProgramIndex
    code = (
        "import threading\n"
        "class P:\n"
        "    def start(self):\n"
        "        for fn in (self._a, self._b):\n"
        "            threading.Thread(target=fn, daemon=True).start()\n"
        "    def _a(self):\n"
        "        pass\n"
        "    def _b(self):\n"
        "        pass\n")
    (tmp_path / "pumps.py").write_text(code)
    sf = core.SourceFile(str(tmp_path), "pumps.py")
    index = ProgramIndex([sf])
    sites = [s for s in index.spawn_sites() if s.kind == "thread"]
    assert len(sites) == 1
    assert sorted(e.name for e in sites[0].entries) == ["_a", "_b"]


def test_schema_drift_thread_role_probe_live_and_bad(tmp_path):
    """The live repo's membership/chaos spawn sites all resolve; a
    planted unresolvable spawn fails the probe."""
    assert sd.thread_role_coverage_errors() == []
    bad = tmp_path / "theanompi_tpu" / "utils"
    bad.mkdir(parents=True)
    (bad / "chaos.py").write_text(
        "import threading\n"
        "def go(fns):\n"
        "    threading.Thread(target=fns[0], daemon=True).start()\n")
    errors = sd.thread_role_coverage_errors(root=str(tmp_path))
    assert errors and "does not resolve" in errors[0][1]


# -- live injections against the REAL files (CLI --check-baseline gate) -----

def test_injection_unguarded_producer_write_in_prefetch(tmp_path):
    """An unguarded cross-thread write planted in the prefetch producer
    fails the tier-1 gate with rc 1."""
    rel = _inject(
        tmp_path, "theanompi_tpu/models/data/prefetch.py",
        "                cursor = self._data.get_cursor() \\\n"
        "                    if hasattr(self._data, \"get_cursor\") else {}\n"
        "                if stop.is_set():     # restart raced the load",
        "                cursor = self._data.get_cursor() \\\n"
        "                    if hasattr(self._data, \"get_cursor\") else {}\n"
        "                self._consumed_cursor = cursor\n"
        "                if stop.is_set():     # restart raced the load")
    proc = _lint_cli(tmp_path, rel, "--check-baseline")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "shared-state-race" in proc.stdout
    assert "_consumed_cursor" in proc.stdout


def test_injection_lock_order_inversion_in_center_server(tmp_path):
    """A planted A→B / B→A inversion across snapshot() and stop() fails
    the gate with a lock-ordering cycle."""
    rel = _inject(
        tmp_path, "theanompi_tpu/parallel/center_server.py",
        "        with self.center._lock:\n"
        "            if self.center._leaves is None:\n"
        "                return None\n",
        "        with self.center._lock:\n"
        "            with self._conns_lock:\n"
        "                pass\n"
        "            if self.center._leaves is None:\n"
        "                return None\n")
    p = tmp_path / rel
    src = p.read_text()
    old = ("            with self._conns_lock:\n"
           "                conns = list(self._conns)\n"
           "                self._conns.clear()\n")
    assert old in src, "center_server.stop changed shape; update injection"
    p.write_text(src.replace(old,
                 "            with self._conns_lock:\n"
                 "                with self.center._lock:\n"
                 "                    pass\n"
                 "                conns = list(self._conns)\n"
                 "                self._conns.clear()\n"))
    proc = _lint_cli(tmp_path, rel, "--check-baseline")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "lock-order cycle" in proc.stdout


def test_injection_telemetry_event_in_signal_hook(tmp_path):
    """A telemetry.event() call planted into the center CLI's SIGTERM
    hook fails the gate (reentrant-BufferedWriter hazard)."""
    rel = _inject(
        tmp_path, "theanompi_tpu/parallel/center_server.py",
        "    signal.signal(signal.SIGTERM, lambda *_: halt.set())",
        "    signal.signal(signal.SIGTERM,\n"
        "                  lambda *_: (tm.event(\"sigterm\"), halt.set()))")
    proc = _lint_cli(tmp_path, rel, "--check-baseline")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "signal-safety" in proc.stdout
    assert "reentrant call" in proc.stdout


# -- the --only concurrency group + cache behavior ---------------------------

def test_only_concurrency_group_runs_just_the_pass(tmp_path):
    (tmp_path / "bad.py").write_text(RACE_BAD + LOCK_ORDER_BAD)
    out = json.loads(_lint_cli(tmp_path, "bad.py", "--only", "concurrency",
                               "--format", "json").stdout)
    from theanompi_tpu.analysis.checkers import CHECK_GROUPS
    group = set(CHECK_GROUPS["concurrency"])
    checks = {f["check"] for f in out["findings"]}
    assert checks and checks <= group, checks
    # the v2 schema carries the new checker names + stable fingerprints
    for f in out["findings"]:
        assert f["check"] in group
        assert len(f["fingerprint"]) == 12
    # and a non-concurrency finding source stays silent under the group
    (tmp_path / "rng.py").write_text(RNG_BAD)
    out2 = json.loads(_lint_cli(tmp_path, "rng.py", "--only",
                                "concurrency", "--format", "json").stdout)
    assert out2["findings"] == []


def test_only_concurrency_repo_warm_cache_subsecond():
    """Satellite gate: a warm-cache whole-repo run of just the
    concurrency pass stays sub-second (modulo interpreter startup),
    mirroring the existing full-suite cache gate."""
    import time as _time
    cold = subprocess.run(
        [sys.executable, LINT, "--only", "concurrency", "--format",
         "json"], cwd=REPO, capture_output=True, text=True, timeout=300)
    t0 = _time.monotonic()
    warm = subprocess.run(
        [sys.executable, LINT, "--only", "concurrency", "--format",
         "json"], cwd=REPO, capture_output=True, text=True, timeout=300)
    elapsed = _time.monotonic() - t0
    w, c = json.loads(warm.stdout), json.loads(cold.stdout)
    assert w["cache"] == "hit"
    assert w["findings"] == c["findings"]
    assert elapsed < 2.5, f"warm concurrency lint took {elapsed:.2f}s"


def test_precommit_carries_concurrency_checkers(tmp_path):
    """precommit_lint.sh runs the concurrency pass on staged blobs with
    the same names/fingerprints (satellite: the hook and the json v2
    schema carry the new checkers unchanged)."""
    import shutil
    repo = tmp_path / "r"
    (repo / "scripts").mkdir(parents=True)
    (repo / "theanompi_tpu").mkdir()
    shutil.copy(os.path.join(REPO, "scripts", "precommit_lint.sh"),
                repo / "scripts" / "precommit_lint.sh")
    shutil.copy(LINT, repo / "scripts" / "lint.py")
    shutil.copytree(os.path.join(REPO, "theanompi_tpu", "analysis"),
                    repo / "theanompi_tpu" / "analysis")
    shutil.copy(os.path.join(REPO, "theanompi_tpu", "jax_compat.py"),
                repo / "theanompi_tpu" / "jax_compat.py")
    (repo / "theanompi_tpu" / "utils").mkdir()
    for m in ("__init__.py", "recorder.py", "telemetry.py"):
        shutil.copy(os.path.join(REPO, "theanompi_tpu", "utils", m),
                    repo / "theanompi_tpu" / "utils" / m)

    def git(*a):
        return subprocess.run(["git", *a], cwd=repo, capture_output=True,
                              text=True, timeout=60)

    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    (repo / "theanompi_tpu" / "racy.py").write_text(RACE_BAD)
    git("add", "theanompi_tpu/racy.py")
    p = subprocess.run(["bash", "scripts/precommit_lint.sh"], cwd=repo,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "shared-state-race" in p.stdout


def test_engine_resolve_callable_survives_cyclic_rebind(tmp_path):
    """`fn = fn` (or a = b / b = a) around a spawn target must degrade
    to unresolved, not recurse to death and abort the engine."""
    from theanompi_tpu.analysis.engine import ProgramIndex
    code = (
        "import threading\n"
        "def go(fn=None):\n"
        "    fn = fn\n"
        "    a = b = None\n"
        "    a = b\n"
        "    b = a\n"
        "    threading.Thread(target=fn, daemon=True).start()\n"
        "    threading.Thread(target=a, daemon=True).start()\n")
    (tmp_path / "cyc.py").write_text(code)
    sf = core.SourceFile(str(tmp_path), "cyc.py")
    index = ProgramIndex([sf])
    sites = [s for s in index.spawn_sites() if s.kind == "thread"]
    assert len(sites) == 2
    assert all(s.entries == [] for s in sites)


def test_daemon_discipline_stored_attr_daemonized_after(tmp_path):
    """`self._t = Thread(...); self._t.daemon = True` is daemonic — the
    post-construction daemon assign must be seen for stored attrs too."""
    code = (
        "import threading\n"
        "class C:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._go)\n"
        "        self._t.daemon = True\n"
        "        self._t.start()\n"
        "    def _go(self):\n"
        "        pass\n"
        "    def stop(self):\n"
        "        self._t.join(timeout=1)\n")
    assert lint_snippet(tmp_path, "x.py", code, "daemon-discipline") == []


# ---------------------------------------------------------------------------
# protocol conformance (round 19, docs/design.md §21)
# ---------------------------------------------------------------------------

from theanompi_tpu.analysis import protocol as proto  # noqa: E402
from theanompi_tpu.analysis.engine import ProgramIndex as _PI  # noqa: E402

CENTER_REL = proto.CENTER_PATH
MEMBERSHIP_REL = proto.MEMBERSHIP_PATH


def _write_at(tmp_path, rel, code):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(code)
    return rel


def _protocol_lint(tmp_path, only, rels):
    return core.run_lint(str(tmp_path), paths=list(rels), only=[only])


WIRECONTRACT_GOOD = '''
class CenterServer:
    def start(self):
        center = self.center
        dedup = self.dedup

        class Handler:
            def _dispatch(self, header, body):
                op = header.get("op")
                tok = header.get("tok")
                if op == "push":
                    wire.send_msg(self.request, {"ok": True})
                elif op == "pull":
                    wire.send_msg(self.request, {"ok": True}, body)
                else:
                    wire.send_msg(self.request,
                                  {"ok": False, "error": "?"})


class RemoteCenter:
    def _roundtrip(self, header, body=b""):
        return self._wire.request(header, body)

    def push(self, body):
        self._roundtrip({"op": "push"}, body)

    def pull(self):
        resp, body = self._roundtrip({"op": "pull"})
        return body
'''

WIRECONTRACT_BAD = WIRECONTRACT_GOOD + '''

class Extra(RemoteCenter):
    def poke(self):
        self._roundtrip({"op": "poke"})
'''


def test_wire_contract_good_fixture(tmp_path):
    rel = _write_at(tmp_path, CENTER_REL, WIRECONTRACT_GOOD)
    assert _protocol_lint(tmp_path, "wire-contract", [rel]) == []


def test_wire_contract_client_op_without_handler(tmp_path):
    rel = _write_at(tmp_path, CENTER_REL, WIRECONTRACT_BAD)
    found = _protocol_lint(tmp_path, "wire-contract", [rel])
    # Extra subclasses RemoteCenter, so its sends are NOT in the
    # declared RemoteCenter scope — move the send in to see it
    assert found == [], [f.render() for f in found]
    bad = WIRECONTRACT_GOOD.replace(
        '    def pull(self):',
        '    def poke(self):\n'
        '        self._roundtrip({"op": "poke"})\n\n'
        '    def pull(self):')
    rel = _write_at(tmp_path, CENTER_REL, bad)
    found = _protocol_lint(tmp_path, "wire-contract", [rel])
    assert len(found) == 1 and "no handler arm" in found[0].message \
        and "'poke'" in found[0].message, [f.render() for f in found]


def test_wire_contract_dead_handler_arm(tmp_path):
    bad = WIRECONTRACT_GOOD.replace(
        'elif op == "pull":',
        'elif op == "purge":\n'
        '                    wire.send_msg(self.request, {"ok": True})\n'
        '                elif op == "pull":')
    rel = _write_at(tmp_path, CENTER_REL, bad)
    found = _protocol_lint(tmp_path, "wire-contract", [rel])
    assert len(found) == 1 and "no in-repo client ever sends" in \
        found[0].message and "'purge'" in found[0].message, \
        [f.render() for f in found]


def test_wire_contract_retry_on_success_is_incoherent(tmp_path):
    bad = WIRECONTRACT_GOOD.replace(
        'wire.send_msg(self.request, {"ok": True}, body)',
        'wire.send_msg(self.request, '
        '{"ok": True, "retry": True}, body)')
    rel = _write_at(tmp_path, CENTER_REL, bad)
    found = _protocol_lint(tmp_path, "wire-contract", [rel])
    assert len(found) == 1 and "retry=true without ok=false" in \
        found[0].message, [f.render() for f in found]


def test_wire_contract_client_reads_unset_reply_field(tmp_path):
    bad = WIRECONTRACT_GOOD.replace(
        "        return body", '        return resp.get("shard")')
    rel = _write_at(tmp_path, CENTER_REL, bad)
    found = _protocol_lint(tmp_path, "wire-contract", [rel])
    assert len(found) == 1 and "reads reply field 'shard'" in \
        found[0].message, [f.render() for f in found]


def test_wire_contract_dynamic_reply_suppresses_read_diff(tmp_path):
    """A ``**``-splat reply can set anything — the read diff must not
    guess against it."""
    bad = WIRECONTRACT_GOOD.replace(
        "        return body", '        return resp.get("shard")'
    ).replace(
        'wire.send_msg(self.request, {"ok": True}, body)',
        'wire.send_msg(self.request, '
        '{"ok": True, **center.stats()}, body)')
    rel = _write_at(tmp_path, CENTER_REL, bad)
    found = _protocol_lint(tmp_path, "wire-contract", [rel])
    assert found == [], [f.render() for f in found]


STATUSZ_FAMILY = {
    proto.TRACING_PATH: '''
class Handler:
    def handle(self):
        header, _ = w.recv_msg(self.request)
        op = header.get("op")
        if op == "health":
            w.send_msg(self.request, {"ok": True})
        elif op == "events":
            w.send_msg(self.request, {"ok": True, "events": []})
        elif op == "flight":
            w.send_msg(self.request, {"ok": True, "path": None})


def statusz_query(addr, op="health", n=16):
    return {}
''',
    proto.FLEETMON_PATH: '''
class Handler:
    def _dispatch(self, header, body):
        op = header.get("op")
        if op == "metrics":
            wire.send_msg(self.request, {"ok": True})
        elif op == "alerts":
            wire.send_msg(self.request, {"ok": True, "alerts": []})


class MetricStreamer:
    def push(self):
        header = {"op": "metrics"}
        self.client.request(header, b"")
''',
    proto.FLEETZ_PATH: '''
from theanompi_tpu.utils import tracing


def probe(addr):
    tracing.statusz_query(addr, "health")
    tracing.statusz_query(addr, "events")
    tracing.statusz_query(addr, "flight")
    tracing.statusz_query(addr, "alerts")
''',
}


def test_wire_contract_statusz_family_pooled(tmp_path):
    rels = [_write_at(tmp_path, rel, code)
            for rel, code in STATUSZ_FAMILY.items()]
    found = core.run_lint(str(tmp_path), paths=rels,
                          only=["wire-contract"])
    assert found == [], [f.render() for f in found]
    # an op the dialer sends that NO statusz-compatible endpoint handles
    bad = STATUSZ_FAMILY[proto.FLEETZ_PATH] + \
        '\n\ndef bad(addr):\n    tracing.statusz_query(addr, "bogus")\n'
    _write_at(tmp_path, proto.FLEETZ_PATH, bad)
    found = core.run_lint(str(tmp_path), paths=rels,
                          only=["wire-contract"])
    assert len(found) == 1 and "statusz_query sends op 'bogus'" in \
        found[0].message, [f.render() for f in found]


RETRY_GOOD = '''
class CenterServer:
    def start(self):
        center = self.center
        dedup = self.dedup

        class Handler:
            def _dispatch(self, header, body):
                op = header.get("op")
                tok = header.get("tok")
                if op == "push":
                    dup, cached = dedup.check(tok, op)
                    if dup:
                        wire.send_msg(self.request,
                                      {"ok": True, "dedup": True})
                        return
                    try:
                        center.n_updates += 1
                        dedup.record(tok, op, {"ok": True})
                    except Exception:
                        dedup.release(tok, op)
                        raise
                    wire.send_msg(self.request, {"ok": True})
                elif op == "pull":
                    wire.send_msg(self.request, {"ok": True}, body)
'''

RETRY_BAD = RETRY_GOOD.replace(
    "                    dup, cached = dedup.check(tok, op)\n"
    "                    if dup:\n"
    "                        wire.send_msg(self.request,\n"
    "                                      {\"ok\": True, \"dedup\": True})\n"
    "                        return\n", "")


def test_retry_safety_claimed_mutation_is_clean(tmp_path):
    rel = _write_at(tmp_path, CENTER_REL, RETRY_GOOD)
    assert _protocol_lint(tmp_path, "retry-safety", [rel]) == []


def test_retry_safety_unclaimed_mutation_is_flagged(tmp_path):
    rel = _write_at(tmp_path, CENTER_REL, RETRY_BAD)
    found = _protocol_lint(tmp_path, "retry-safety", [rel])
    assert len(found) == 1, [f.render() for f in found]
    assert "writes `center.n_updates`" in found[0].message
    assert "at-most-once" in found[0].message


def test_retry_safety_nonterminating_dup_arm_is_not_a_claim(tmp_path):
    """A dup arm that falls through to the mutation reapplies it — the
    claim only dominates when the duplicate path exits."""
    bad = RETRY_GOOD.replace(
        "                        wire.send_msg(self.request,\n"
        "                                      {\"ok\": True, \"dedup\": True})\n"
        "                        return\n",
        "                        pass\n")
    rel = _write_at(tmp_path, CENTER_REL, bad)
    found = _protocol_lint(tmp_path, "retry-safety", [rel])
    assert len(found) == 1 and "writes `center.n_updates`" in \
        found[0].message, [f.render() for f in found]


def test_retry_safety_mutating_method_via_lattice(tmp_path):
    """A handler calling a state-class method that mutates (directly or
    through a same-class call) is a mutation site — the §21 lattice."""
    state = '''
class ElasticCenter:
    def __init__(self):
        self.n_updates = 0

    def _bump(self):
        self.n_updates += 1

    def apply(self, body):
        self._bump()

    def read(self):
        return self.n_updates
'''
    srv = RETRY_GOOD.replace("center.n_updates += 1",
                             "center.apply(body)")
    srv_bad = RETRY_BAD.replace("center.n_updates += 1",
                                "center.apply(body)")
    rel_state = _write_at(tmp_path, proto.ASYNC_EASGD_PATH, state)
    rel = _write_at(tmp_path, CENTER_REL, srv)
    assert core.run_lint(str(tmp_path), paths=[rel, rel_state],
                         only=["retry-safety"]) == []
    rel = _write_at(tmp_path, CENTER_REL, srv_bad)
    found = core.run_lint(str(tmp_path), paths=[rel, rel_state],
                          only=["retry-safety"])
    assert len(found) == 1 and "calls mutating `center.apply`" in \
        found[0].message, [f.render() for f in found]
    # read-only calls never flag, claimed or not
    srv_read = RETRY_BAD.replace("center.n_updates += 1",
                                 "x = center.read()")
    rel = _write_at(tmp_path, CENTER_REL, srv_read)
    assert core.run_lint(str(tmp_path), paths=[rel, rel_state],
                         only=["retry-safety"]) == []


def test_retry_safety_idempotent_op_exempt(tmp_path):
    """An op declared idempotent-by-algebra (init/demote/readmit) may
    mutate unclaimed."""
    srv = RETRY_BAD.replace('if op == "push":', 'if op == "demote":')
    rel = _write_at(tmp_path, CENTER_REL, srv)
    assert _protocol_lint(tmp_path, "retry-safety", [rel]) == [], \
        [f.render() for f in _protocol_lint(tmp_path, "retry-safety",
                                            [rel])]


SM_GOOD = '''
MEMBERSHIP_EVENTS = ("worker_join", "worker_leave", "worker_demote")
CENTER_EVENTS = ("center_down", "center_restored")


class Reactor:
    def on_join(self, worker, info):
        pass

    def on_leave(self, worker, info):
        pass

    def on_demote(self, worker, info):
        pass

    def on_readmit(self, worker, info):
        pass


class LogReactor(Reactor):
    def on_join(self, worker, info):
        pass

    def on_leave(self, worker, info):
        pass

    def on_demote(self, worker, info):
        pass

    def on_readmit(self, worker, info):
        pass


class MembershipController:
    def _emit(self, event, worker, hook, **info):
        self.transitions.append((event, worker, info))

    def join(self, worker):
        st = self.workers[worker]
        st["status"] = "live"
        self._emit("worker_join", worker, "on_join")

    def leave(self, worker, reason="exit"):
        st = self.workers[worker]
        st["status"] = "left" if reason == "finished" else "dead"
        self._emit("worker_leave", worker, "on_leave")

    def demote(self, worker):
        st = self.workers[worker]
        st["status"] = "demoted"
        self._emit("worker_demote", worker, "on_demote")
'''


def test_state_machine_good_fixture(tmp_path):
    rel = _write_at(tmp_path, MEMBERSHIP_REL, SM_GOOD)
    assert _protocol_lint(tmp_path, "state-machine", [rel]) == []


def test_state_machine_transition_without_event(tmp_path):
    bad = SM_GOOD.replace(
        '        st["status"] = "demoted"\n'
        '        self._emit("worker_demote", worker, "on_demote")\n',
        '        st["status"] = "demoted"\n')
    rel = _write_at(tmp_path, MEMBERSHIP_REL, bad)
    found = _protocol_lint(tmp_path, "state-machine", [rel])
    msgs = [f.message for f in found]
    assert any("without emitting its declared 'worker_demote'" in m
               for m in msgs), msgs
    assert any("'worker_demote' is never emitted" in m for m in msgs), \
        msgs


def test_state_machine_reactor_missing_hook(tmp_path):
    bad = SM_GOOD.replace(
        "class LogReactor(Reactor):\n"
        "    def on_join(self, worker, info):\n"
        "        pass\n\n"
        "    def on_leave(self, worker, info):\n"
        "        pass\n\n"
        "    def on_demote(self, worker, info):\n"
        "        pass\n",
        "class LogReactor(Reactor):\n"
        "    def on_join(self, worker, info):\n"
        "        pass\n\n"
        "    def on_leave(self, worker, info):\n"
        "        pass\n")
    rel = _write_at(tmp_path, MEMBERSHIP_REL, bad)
    found = _protocol_lint(tmp_path, "state-machine", [rel])
    assert len(found) == 1 and "neither handles nor explicitly " \
        "ignores `on_demote`" in found[0].message, \
        [f.render() for f in found]


def test_state_machine_event_outside_vocabulary(tmp_path):
    bad = SM_GOOD.replace('self._emit("worker_demote", worker',
                          'self._emit("worker_demotedz", worker')
    rel = _write_at(tmp_path, MEMBERSHIP_REL, bad)
    found = _protocol_lint(tmp_path, "state-machine", [rel])
    msgs = [f.message for f in found]
    assert any("outside the declared MEMBERSHIP_EVENTS" in m
               for m in msgs), msgs


def test_state_machine_header_version_guard(tmp_path):
    good = '''
class Handler:
    def _dispatch(self, header, body):
        op = header.get("op")
        trc = header.get("trace")
        island = header["island"]
'''
    rel = _write_at(tmp_path, CENTER_REL, good)
    assert _protocol_lint(tmp_path, "state-machine", [rel]) == []
    bad = good.replace('header.get("trace")', 'header["trace"]') \
              .replace('header["island"]', 'header.get("shard")')
    rel = _write_at(tmp_path, CENTER_REL, bad)
    found = _protocol_lint(tmp_path, "state-machine", [rel])
    msgs = sorted(f.message for f in found)
    assert len(found) == 2, msgs
    assert any("undeclared wire-header field 'shard'" in m
               for m in msgs), msgs
    assert any("subscript-reads v2-optional header field 'trace'" in m
               for m in msgs), msgs


# -- op-table extraction units on a synthetic pair ---------------------------

SYN_SERVER = '''
OP_C = "c"


class Srv:
    def handle(self, header, body):
        op = header.get("op")
        if op == "a":
            pass
        elif op in ("b", "a"):
            pass
        elif op == OP_C:
            pass
'''

SYN_CLIENT = '''
class Cli:
    def send_a(self):
        self.wire.request({"op": "a"})

    def send_b(self):
        header = {"op": "b"}
        self.wire.request(header)

    def send_dynamic(self, op):
        self.wire.request({"op": op})      # not statically evaluable
'''


def _syn_index(tmp_path):
    (tmp_path / "srv.py").write_text(SYN_SERVER)
    (tmp_path / "cli.py").write_text(SYN_CLIENT)
    files = [core.SourceFile(str(tmp_path), "srv.py"),
             core.SourceFile(str(tmp_path), "cli.py")]
    return _PI(files)


def test_protocol_op_table_extraction(tmp_path):
    index = _syn_index(tmp_path)
    spec = proto.EndpointSpec(
        name="syn", server_path="srv.py", dispatch="Srv.handle",
        clients=(proto.ClientSurface("cli.py", "Cli", ("request",)),))
    table = proto.server_op_table(index, spec)
    assert set(table) == {"a", "b", "c"}        # eq, membership, const
    ctab = proto.client_op_table(index, spec)
    assert set(ctab) == {"a", "b"}              # inline + local header
    assert all(s.path == "cli.py" for sites in ctab.values()
               for s in sites)


def test_protocol_dispatch_missing_is_reported(tmp_path):
    """Renaming the dispatch function must fail loudly, not blind the
    checker."""
    rel = _write_at(tmp_path, CENTER_REL,
                    WIRECONTRACT_GOOD.replace("_dispatch", "_route"))
    found = _protocol_lint(tmp_path, "wire-contract", [rel])
    assert len(found) == 1 and "protocol model" in found[0].message \
        and "out of date" in found[0].message, \
        [f.render() for f in found]


def test_protocol_mutation_lattice(tmp_path):
    (tmp_path / "state.py").write_text('''
class State:
    def __init__(self):
        self.n = 0
        self.items = {}

    def read(self):
        return self.n

    def peek(self, k):
        return self.items.get(k)

    def bump(self):
        self.n += 1

    def bump_twice(self):
        self.bump()

    def stash(self, k, v):
        self.items[k] = v

    def retire(self, k):
        self.items.pop(k)
''')
    index = _PI([core.SourceFile(str(tmp_path), "state.py")])
    mut = proto.mutating_methods(index, ("state.State",))
    assert mut == {"__init__", "bump", "bump_twice", "stash", "retire"}


def test_protocol_fold_op_test(tmp_path):
    import ast as _ast
    (tmp_path / "m.py").write_text("X = 'c'\n")
    sf = core.SourceFile(str(tmp_path), "m.py")
    index = _PI([sf])

    def fold(src, value):
        test = _ast.parse(src, mode="eval").body
        return proto.fold_op_test(test, {"op"}, value, sf, index)

    assert fold('op == "a"', "a") is True
    assert fold('op == "a"', "b") is False
    assert fold('op in ("a", "b")', "b") is True
    assert fold('op not in ("a", "b")', "b") is False
    assert fold('op == "a" and leaves is None', "b") is False
    assert fold('op == "a" and leaves is None', "a") is None
    assert fold('op == X', "c") is True
    assert fold('other == "a"', "a") is None


# -- the three live injections (ISSUE 15 acceptance) -------------------------

def _check_baseline_cli(root, *paths):
    return subprocess.run(
        [sys.executable, LINT, "--root", str(root), "--check-baseline",
         *paths], capture_output=True, text=True, timeout=300)


def test_injection_removed_center_handler_arm(tmp_path):
    rel = _inject(tmp_path, CENTER_REL,
                  'elif op == "readmit":', 'elif op == "readmitz":')
    r = _check_baseline_cli(tmp_path, rel)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "no handler arm" in r.stdout and "'readmit'" in r.stdout
    assert "no in-repo client ever sends" in r.stdout     # the dead twin


def test_injection_unclaimed_mutating_handler_path(tmp_path):
    import shutil
    # the mutation lattice needs the state class in scope, exactly as
    # the repo-wide gate has it
    dst = tmp_path / proto.ASYNC_EASGD_PATH
    dst.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(os.path.join(REPO, proto.ASYNC_EASGD_PATH), dst)
    rel = _inject(tmp_path, CENTER_REL,
                  "dup, cached = dedup.check(tok, op)",
                  "dup, cached = False, None")
    r = _check_baseline_cli(tmp_path, "theanompi_tpu")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "retry-safety" in r.stdout
    assert "without a dominating DedupWindow claim check" in r.stdout
    assert "push_delta_leaves" in r.stdout
    assert "push_pull_leaves" in r.stdout


def test_injection_transition_without_event(tmp_path):
    rel = _inject(
        tmp_path, MEMBERSHIP_REL,
        '        self._emit("worker_demote", worker, "on_demote",\n'
        '                   reason=reason, **info)\n',
        '')
    r = _check_baseline_cli(tmp_path, rel)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "state-machine" in r.stdout
    assert "without emitting its declared 'worker_demote'" in r.stdout


# -- cache-key sensitivity + json fingerprints for the new checkers ----------

def test_protocol_findings_cache_and_fingerprints(tmp_path):
    """Protocol findings are engine-scoped: cached at tree level,
    reproduced bit-identically on a warm hit, invalidated by a
    server-file edit, and fingerprinted in --format json."""
    bad = WIRECONTRACT_GOOD.replace(
        '    def pull(self):',
        '    def poke(self):\n'
        '        self._roundtrip({"op": "poke"})\n\n'
        '    def pull(self):')
    rel = _write_at(tmp_path, CENTER_REL, bad)
    cold = _lint_cli(tmp_path, rel, "--only", "wire-contract",
                     "--format", "json")
    c = json.loads(cold.stdout)
    assert c["cache"] == "miss" and cold.returncode == 1
    assert len(c["findings"]) == 1
    fp = c["findings"][0]["fingerprint"]
    assert len(fp) == 12 and int(fp, 16) >= 0
    warm = _lint_cli(tmp_path, rel, "--only", "wire-contract",
                     "--format", "json")
    w = json.loads(warm.stdout)
    assert w["cache"] == "hit" and w["findings"] == c["findings"]
    # fixing the server invalidates the tree entry
    _write_at(tmp_path, CENTER_REL, WIRECONTRACT_GOOD)
    fixed = _lint_cli(tmp_path, rel, "--only", "wire-contract",
                      "--format", "json")
    f = json.loads(fixed.stdout)
    assert f["cache"] == "miss" and f["findings"] == []
    # checker selection keys the cache: a different --only over the
    # same tree is its own entry, not a stale hit of the first
    other = _lint_cli(tmp_path, rel, "--only", "retry-safety",
                      "--format", "json")
    assert json.loads(other.stdout)["cache"] == "miss"


def test_protocol_group_alias():
    r = subprocess.run(
        [sys.executable, LINT, "--only", "protocol", "--check-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr


# -- --diff mode -------------------------------------------------------------

def _git(cwd, *args):
    return subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=str(cwd), capture_output=True, text=True, timeout=60)


def test_diff_mode(tmp_path):
    assert _git(tmp_path, "init", "-q").returncode == 0
    d = tmp_path / "theanompi_tpu"
    d.mkdir()
    (d / "x.py").write_text("x = 1\n")
    (tmp_path / "outside.py").write_text("import time\n")
    _git(tmp_path, "add", "-A")
    assert _git(tmp_path, "commit", "-qm", "init").returncode == 0

    # nothing changed: exits 0 without linting anything
    r = _lint_cli(tmp_path, "--diff", "HEAD")
    assert r.returncode == 0 and "no changed python files" in r.stdout

    # a worktree edit introducing a finding is seen
    (d / "x.py").write_text(RNG_BAD)
    (tmp_path / "outside.py").write_text("import os\n")   # out of scope
    r = _lint_cli(tmp_path, "--diff", "HEAD", "--format", "json")
    out = json.loads(r.stdout)
    assert r.returncode == 1
    assert {f["path"] for f in out["findings"]} == \
        {"theanompi_tpu/x.py"}

    # CACHED = the staged index vs HEAD
    r = _lint_cli(tmp_path, "--diff", "CACHED")
    assert r.returncode == 0 and "no changed python files" in r.stdout
    _git(tmp_path, "add", "-A")
    r = _lint_cli(tmp_path, "--diff", "CACHED")
    assert r.returncode == 1

    # guard rails
    r = _lint_cli(tmp_path, "--diff", "HEAD", "theanompi_tpu/x.py")
    assert r.returncode == 2 and "mutually exclusive" in r.stderr
    r = _lint_cli(tmp_path, "--diff", "NOSUCHREF")
    assert r.returncode == 2
    r = _lint_cli(tmp_path, "--diff", "HEAD", "--update-baseline")
    assert r.returncode == 2 and "--diff" in r.stderr
    # ...and the refusal must hold on an EMPTY changeset too — the
    # early exit 0 must not read as "baseline updated" to automation
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "sync")
    r = _lint_cli(tmp_path, "--diff", "HEAD", "--update-baseline")
    assert r.returncode == 2 and "--diff" in r.stderr


def test_retry_safety_direct_self_attr_mutation(tmp_path):
    """A mutation spelled through the server attr itself
    (``self.center.x`` / ``outer.center.x``) is the same mutation as
    through a closure alias."""
    src = '''
class CenterServer:
    def start(self):
        dedup = self.dedup
        outer = self

        class Handler:
            def _dispatch(self, header, body):
                op = header.get("op")
                tok = header.get("tok")
                if op == "push":
                    outer.center.n_updates += 1
                    dedup.record(tok, op, {"ok": True})
'''
    rel = _write_at(tmp_path, CENTER_REL, src)
    found = _protocol_lint(tmp_path, "retry-safety", [rel])
    assert len(found) == 1 and \
        "writes `outer.center.n_updates`" in found[0].message, \
        [f.render() for f in found]


def test_retry_safety_renamed_self_capture_still_seen(tmp_path):
    """The self-capture alias is DERIVED, not hardcoded: renaming
    ``outer = self`` must not blind the direct-write detection
    (review finding, round 19)."""
    src = '''
class CenterServer:
    def start(self):
        dedup = self.dedup
        srv = self

        class Handler:
            def _dispatch(self, header, body):
                op = header.get("op")
                tok = header.get("tok")
                if op == "push":
                    srv.center.n_updates += 1
                    dedup.record(tok, op, {"ok": True})
'''
    rel = _write_at(tmp_path, CENTER_REL, src)
    found = _protocol_lint(tmp_path, "retry-safety", [rel])
    assert len(found) == 1 and \
        "writes `srv.center.n_updates`" in found[0].message, \
        [f.render() for f in found]


def test_wire_contract_unrelated_dict_does_not_mask_read_diff(tmp_path):
    """A constant-key store into a dict that never reaches a reply must
    not launder its key into the emitted set (review finding: the
    unset-reply-field diff would be silently masked)."""
    bad = WIRECONTRACT_GOOD.replace(
        "        return body", '        return resp.get("shard")'
    ).replace(
        'wire.send_msg(self.request, {"ok": True}, body)',
        'info = {}\n'
        '                    info["shard"] = 1\n'
        '                    wire.send_msg(self.request, '
        '{"ok": True}, body)')
    rel = _write_at(tmp_path, CENTER_REL, bad)
    found = _protocol_lint(tmp_path, "wire-contract", [rel])
    assert len(found) == 1 and "reads reply field 'shard'" in \
        found[0].message, [f.render() for f in found]


def test_schema_drift_probes_stay_jax_free():
    """The live probes — including the §21 probe that drives a real
    RemoteCenter against a stubbed wire — must never drag jax into the
    lint process.  Pinned with the cache OFF: on a warm tree hit the
    probes never run, so the cached variant of this contract
    (test_cli_runs_clean_without_jax) can mask a probe regression —
    exactly how the round-19 `import jax`-before-roundtrip bug in
    RemoteCenter.pull slipped through a green gate."""
    env = dict(os.environ, TPULINT_ASSERT_NO_JAX="1")
    proc = subprocess.run(
        [sys.executable, LINT, "--only", "schema-drift", "--no-cache"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# oracle-pair (ops/ Pallas kernels must keep registered, tested jnp oracles)
# ---------------------------------------------------------------------------

ORACLE_MOD_GOOD = '''
from jax.experimental import pallas as pl


def thing_jnp(x):
    return x + 1


def _thing_pallas(x):
    return pl.pallas_call(lambda i, o: None)(x)


PALLAS_ORACLES = {"_thing_pallas": "thing_jnp"}
'''

ORACLE_TEST_GOOD = '''
def test_thing_pallas_matches_oracle():
    assert _thing_pallas is not thing_jnp
'''


def _oracle_lint(tmp_path, mod_code, test_code=ORACLE_TEST_GOOD):
    from theanompi_tpu.analysis.checkers import oracle_pair
    ops = tmp_path / "theanompi_tpu" / "ops"
    ops.mkdir(parents=True)
    (ops / "mymod.py").write_text(mod_code)
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "test_mymod.py").write_text(test_code)
    return oracle_pair.oracle_pair_findings(str(tmp_path))


def test_oracle_pair_good_fixture(tmp_path):
    assert _oracle_lint(tmp_path, ORACLE_MOD_GOOD) == []


def test_oracle_pair_missing_registry(tmp_path):
    bad = ORACLE_MOD_GOOD.replace(
        'PALLAS_ORACLES = {"_thing_pallas": "thing_jnp"}', "")
    found = _oracle_lint(tmp_path, bad)
    assert len(found) == 1 and "declares no pure-literal" in \
        found[0].message, [f.render() for f in found]


def test_oracle_pair_unregistered_wrapper_and_stale_entry(tmp_path):
    # registry names a ghost wrapper while the real one goes unregistered:
    # both directions of drift must surface
    bad = ORACLE_MOD_GOOD.replace('{"_thing_pallas": "thing_jnp"}',
                                  '{"_gone_pallas": "thing_jnp"}')
    found = _oracle_lint(tmp_path, bad)
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 2, [f.render() for f in found]
    assert "`_thing_pallas` has no PALLAS_ORACLES entry" in msgs
    assert "stale registry entry" in msgs


def test_oracle_pair_oracle_not_defined(tmp_path):
    bad = ORACLE_MOD_GOOD.replace('"thing_jnp"}', '"missing_jnp"}')
    found = _oracle_lint(tmp_path, bad)
    assert len(found) == 1 and "not defined in this module" in \
        found[0].message, [f.render() for f in found]


def test_oracle_pair_untested_pair(tmp_path):
    # the test file references only the wrapper, never the oracle — the
    # equality contract is unpinned even though both names exist
    found = _oracle_lint(tmp_path, ORACLE_MOD_GOOD,
                         "def test_x():\n    return _thing_pallas\n")
    assert len(found) == 1 and "no tests/ file references both" in \
        found[0].message, [f.render() for f in found]


def test_oracle_pair_repo_is_clean_and_jax_free():
    """The real ops/ tree must pass (every kernel paired + tested), and
    the probe itself must never import jax — it runs inside the lint
    CLI's backend-free process."""
    env = dict(os.environ, TPULINT_ASSERT_NO_JAX="1")
    proc = subprocess.run(
        [sys.executable, LINT, "--only", "oracle-pair", "--no-cache"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# compile-surface discipline (PR 20): retrace-hazard / dtype-flow
# ---------------------------------------------------------------------------

RETRACE_BAD = """
import time
import jax
import jax.numpy as jnp

def step(x):
    return x * 2

def install():
    probe = jax.jit(lambda s: s)
    fns = []
    for i in range(4):
        fns.append(jax.jit(step))
    return probe, fns

def shaped(x, n):
    return x + jnp.arange(n)

run = jax.jit(shaped)

def build_train_step(model):
    return jnp.arange(int(time.time()) % 128)
"""

RETRACE_GOOD = """
import jax
import jax.numpy as jnp

def step(x):
    return x * 2

_jitted_step = jax.jit(step)

def shaped(x, n):
    return x + jnp.arange(n)

run = jax.jit(shaped, static_argnums=(1,))
"""


def test_retrace_hazard_bad_fixture(tmp_path):
    """All four hazard classes fire on one file: fresh lambda identity,
    jit-in-loop, a non-static shape param, and a host clock feeding
    shape arithmetic."""
    found = lint_snippet(tmp_path, "bad.py", RETRACE_BAD,
                         "retrace-hazard")
    msgs = [f.message for f in found]
    assert len(found) == 4, msgs
    assert any("fresh lambda at a jax.jit boundary" in m for m in msgs)
    assert any("jax.jit called inside a loop" in m for m in msgs)
    assert any("spends parameter `n` in a shape-static slot" in m
               for m in msgs)
    assert any("host value `time.time()` feeds shape arithmetic" in m
               for m in msgs)
    assert all(f.check == "retrace-hazard" for f in found)


def test_retrace_hazard_good_fixture(tmp_path):
    """Hoisted defs, static_argnums coverage and a loop-free jit are
    all silent."""
    assert lint_snippet(tmp_path, "good.py", RETRACE_GOOD,
                        "retrace-hazard") == []


def test_retrace_hazard_partial_decorator(tmp_path):
    """@functools.partial(jax.jit, static_argnums=...) boundaries get
    the same static-name credit as direct @jax.jit."""
    code = (
        "import functools\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@functools.partial(jax.jit, static_argnums=(1,))\n"
        "def good(x, n):\n"
        "    return x + jnp.arange(n)\n"
        "@jax.jit\n"
        "def bad(x, n):\n"
        "    return x + jnp.arange(n)\n")
    found = lint_snippet(tmp_path, "x.py", code, "retrace-hazard")
    assert len(found) == 1, [f.render() for f in found]
    assert "`bad` spends parameter `n`" in found[0].message


DTYPE_BAD = """
import jax.numpy as jnp
from jax import lax

def all_reduce(g, axis, bias):
    total = lax.psum(g.astype(jnp.bfloat16), axis) + bias
    r = lax.psum(g.astype(jnp.bfloat16), axis)
    out = r + bias
    return total, out

def bucketed(packed, axis):
    flat = packed.astype(jnp.bfloat16)
    outs = [lax.psum(b, axis) for b in flat]
    return outs

def roundtrip(g, wd):
    return g.astype(wd).astype(jnp.float32)
"""

DTYPE_GOOD = """
import jax.numpy as jnp
from jax import lax

NONBITEXACT = {
    "wire_round": "owned chunk rounds to the wire dtype so every rank "
                  "holds the identical bit pattern",
}

def all_reduce(g, axis, bias):
    total = lax.psum(g.astype(jnp.bfloat16), axis).astype(g.dtype) + bias
    r = lax.psum(g.astype(jnp.bfloat16), axis)
    r = r.astype(jnp.float32)
    return total, r + bias

def bucketed(buckets, axis):
    outs = [lax.psum(b.astype(jnp.bfloat16), axis).astype(jnp.float32)
            for b in buckets]
    return outs

def wire_round(g, wd):
    return g.astype(wd).astype(jnp.float32)
"""


def test_dtype_flow_bad_fixture(tmp_path):
    """Direct low-precision accumulate, accumulate through a local,
    pre-bucket wire cast, and an unregistered round-trip all fire."""
    found = lint_snippet(tmp_path, "bad.py", DTYPE_BAD, "dtype-flow")
    msgs = [f.message for f in found]
    assert len(found) == 4, msgs
    assert any("bfloat16 collective result accumulated via `+`" in m
               for m in msgs)
    assert any("`r` accumulated via `+`" in m for m in msgs)
    assert any("wire-cast BEFORE bucketing" in m for m in msgs)
    assert any("round-trip in `roundtrip`" in m and "NONBITEXACT" in m
               for m in msgs)
    assert all(f.check == "dtype-flow" for f in found)


def test_dtype_flow_good_fixture(tmp_path):
    """Immediate re-upcast, per-bucket casts, and a registered
    round-trip are the blessed shapes — zero findings."""
    assert lint_snippet(tmp_path, "good.py", DTYPE_GOOD,
                        "dtype-flow") == []


def test_dtype_flow_stale_registry_entry(tmp_path):
    """Renaming the registry key breaks both directions at once: the
    real chain goes unregistered AND the ghost entry goes stale."""
    code = DTYPE_GOOD.replace('"wire_round":', '"ghost_site":')
    found = lint_snippet(tmp_path, "m.py", code, "dtype-flow")
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 2, [f.render() for f in found]
    assert "round-trip in `wire_round`" in msgs
    assert "stale NONBITEXACT entry 'ghost_site'" in msgs


def test_dtype_flow_registry_must_be_literal(tmp_path):
    code = 'NONBITEXACT = dict(x="y")\n'
    found = lint_snippet(tmp_path, "m.py", code, "dtype-flow")
    assert len(found) == 1 and "pure literal" in found[0].message, \
        [f.render() for f in found]


# -- the two real-file injections, through the CLI gate --- -------------------

def _gate(tmp_path):
    return _lint_cli(tmp_path, "--check-baseline", "--no-cache")


def test_injection_fresh_lambda_in_model_base_cli(tmp_path):
    rel = _inject(
        tmp_path, "theanompi_tpu/models/model_base.py",
        "        from ..parallel.exchanger import BSP_Exchanger\n",
        "        from ..parallel.exchanger import BSP_Exchanger\n"
        "        probe = jax.jit(lambda s: s)\n")
    bad = _gate(tmp_path)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "fresh lambda at a jax.jit boundary" in bad.stdout
    assert "retrace-hazard" in bad.stdout
    (tmp_path / rel).write_text(
        open(os.path.join(REPO, rel)).read())
    good = _gate(tmp_path)
    assert good.returncode == 0, good.stdout + good.stderr


def test_injection_low_precision_accumulate_in_strategies_cli(tmp_path):
    rel = "theanompi_tpu/parallel/strategies.py"
    src = open(os.path.join(REPO, rel)).read()
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src + "\n\ndef _injected_total(g, axis):\n"
                 "    return lax.psum(g.astype(jnp.bfloat16), axis)"
                 " + 1.0\n")
    bad = _gate(tmp_path)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "accumulated via `+`" in bad.stdout
    assert "dtype-flow" in bad.stdout
    p.write_text(src)
    good = _gate(tmp_path)
    assert good.returncode == 0, good.stdout + good.stderr


# -- disk_scoped + result-cache sensitivity ---------------------------------

def test_disk_scoped_is_a_checker_attribute():
    """The partial-run disk probes are declared per checker (one
    attribute the runner folds in), not a CLI carve-out list."""
    from theanompi_tpu.analysis.checkers.schema_drift import RECORDER_PATH
    from theanompi_tpu.analysis.core import CHECKERS, Checker
    assert Checker.disk_scoped == ()
    assert CHECKERS["retrace-hazard"].disk_scoped == ()
    assert RECORDER_PATH in CHECKERS["schema-drift"].disk_scoped
    assert any("*" in pat
               for pat in CHECKERS["oracle-pair"].disk_scoped)


# -- group alias, warm cache, jax-free --------------------------------------

def test_compile_surface_group_alias():
    r = subprocess.run(
        [sys.executable, LINT, "--only", "compile-surface",
         "--check-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr


def test_only_compile_surface_repo_warm_cache_subsecond():
    """Acceptance gate: a warm-cache whole-repo run of just the
    compile-surface group stays sub-second (modulo interpreter
    startup) and finding-identical to the cold run."""
    import time as _time
    cold = subprocess.run(
        [sys.executable, LINT, "--only", "compile-surface", "--format",
         "json"], cwd=REPO, capture_output=True, text=True, timeout=300)
    t0 = _time.monotonic()
    warm = subprocess.run(
        [sys.executable, LINT, "--only", "compile-surface", "--format",
         "json"], cwd=REPO, capture_output=True, text=True, timeout=300)
    elapsed = _time.monotonic() - t0
    w, c = json.loads(warm.stdout), json.loads(cold.stdout)
    assert w["cache"] == "hit"
    assert w["findings"] == c["findings"]
    assert elapsed < 2.5, f"warm compile-surface lint took {elapsed:.2f}s"


def test_compile_surface_stays_jax_free():
    env = dict(os.environ, TPULINT_ASSERT_NO_JAX="1")
    proc = subprocess.run(
        [sys.executable, LINT, "--only", "compile-surface", "--no-cache"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- SARIF emitter ----------------------------------------------------------

def test_sarif_format_findings(tmp_path):
    (tmp_path / "bad.py").write_text(DTYPE_BAD)
    r = _lint_cli(tmp_path, "bad.py", "--only", "dtype-flow",
                  "--format", "sarif")
    assert r.returncode == 1, r.stdout + r.stderr
    log = json.loads(r.stdout)
    assert log["version"] == "2.1.0"
    run = log["runs"][0]
    assert run["tool"]["driver"]["name"] == "tpulint"
    assert [ru["id"] for ru in run["tool"]["driver"]["rules"]] == \
        ["dtype-flow"]
    results = run["results"]
    assert len(results) == 4, results
    for res in results:
        assert res["ruleId"] == "dtype-flow"
        assert res["level"] == "error"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "bad.py"
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1
        fp = res["partialFingerprints"]["tpulintFingerprint/v1"]
        assert len(fp) == 12 and int(fp, 16) >= 0


def test_sarif_format_clean_tree(tmp_path):
    (tmp_path / "ok.py").write_text("x = 1\n")
    r = _lint_cli(tmp_path, "ok.py", "--format", "sarif")
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.loads(r.stdout)["runs"][0]["results"] == []

