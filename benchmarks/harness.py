"""The benchmark's harness: one cell, one run, through the worker's own calls.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration, a
traffic mix and a number of chips.  Everything that belongs to one of them
lives in a file the harness finds by name under the directories the manifest
lists in ``paths`` -- ``configs/<name>.json`` (named by the manifest's
``file``), ``traffic/<name>.json``, ``flops/<name>.py``,
``reference/<name>.py``, ``end_to_end/<metric>.py``,
``layer_metrics/<metric>.py`` -- so a later cell, mix, model or metric is new
files plus new manifest entries, and nothing here branches on a name.

The system is driven as a session drives it: the rule's own ``Worker`` (mesh,
recorder, exchanger), ``worker.build_model``, ``model.compile_iter_fns`` in the
recorder's ``compile`` bracket, ``scale_lr``, ``adjust_hyperp``,
``shuffle_data``, then ``Worker.run``'s inner loop verbatim.  ``Worker.run``
itself runs for epochs, not seconds, and ends in a validation pass, so it
cannot be the loop of a timed window (PERF.md, Open questions).

:func:`run_cell` works on any backend, so the tests rehearse it on the CPU
mesh; :func:`result_line` writes device metrics only for a TPU run, and
``run.py`` refuses to start without one.
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from benchmarks import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
# traces land here (inside the checkout, listed in .gitignore)
OUT_DIR = os.path.join(ROOT, ".bench_out")

WARMUP_STEPS = 5
FIRST_COST_TOL = 0.25          # |first cost - ln(n_class)|, unless the
                               # configuration file states another
# the traced stretch inside a --trace 1 window: untraced lead-in, settle time
# after start_trace (it stalls the host), then the stretch itself
TRACE_LEAD_S, TRACE_SETTLE_S, TRACE_STRETCH_S = 2.0, 0.5, 5.0
SPAN_PREFIX = "bench."         # the harness's own TraceAnnotations
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
RECORDER_BUCKETS = ("load", "stage", "train", "comm", "wait")
# Phases a run times and `setup_s` leaves out.  `runtime_start` is the first
# `jax.devices()`: the chip's runtime coming up, 4-5 s longer in some
# processes than in others on the same machine and the same code.  It is the
# machine's, no change to the program moves it, and left in it made two sets
# of runs of one code differ by more than the set-up's bound (PERF.md, 6).
OUTSIDE_SETUP = ("runtime_start",)


class Refused(RuntimeError):
    """The run cannot be made here (too few devices, files missing)."""


# ---------------------------------------------------------------------------
# the manifest and the files it names
# ---------------------------------------------------------------------------

def load_manifest(path: str = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def find_file(manifest: dict, sub: str, name: str, ext: str) -> str:
    """``<path>/<sub>/<name><ext>`` under the first of the manifest's
    ``paths`` that has it."""
    for p in manifest["paths"]:
        cand = os.path.join(ROOT, p, sub, name + ext)
        if os.path.isfile(cand):
            return cand
    raise Refused(f"no {sub}/{name}{ext} under {manifest['paths']}")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(manifest: dict, sub: str, name: str):
    """Import ``<path>/<sub>/<name>.py`` by its file, under a name of its
    own, so that directories outside the ``benchmarks`` package (a later
    path, the tests' toy cell) load the same way."""
    path = find_file(manifest, sub, name, ".py")
    modname = "_bench_%s_%s" % (sub, name.replace("-", "_").replace(".", "_"))
    if modname in sys.modules and \
            getattr(sys.modules[modname], "__file__", None) == path:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                  # the configuration file
    traffic: dict                 # the traffic file
    end_to_end: List[dict]        # manifest entries that apply to this cell
    per_layer: List[dict]


def load_cell(manifest: dict, workload: str) -> Cell:
    entry = next((w for w in manifest["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise Refused(f"no workload {workload!r} in the manifest; have "
                      f"{[w['name'] for w in manifest['workloads']]}")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(find_file(manifest, "traffic", entry["traffic"],
                                  ".json"))

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(workload, int(entry["chips"]), config, traffic,
                [m for m in manifest["end_to_end"] if applies(m)],
                [m for m in manifest["per_layer"] if applies(m)])


# ---------------------------------------------------------------------------
# what one run measured
# ---------------------------------------------------------------------------

@dataclass
class Stretch:
    """A stretch of steady steps: the window, or the traced part of it."""
    seconds: float = 0.0
    steps: int = 0
    buckets: Dict[str, float] = field(default_factory=dict)  # recorder deltas
    # ms between the returns of successive dispatches: p10, p50, p90
    period_ms: Dict[str, float] = field(default_factory=dict)


@dataclass
class Run:
    """Raw measurements of one run; metric readers take what they need."""
    cell: Cell
    device: Dict[str, Any]                    # platform, kind, count
    peaks: Optional[dict] = None              # the chip's entry in peaks.json
    global_batch: int = 0                     # samples per step, all chips
    steps_per_call: int = 1
    setup_s: float = 0.0
    setup_phases: Dict[str, float] = field(default_factory=dict)
    compile_bucket_s: float = 0.0             # recorder `compile`
    window: Stretch = field(default_factory=Stretch)
    traced: Optional[Stretch] = None          # --trace 1 only
    tables: Optional[trace_lib.TraceTables] = None
    trace_window: Optional[trace_lib.Interval] = None   # the traced stretch
    host_rows: List[trace_lib.Row] = field(default_factory=list)  # Unix ns
    # --trace 1 only: instruction name -> op_name of the train program
    scopes: Dict[str, str] = field(default_factory=dict)
    scope_join_error: Optional[str] = None    # why `scopes` is empty, if so
    # seconds of what runs once the window has closed and the memory is
    # read (the scope join, the reference's training steps): in no metric
    after_window_s: Dict[str, float] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    compiles_in_window: int = 0
    memory_peak_bytes: Optional[int] = None
    flops_per_sample: float = 0.0
    reference: Dict[str, Any] = field(default_factory=dict)
    first_cost: float = float("nan")
    problems: List[str] = field(default_factory=list)   # why not `correct`
    # every number `correct` compared, beside its limit: name -> [value, limit]
    compared: Dict[str, List[float]] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return not self.problems


class _CompileCounter:
    """Counts XLA backend compiles (cache hits included: both mean a program
    was not ready) through ``jax.monitoring``; inert after ``close()``."""

    def __init__(self):
        import jax
        self.times: List[float] = []
        self._open = True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self._open and event == COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)

    def close(self):
        self._open = False


def _tap_recorder(recorder, sink: List[trace_lib.Row]) -> None:
    """Keep every recorder bracket (``load``, ``stage``, ``train``, ...) as
    a host-clock row too.  Only the traced run does this: the brackets live
    inside ``train_iter``, where the benchmark may not put spans."""
    start, end = recorder.start, recorder.end
    t0 = [0]

    def tapped_start():
        t0[0] = time.time_ns()
        start()

    def tapped_end(section):
        dt = end(section)
        sink.append((section, t0[0], time.time_ns()))
        return dt

    recorder.start, recorder.end = tapped_start, tapped_end


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class _Loop:
    """``Worker.run``'s inner loop around one worker and model, verbatim,
    inside the harness's spans, with the counts a window needs."""

    def __init__(self, run: Run, worker, model):
        self.run, self.model = run, model
        self.recorder, self.exchanger = worker.recorder, worker.exchanger
        self.spc = max(1, int(getattr(model, "steps_per_call", 1)))
        self.fused = bool(getattr(worker.exchanger, "fused", False))
        self.costs: List[Any] = []      # device scalars, one per dispatch
        self.returned: List[float] = []  # perf_counter at each one's return
        self.count = self.raised = 0
        # the traced run points this at a list: the spans below then leave
        # a row on the host's Unix clock each, for the idle-gap attribution
        self.sink: Optional[List[trace_lib.Row]] = None

    @contextlib.contextmanager
    def span(self, name: str):
        """The harness's own span around a call into the program: a
        ``TraceAnnotation`` for whoever traces the host by hand (the
        benchmark's own trace does not, see ``trace.py``), and a row."""
        import jax
        t0 = time.time_ns()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
        if self.sink is not None:
            self.sink.append((name, t0, time.time_ns()))

    def dispatch(self) -> None:
        self.count += self.spc
        with self.span("train_iter"):
            self.model.train_iter(self.count, self.recorder)
        if not self.fused:
            with self.span("exchange"):
                self.exchanger.exchange(self.recorder, self.count)
        with self.span("print_train_info"):
            self.recorder.print_train_info(self.count, stride=self.spc)
        self.costs.append(self.model.current_info["cost"])
        self.returned.append(time.perf_counter())

    def issue_until(self, t_end: float) -> None:
        while time.perf_counter() < t_end and not self.raised:
            try:
                self.dispatch()
            except Exception as e:      # a step that raised has failed; its
                self.raised += 1        # donated state is gone, so no
                self.run.problems.append(   # further step is issued
                    f"step {self.count} raised {e!r}")

    def mark(self):
        """Where a stretch starts: the clock, the dispatches so far, the
        recorder's totals."""
        return (time.perf_counter(), len(self.costs),
                {b: float(self.recorder.t_sec_total[b])
                 for b in RECORDER_BUCKETS})

    def since(self, mark) -> Stretch:
        t0, n0, b0 = mark
        t1, n1, b1 = self.mark()
        gaps = sorted(b - a for a, b in zip(self.returned[n0:n1],
                                            self.returned[n0 + 1:n1]))
        return Stretch(t1 - t0, (n1 - n0) * self.spc,
                       {b: b1[b] - b0[b] for b in RECORDER_BUCKETS},
                       {f"p{q}": 1e3 * gaps[len(gaps) * q // 100]
                        for q in (10, 50, 90)} if gaps else {})


def run_cell(manifest: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_process_start: Optional[float] = None,
             early_phases: Optional[Dict[str, float]] = None,
             control: Optional[dict] = None) -> Run:
    """Set up the cell, warm it up, measure a window of ``seconds``.
    ``early_phases`` is what the caller timed between ``t_process_start``
    and this call (``run.py``: the imports, the runtime's start-up).
    ``control`` is worker configuration laid over the cell's: the tests and
    ``readings.py`` run the cell's control with it (``compute_dtype`` one
    precision down, a model class with a fault planted); ``run.py`` has no
    way to pass it."""
    t_start = time.time() if t_process_start is None else t_process_start
    phases: Dict[str, float] = dict(early_phases or {})
    last = [t_start + sum(phases.values())]

    def phase(name):
        now = time.time()
        phases[name] = now - last[0]
        last[0] = now

    cell = load_cell(manifest, workload)
    flops_mod = load_module(manifest, "flops", cell.config["flops"])
    ref_mod = load_module(manifest, "reference", cell.config["reference"])

    import jax

    from theanompi_tpu.utils import jax_cache
    from theanompi_tpu.worker import WORKERS

    jax_cache.configure()           # <checkout>/.jax_cache unless JAX's env
    devices = jax.devices()         # variable names another directory
    if len(devices) < cell.chips:
        raise Refused(f"cell {workload!r} needs {cell.chips} chip(s); JAX "
                      f"sees {len(devices)} {devices[0].platform} device(s)")
    run = Run(cell, {"platform": devices[0].platform,
                     "kind": devices[0].device_kind, "count": len(devices)},
              setup_phases=phases)
    if devices[0].platform == "tpu":
        from benchmarks.peaks import peaks_for
        run.peaks = peaks_for(devices[0].device_kind)
    run.flops_per_sample = float(flops_mod.train_flops_per_sample(cell.config))

    # -- the worker, as a session builds it; unset keys are the program's
    config = dict(cell.config.get("worker_config", {}))
    config.update(cell.traffic["worker_config"])
    config.update(n_workers=cell.chips, seed=int(seed))
    control = dict(control or {})
    modelfile = control.pop("modelfile", cell.config["modelfile"])
    modelclass = control.pop("modelclass", cell.config["modelclass"])
    config.update(control)
    worker = WORKERS[config.get("rule", "bsp")](config)
    model = worker.build_model(modelfile, modelclass)
    phase("build_model")
    compiles = _CompileCounter()
    try:
        worker.recorder.start()
        model.compile_iter_fns(worker.exchanger)
        worker.recorder.end("compile")
        run.compile_bucket_s = float(worker.recorder.t_sec_total["compile"])
        if config.get("scale_lr", True) and worker.size > 1:
            model.scale_lr(worker.size)
        phase("compile_iter_fns")

        # a reference with a training objective is held against the timed
        # path's own first steps, once the window has closed; one without
        # against the evaluation-mode forward pass, here, as before
        follows = getattr(ref_mod, "train_loss", None) is not None
        if not follows:
            run.reference = reference_check(ref_mod, cell.config, model,
                                            seed)
            if not run.reference["ok"]:
                run.problems.append(
                    f"reference check failed: {run.reference}")
        phase("reference_check")

        loop = _Loop(run, worker, model)
        run.steps_per_call = loop.spc
        run.global_batch = int(model.data.global_batch)
        model.adjust_hyperp(0)
        model.data.shuffle_data(0 + model.seed)
        first = FirstSteps(model, loop.spc) if follows else None

        # -- warm-up: the first step compiles, or loads from the cache
        loop.dispatch()
        run.first_cost = float(loop.costs[0])
        if first is not None:
            first.after(1)
        phase("first_step")
        for done in range(2, WARMUP_STEPS + 1):
            loop.dispatch()
            if first is not None:
                first.after(done)
        jax.block_until_ready(model.step_state)
        n_warm = len(loop.costs)
        phase("warmup")

        # -- the window
        run.setup_s = time.time() - t_start - sum(
            phases.get(k, 0.0) for k in OUTSIDE_SETUP)
        opened = loop.mark()
        if trace:
            _traced_window(run, loop, opened[0], seconds)
        else:
            loop.issue_until(opened[0] + seconds)
        jax.block_until_ready(model.step_state)
        run.window = loop.since(opened)
        run.compiles_in_window = compiles.between(opened[0],
                                                  time.perf_counter())
        _after_window(run, loop, model, n_warm)
        # after the clock, the window and the memory reading
        if trace:
            t0 = time.time()
            run.scopes, run.scope_join_error = train_scopes(
                model, worker.exchanger)
            run.after_window_s["scope_join"] = time.time() - t0
        if first is not None:
            t0 = time.time()
            model.step_state = None     # the reference wants the room
            run.reference = train_check(ref_mod, cell, first, loop.costs,
                                        config)
            run.after_window_s["train_check"] = time.time() - t0
            if not run.reference["ok"]:
                run.problems.append(
                    f"the first steps left the reference: {run.reference}")
            run.compared.update(_train_compared(run.reference))
    finally:
        compiles.close()
        # the prefetcher's producer must not outlive the run (it would
        # touch the device while the interpreter shuts down)
        stop = getattr(model.data, "_shutdown", None)
        if stop is not None:
            stop()
    return run


def _traced_window(run: Run, loop: _Loop, t_open: float,
                   seconds: float) -> None:
    """The window of a ``--trace 1`` run: an untraced lead-in, then
    ``start_trace``, time to settle, the traced stretch between two reads
    of the host's clock, ``stop_trace``, and the rest of the window."""
    import jax

    trace_dir = os.path.join(OUT_DIR, "trace", run.cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    loop.issue_until(t_open + min(TRACE_LEAD_S, seconds / 4))
    loop.sink = run.host_rows
    _tap_recorder(loop.recorder, run.host_rows)
    # device planes only: see trace.py for what host tracing costs here
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    loop.issue_until(time.perf_counter() + TRACE_SETTLE_S)
    started, unix0 = loop.mark(), time.time_ns()
    loop.issue_until(started[0] + min(TRACE_STRETCH_S, seconds / 4))
    run.traced, unix1 = loop.since(started), time.time_ns()
    loop.sink = None
    jax.profiler.stop_trace()
    loop.issue_until(t_open + seconds)
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if found:
        run.tables = t = trace_lib.load_xplane(found[0])
        if t.start_unix_ns is not None:
            run.trace_window = (t.on_trace_clock(unix0),
                                t.on_trace_clock(unix1))


def _after_window(run: Run, loop: _Loop, model, n_warm: int) -> None:
    """Memory first (the checks that follow allocate), then what decides
    ``correct``."""
    import jax
    import numpy as np

    from benchmarks.reference import check

    cell = run.cell
    stats = [d.memory_stats() for d in model.mesh.devices.flat]
    if all(s and "peak_bytes_in_use" in s for s in stats):
        run.memory_peak_bytes = max(int(s["peak_bytes_in_use"])
                                    for s in stats)
    finite = np.isfinite(np.asarray(jax.device_get(loop.costs), np.float64))
    run.attempted = run.window.steps + loop.raised * loop.spc
    run.failed = (int((~finite[n_warm:]).sum()) + loop.raised) * loop.spc
    if not finite.all():
        run.problems.append(f"{int((~finite).sum())} of {len(finite)} costs "
                            f"are not finite")
    n_class = int(cell.config["n_class"])
    tol = cell.config.get("first_cost_tol", FIRST_COST_TOL)
    if tol is not None and \
            not abs(run.first_cost - math.log(n_class)) < tol:
        run.problems.append(f"first cost {run.first_cost:.4f} is not within "
                            f"{tol} of ln({n_class})")
    if run.compiles_in_window:
        run.problems.append(f"{run.compiles_in_window} compilation(s) "
                            f"inside the window")
    if run.window.steps == 0:
        run.problems.append("no step completed inside the window")
    layout = layout_problems(model, cell)
    run.problems += layout
    ref = run.reference
    if ref:         # the forward check of set-up; the training one follows
        run.compared = {
            "logit_rel_err": [ref["logit_rel_err"], check.LOGIT_REL_TOL],
            "loss_err": [ref["loss_err"], ref["loss_tol"]]}
    if tol is not None:
        run.compared["first_cost_gap"] = [
            abs(run.first_cost - math.log(n_class)), tol]
    run.compared.update(
        costs_not_finite=[int((~finite).sum()), 0],
        steps_raised=[loop.raised, 0],
        compiles_in_window=[run.compiles_in_window, 0],
        layout_faults=[len(layout), 0])


def train_scopes(model, exchanger):
    """``(scopes, error)``: instruction name -> ``op_name`` of the compiled
    train program, from the ``Compiled``'s own text where the program holds
    one, else ``train_fn`` lowered with the model's own avals and compiled
    again, which is a load from the cache the first step filled.  The join
    is an aid to reading the trace: if the text cannot be had the run goes
    on without scopes, and the trace line's ``device`` says why."""
    import traceback

    fn = model.train_fn
    try:
        if not hasattr(fn, "as_text"):
            spc = max(1, int(getattr(model, "steps_per_call", 1)))
            fn = fn.lower(*model._train_input_avals(spc, exchanger)).compile()
        return trace_lib.scopes_from_hlo(fn.as_text()), None
    except Exception as e:
        print("benchmarks: no compiled text for the scope join:\n"
              + traceback.format_exc(), file=sys.stderr)
        return {}, repr(e)


def layout_problems(model, cell: Cell) -> List[str]:
    """Across chips: every parameter leaf spans all of them and, where the
    traffic says the rule keeps replicas identical, they are bit-identical."""
    import jax
    import jax.numpy as jnp

    n = cell.chips
    if n == 1:
        return []
    problems = []
    params = model.step_state["params"]
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if len(leaf.sharding.device_set) != n:
            problems.append(f"parameter {jax.tree_util.keystr(path)} lies on "
                            f"{len(leaf.sharding.device_set)} of {n} chips")
    if cell.traffic.get("check", {}).get("replicas_bit_identical"):
        same = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.all(x == x[:1]), t))(params)
        for path, ok in jax.tree_util.tree_leaves_with_path(same):
            if not bool(ok):
                problems.append(f"replicas of {jax.tree_util.keystr(path)} "
                                f"differ after the window")
    return problems


def reference_check(ref_mod, config: dict, model, seed: int) -> dict:
    """The system's evaluation-mode forward pass against the plain float32
    reference, same parameters, a seeded batch: the reference's own
    (``batch(config, rng)``), else 8 image crops."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import check

    rng = np.random.RandomState(seed)
    make_batch = getattr(ref_mod, "batch", None)
    x, y = make_batch(config, rng) if make_batch is not None \
        else check.image_batch(config, rng, 8)
    params = model.canonical_host_params()
    bn = model.bn_state

    def system(params, bn, x, y):
        logits, _ = model.apply_model(params, model.stage_input(x),
                                      train=False, rng=None, state=bn)
        cost, _ = model.val_metrics(params, bn, {"x": x, "y": y})
        return logits.astype(jnp.float32), cost

    def reference(params, x, y):
        logits = ref_mod.forward(params, x)
        return logits, check.plain_softmax_loss(logits, y)

    sys_logits, sys_loss = jax.jit(system)(params, bn, x, y)
    with jax.default_matmul_precision("highest"):
        ref_logits, ref_loss = jax.jit(reference)(params, x, y)
    return check.compare(np.asarray(ref_logits), np.asarray(sys_logits),
                         float(ref_loss), float(sys_loss))


def _replica0(tree):
    """Host copy of the first replica of a boxed ``[n_workers, ...]`` tree."""
    import jax
    import numpy as np

    return jax.tree.map(lambda x: np.asarray(x)[0], tree)


class FirstSteps:
    """What the timed path took and gave in its first steps, kept for the
    reference to follow once the window has closed: the batches the loader
    fed (it is tapped for those steps and let go again), the optimizer's
    first moment after one step, the parameters after the last.  The steps
    themselves go through the window's own ``dispatch``."""

    def __init__(self, model, steps_per_call: int):
        from benchmarks.reference import check

        if steps_per_call != 1:
            raise Refused("the training comparison follows single steps; "
                          f"the traffic sets steps_per_call {steps_per_call}")
        self.model, self.n = model, check.TRAIN_STEPS
        self.params0 = model.params     # the host tree the state was placed
        self.batches: List[Any] = []    # from, not the program's state
        self.first_moment = self.params = None
        feed = model.data.next_train_batch

        def tapped(count):
            batch = feed(count)
            self.batches.append(batch)
            return batch

        model.data.next_train_batch = tapped

    def after(self, step: int) -> None:
        import numpy as np

        state = self.model.step_state
        if step == 1:
            moments = state["opt_state"]
            if not isinstance(moments, dict) or "m" not in moments:
                raise Refused("the training comparison reads Adam's first "
                              "moment; the program's optimizer keeps none")
            self.first_moment = _replica0(moments["m"])
        if step == self.n:
            self.params = _replica0(state["params"])
            del self.model.data.next_train_batch        # the tap
            self.batches = [(np.asarray(b["x"]), np.asarray(b["y"]))
                            for b in self.batches]


def train_check(ref_mod, cell: Cell, first: FirstSteps, costs,
                worker_config: dict) -> dict:
    """The reference follows the timed path's first steps and the two are
    compared (``check.compare_steps``).  The optimizer and its learning
    rate are what the configuration file states under ``check.optimizer``,
    scaled by the chips as the harness scales the program's."""
    import jax
    import numpy as np

    from benchmarks.reference import check, plain_opt

    stated = cell.config.get("check", {})
    optimizer = stated.get("optimizer")
    if optimizer is None:
        raise Refused("a reference with train_loss needs check.optimizer "
                      "in its configuration file")
    lr = float(optimizer["learning_rate"])
    if worker_config.get("scale_lr", True) and cell.chips > 1:
        lr *= cell.chips
    got = {"losses": [float(c) for c in
                      np.asarray(jax.device_get(costs[:first.n]))],
           "first_grad": plain_opt.first_gradient(
               first.first_moment, plain_opt.hyper(optimizer)["b1"]),
           "params": first.params}
    ref = check.follow_steps(ref_mod.train_loss, first.params0,
                             first.batches, cell.chips, lr, optimizer)
    return check.compare_steps(ref, got, first.params0,
                               stated.get("grad_leaves", ()))


def _train_compared(ref: dict) -> Dict[str, List[float]]:
    return {name: [ref[name], ref[tol]] for name, tol in (
        ("grad_norm_gap", "grad_norm_tol"),
        ("change_norm_gap", "change_norm_tol"))}


# ---------------------------------------------------------------------------
# the line
# ---------------------------------------------------------------------------

def read_metrics(manifest: dict, entries: List[dict], sub: str,
                 run: Run) -> Dict[str, dict]:
    """Each metric's own reader over the run; a reader that finds nothing to
    read returns None and the metric is left out of the line."""
    out = {}
    for entry in entries:
        value = load_module(manifest, sub, entry["name"]).read(run)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def result_line(manifest: dict, run: Run, trace: bool) -> dict:
    """The contract's object.  Device metrics are written for a TPU run
    only: a CPU rehearsal's line has the keys and no metric."""
    on_chip = run.device["platform"] == "tpu"
    device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": {}, "device": device}
    if on_chip:
        _device_metrics(manifest, run, trace, line)
    # the last key of the line; a reading that is no number (a NaN cost)
    # goes as null, so that the line stays JSON
    line["checks"] = {k: [v if math.isfinite(v) else None, limit]
                      for k, (v, limit) in run.compared.items()}
    return line


def _device_metrics(manifest: dict, run: Run, trace: bool,
                    line: dict) -> None:
    device = line["device"]
    if not trace:
        line["metrics"] = read_metrics(manifest, run.cell.end_to_end,
                                       "end_to_end", run)
        return
    line["metrics"] = read_metrics(manifest, run.cell.per_layer,
                                   "layer_metrics", run)
    t, window = run.tables, run.trace_window
    if t is None or window is None or not t.devices:
        run.problems.append("the trace holds no device plane or no start "
                            "time")
        line["correct"] = False
        return
    used = t.devices[:run.cell.chips]
    device["busy_s"] = sum(trace_lib.busy_ns(d.ops, window)
                           for d in used) / len(used) / 1e9
    device["window_s"] = (window[1] - window[0]) / 1e9
    if run.scopes:
        device["unscoped_share"] = trace_lib.unscoped_share(
            t.devices[0].ops, window, run.scopes)
    elif run.scope_join_error:
        device["scope_join_error"] = run.scope_join_error
    line["breakdown"] = breakdown(run)


def breakdown(run: Run) -> dict:
    """Chip 0: the instructions that took most time, each with the tail of
    its ``op_name`` where the scope join knows it, and the idle time by what
    the host was doing (harness span, and inside ``train_iter`` the recorder
    bracket)."""
    t, window = run.tables, run.trace_window
    dev = t.devices[0]
    host = [(name, t.on_trace_clock(s), t.on_trace_clock(e))
            for name, s, e in run.host_rows]
    busy = trace_lib.union((s, e) for _, s, e in dev.ops)
    return {"device_ops": trace_lib.top_device_ops(dev.ops, window,
                                                   scopes=run.scopes),
            "idle_gaps": trace_lib.attribute_gaps(
                trace_lib.gaps(busy, window), host)}
