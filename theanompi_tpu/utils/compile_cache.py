"""Persistent AOT executable cache: compile once, deserialize forever.

Theano-MPI paid one Theano compile per worker at session start and amortized
it over the whole run; this rebuild pays the equivalent XLA compile on EVERY
process start (26–270 s per program were measured in round 5).  JAX's own
persistent compilation cache (placed by ``utils/jax_cache.py``) absorbs
that under a key that is opaque to us.  This module serializes the compiled
executables OURSELVES (``jax.experimental.serialize_executable``) under a
key WE control, so that an off-line prewarm (``scripts/prewarm_cache.py``)
can be addressed by the run that follows.

It is OPT-IN and off by default: nothing enables it unless config
``compile_cache`` or ``THEANOMPI_COMPILE_CACHE`` names a directory.
Whether it earns its keep beside JAX's cache is ROADMAP D2; PR 21 measured
JAX's cache alone cutting the AlexNet set-up on a v5e from 32 s to 9 s.

**The key** (content-addressed, sha256 over a canonical JSON):

* the StableHLO hash of the lowered program — shapes, dtypes, shardings,
  the whole traced computation;
* ``jax.__version__`` / ``jaxlib.__version__`` (an executable must never
  be loaded into a different runtime than compiled it);
* platform + device kind of the target mesh (``tpu``/``cpu``,
  ``TPU v5 lite``/...) — deliberately NOT ``platform_version``: that was
  the opaque variable suspected of breaking the round-5 XLA-cache
  experiment, and PJRT executables are compatible across patch builds of
  the same device kind (a genuinely incompatible blob still fails loudly
  at deserialize and falls back to a fresh compile);
* mesh axis names + shape;
* the donation signature (which flat args are donated);
* the PRNG impl (``rbg`` vs ``threefry2x32`` change the key dtype AND the
  lowered program, but belt-and-braces);
* caller extras (fn name, rule signature, steps_per_call, ...).

**The fallback ladder** (``get_or_compile``): hit (deserialize, ~ms) →
deserialize-fallback (corrupt blob / version drift → fresh compile,
counter incremented, entry rewritten) → fresh compile + serialize →
serialize-unsupported (backend can't export → fresh compile result is
still returned; only persistence is lost).  The cache can never make a
run fail: every cache-side error degrades to the plain compile.

Entry format, one file per key (``<key>.jexec``): a one-line JSON header
(versions, label, platform — checked BEFORE unpickling) followed by the
pickled ``(payload, in_tree, out_tree)`` triple from
``serialize_executable.serialize``.  A ``manifest.json`` sidecar holds
human-readable metadata per key for ``scripts/prewarm_cache.py`` and
post-mortems.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import sys
import time
from typing import Any, Dict, Optional, Tuple

ENV_CACHE_DIR = "THEANOMPI_COMPILE_CACHE"

_FORMAT = 1
_MAGIC = "theanompi-aot"

# one shared instance per directory, so hit counters aggregate across every
# compile surface of the process (model, bench, prewarm)
_INSTANCES: Dict[str, "CompileCache"] = {}


class _EntryMismatch(Exception):
    """Header/runtime disagreement (version drift, truncation) — triggers
    the deserialize-fallback rung, never an error."""


def _versions() -> Tuple[str, str]:
    import jax
    import jaxlib
    return jax.__version__, jaxlib.__version__


def _mesh_device(mesh):
    """First device of the target mesh — works for runtime meshes AND
    topology-AOT meshes (non-addressable devices still report platform and
    device_kind, which is all the key reads)."""
    if mesh is None:
        import jax
        return jax.devices()[0]
    return next(iter(mesh.devices.flat))


def _donation_signature(lowered) -> Tuple:
    """Which flat args are donated, from the Lowered's args_info (best
    effort — absent attributes degrade to an empty signature rather than
    blocking the cache)."""
    try:
        import jax
        return tuple(bool(getattr(a, "donated", False))
                     for a in jax.tree_util.tree_leaves(lowered.args_info))
    except Exception:
        return ()


def program_key(lowered, mesh=None, extra: Optional[dict] = None) -> str:
    """Content-addressed key for one lowered program on one target."""
    import jax
    dev = _mesh_device(mesh)
    jax_v, jaxlib_v = _versions()
    parts = {
        "stablehlo": hashlib.sha256(
            lowered.as_text().encode("utf-8")).hexdigest(),
        "jax": jax_v,
        "jaxlib": jaxlib_v,
        "platform": getattr(dev, "platform", "?"),
        "device_kind": getattr(dev, "device_kind", "?"),
        "mesh": None if mesh is None else
        {"axes": list(mesh.axis_names),
         "shape": [int(mesh.shape[a]) for a in mesh.axis_names]},
        "donate": list(_donation_signature(lowered)),
        "prng": str(jax.config.jax_default_prng_impl),
        "extra": extra or {},
    }
    blob = json.dumps(parts, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:40]


def program_summary(compiled) -> dict:
    """Best-effort cost/memory summary of one compiled executable for the
    manifest (``scripts/explain_program.py`` reads it): XLA
    ``cost_analysis`` (flops, bytes accessed) + ``memory_analysis``
    (argument/output/temp/code bytes, and their sum as the HBM-peak
    estimate).  A cache hit skips the recompute — the summary was taken at
    write time, when the fresh executable was in hand.  Every probe is
    fenced: a backend that reports nothing (or nonsense like -1) yields a
    smaller dict, never an error."""
    out: Dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        if isinstance(ca, dict):
            for src, dst in (("flops", "flops"),
                             ("bytes accessed", "bytes_accessed"),
                             ("transcendentals", "transcendentals")):
                v = ca.get(src)
                if v is not None and float(v) > 0:
                    out[dst] = float(v)
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for attr, dst in (("argument_size_in_bytes", "argument_bytes"),
                          ("output_size_in_bytes", "output_bytes"),
                          ("temp_size_in_bytes", "temp_bytes"),
                          ("alias_size_in_bytes", "alias_bytes"),
                          ("generated_code_size_in_bytes",
                           "generated_code_bytes")):
            v = getattr(ma, attr, None)
            if v is not None and int(v) >= 0:
                out[dst] = int(v)
        if {"argument_bytes", "output_bytes", "temp_bytes"} <= out.keys():
            # aliased (donated) buffers are counted inside argument_bytes
            # and reused for output — subtract so donation shows up as the
            # memory win it is
            out["peak_hbm_bytes_est"] = (
                out["argument_bytes"] + out["output_bytes"]
                + out["temp_bytes"] - out.get("alias_bytes", 0))
    except Exception:
        pass
    return out


def key_extra(fn: str, model=None, exchanger=None,
              spc: Optional[int] = None) -> dict:
    """The caller-extras dict EVERY compile surface must build the same way
    (model_base, bench.py, scripts/prewarm_cache.py) — a drifted extras
    dict silently forfeits the prewarm hit, so the composition lives here.

    The rule signature is belt-and-braces over the HLO hash: two rules
    that happened to lower identically must still never share an entry.
    ``spc`` is stamped only when the caller passes it (the train surface):
    spc-independent programs (val, the standalone exchange, zero-shadow,
    fsdp-val) are byte-identical across spc variants of a row, and keying
    them per-spc would compile and store one redundant twin per variant.
    """
    extra: Dict[str, Any] = {"fn": str(fn)}
    if model is not None:
        extra["model"] = type(model).__name__
        extra["n_subb"] = int(getattr(model, "n_subb", 1))
        v = int(getattr(model, "pp_interleave", 1) or 1)
        if v > 1:
            # the interleaved pipeline schedule reshapes the whole scan
            # (chunked layers, ring hops, v·M+pp−1 ticks) — interleaved and
            # fill/drain builds of the same row must never share an entry.
            # Stamped only when v > 1 so every pre-existing key (and every
            # prewarmed fill/drain entry) stays byte-stable.
            extra["pp_interleave"] = v
        cfg = getattr(model, "config", {}) or {}
        if str(fn) == "train" and cfg.get("numerics", False) \
                and getattr(model, "_fsdp", None) is None:
            # the numerics health plane adds the aux out-path + cadence
            # cond to the traced TRAIN step only (utils/numerics) —
            # stamped only when effectively ON (fsdp builds stay inert),
            # so every pre-existing key (and every numerics-off build)
            # stays byte-stable
            from . import numerics as _numerics
            extra["numerics"] = _numerics.cadence(cfg)
        if getattr(model, "config", {}).get("update_sharding", False):
            # leaf-wise update-plane sharding reshapes the step (chunked
            # moments, fused allgather) AND its state avals; the threshold
            # moves leaves between the sharded/replicated layouts, so it
            # is part of the identity.  Stamped only when the knob is on —
            # every pre-existing key (zero_opt sessions included) stays
            # byte-stable.
            mb = model.config.get("ushard_min_bytes")
            if mb is None:
                # update_sharding imports jax at module scope — resolve
                # its default only when the config doesn't pin one, so
                # jax-free callers (the schema-drift key_extra probe)
                # can build extras without a backend
                from ..parallel import update_sharding as _us
                mb = _us.DEFAULT_MIN_BYTES
            extra["ushard"] = int(mb)
    if spc is not None:
        extra["spc"] = int(spc)
    if exchanger is not None:
        strat = getattr(exchanger, "strategy", None)
        extra["rule"] = ":".join(
            str(x) for x in (type(exchanger).__name__,
                             getattr(exchanger, "mode", ""),
                             getattr(strat, "name", ""),
                             getattr(exchanger, "exchange_freq", 1)))
        bb = int(getattr(exchanger, "bucket_bytes", 0) or 0)
        if bb:
            # the bucketed-wire schedule (parallel/buckets.py) reshapes
            # the collective sequence: a bucketed and a monolithic build
            # of the same rule must never share an entry (belt-and-braces
            # over the HLO hash, like the rule signature)
            extra["bucket_bytes"] = bb
    if os.environ.get("THEANOMPI_TPU_NO_PALLAS", "0") == "1":
        # the compression ops dispatch to the jnp oracles instead of
        # the Pallas kernels (ops/_pallas_util) — a different program with
        # the same config, so the forced-oracle build must never share an
        # entry with the kernel build.  Stamped only when forced, so every
        # pre-existing key (and every default TPU build) stays byte-stable.
        extra["no_pallas"] = 1
    return extra


class CompileCache:
    """One cache directory: content-addressed ``.jexec`` entries + manifest.

    ``enabled=False`` builds the inert no-op instance — ``get_or_compile``
    then just compiles and reports ``cache: 'off'`` (the pre-cache
    behavior, bit for bit).
    """

    def __init__(self, cache_dir: Optional[str], enabled: bool = True):
        self.cache_dir = cache_dir
        self.enabled = bool(enabled and cache_dir)
        self.counters = {"hits": 0, "misses": 0, "deserialize_fallbacks": 0,
                         "serialize_unsupported": 0}
        if self.enabled:
            try:
                os.makedirs(self.cache_dir, exist_ok=True)
            except OSError as e:
                # an uncreatable dir (read-only mount, a file in the way)
                # must degrade to the plain compile, not crash the run —
                # the module contract: every cache-side error is non-fatal
                print(f"compile_cache: cannot create {self.cache_dir} "
                      f"({e}) — cache disabled", file=sys.stderr)
                self.enabled = False

    def _tick(self, kind: str) -> None:
        """Bump a ladder counter, mirrored into the process telemetry
        registry (``compile_cache.<kind>``) so run reports and the flight
        recorder see where executables came from."""
        self.counters[kind] += 1
        from . import telemetry
        tm = telemetry.active()
        if tm.enabled:
            tm.counter("compile_cache." + kind)

    # -- entry IO ----------------------------------------------------------

    def _path(self, key: str) -> str:
        return os.path.join(self.cache_dir, key + ".jexec")

    def has(self, key: str) -> bool:
        return self.enabled and os.path.exists(self._path(key))

    def _write_entry(self, key: str, label: str, payload: bytes,
                     in_tree, out_tree, device) -> None:
        jax_v, jaxlib_v = _versions()
        header = {"magic": _MAGIC, "format": _FORMAT,
                  "jax": jax_v, "jaxlib": jaxlib_v,
                  "platform": getattr(device, "platform", "?"),
                  "device_kind": getattr(device, "device_kind", "?"),
                  "label": label, "created": time.time()}
        tmp = self._path(key) + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(json.dumps(header).encode("utf-8") + b"\n")
            f.write(pickle.dumps((payload, in_tree, out_tree)))
        os.replace(tmp, self._path(key))     # atomic: readers never see half

    def _parse_header(self, head: bytes) -> dict:
        """Validate one entry's header line.  Raises ``_EntryMismatch`` on
        format/version drift or structural damage."""
        try:
            header = json.loads(head.decode("utf-8"))
        except ValueError as e:
            raise _EntryMismatch(f"unparseable header: {e}") from e
        if header.get("magic") != _MAGIC:
            raise _EntryMismatch("bad magic")
        if header.get("format") != _FORMAT:
            raise _EntryMismatch(
                f"entry format {header.get('format')!r}, reader speaks "
                f"{_FORMAT}")
        jax_v, jaxlib_v = _versions()
        if (header.get("jax"), header.get("jaxlib")) != (jax_v, jaxlib_v):
            raise _EntryMismatch(
                f"built on jax {header.get('jax')}/jaxlib "
                f"{header.get('jaxlib')}, runtime is {jax_v}/{jaxlib_v}")
        return header

    def check_header(self, key: str) -> None:
        """Header-only validation (one readline, no unpickle) — the
        ``load=False`` prewarm rung, so a damaged or version-drifted entry
        is recompiled OFF-line instead of surfacing as a
        deserialize-fallback paying the full compile on the chip."""
        with open(self._path(key), "rb") as f:
            self._parse_header(f.readline())

    def _read_entry(self, key: str):
        """Header-checked read.  Raises ``_EntryMismatch`` on version drift
        or structural damage — the caller's deserialize-fallback rung."""
        with open(self._path(key), "rb") as f:
            header = self._parse_header(f.readline())
            try:
                payload, in_tree, out_tree = pickle.loads(f.read())
            except Exception as e:
                raise _EntryMismatch(f"corrupt body: {e!r}") from e
        return header, payload, in_tree, out_tree

    # -- the ladder --------------------------------------------------------

    def get_or_compile(self, lowered, label: str = "", mesh=None,
                       extra: Optional[dict] = None, load: bool = True):
        """Return ``(compiled, info)`` for one lowered program.

        ``info``: ``cache`` ∈ {hit, miss, deserialize_fallback, off},
        ``compile_secs`` (wall time of whichever path ran — the
        deserialize for a hit, the XLA compile otherwise), ``key``,
        ``serialized`` (did the entry land on disk).

        ``load=False`` (prewarm): a present entry is trusted from its
        header and NOT deserialized — the off-line venue has no runtime
        client to load into; returns ``(None, info)`` on a hit.
        """
        t0 = time.time()
        if not self.enabled:
            compiled = lowered.compile()
            return compiled, {"cache": "off", "key": None, "label": label,
                              "compile_secs": round(time.time() - t0, 3),
                              "serialized": False}
        key = program_key(lowered, mesh=mesh, extra=extra)
        info: Dict[str, Any] = {"cache": "miss", "key": key, "label": label,
                                "serialized": False}
        if self.has(key):
            if not load:
                try:
                    self.check_header(key)
                except Exception as e:
                    # a damaged/drifted entry found OFF-line: recompile it
                    # now, not on the chip's clock
                    self._tick("deserialize_fallbacks")
                    info["cache"] = "deserialize_fallback"
                    info["fallback_reason"] = str(e)[:300]
                    print(f"compile_cache: entry {key[:12]} unusable "
                          f"({str(e)[:200]}) — re-prewarming",
                          file=sys.stderr)
                else:
                    self._tick("hits")
                    self._bump_manifest(key, label)
                    info.update(cache="hit",
                                compile_secs=round(time.time() - t0, 3))
                    return None, info
            else:
                try:
                    from jax.experimental import serialize_executable as se
                    _, payload, in_tree, out_tree = self._read_entry(key)
                    backend = getattr(_mesh_device(mesh), "client", None)
                    compiled = se.deserialize_and_load(
                        payload, in_tree, out_tree, backend=backend)
                    self._tick("hits")
                    self._bump_manifest(key, label)
                    info.update(cache="hit",
                                compile_secs=round(time.time() - t0, 3))
                    return compiled, info
                except Exception as e:
                    # corrupt blob, version drift, backend refusal — rung 2:
                    # count it, recompile fresh, rewrite the entry below
                    self._tick("deserialize_fallbacks")
                    info["cache"] = "deserialize_fallback"
                    info["fallback_reason"] = str(e)[:300]
                    print(f"compile_cache: entry {key[:12]} unusable "
                          f"({str(e)[:200]}) — recompiling", file=sys.stderr)
        if info["cache"] == "miss":
            self._tick("misses")
        t0 = time.time()
        compiled = lowered.compile()
        compile_secs = time.time() - t0
        info["compile_secs"] = round(compile_secs, 3)
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            self._write_entry(key, label, payload, in_tree, out_tree,
                              _mesh_device(mesh))
            self._record_manifest(key, label, compile_secs, len(payload),
                                  mesh, compiled=compiled, extra=extra)
            info["serialized"] = True
        except Exception as e:
            # rung 4: the backend (or this program shape) can't serialize —
            # the fresh compile is still perfectly usable, only persistence
            # is lost.  Harmless by design.
            self._tick("serialize_unsupported")
            info["serialize_error"] = str(e)[:300]
            print(f"compile_cache: cannot serialize {label or key[:12]} "
                  f"({str(e)[:200]}) — running uncached", file=sys.stderr)
        return compiled, info

    # -- manifest ----------------------------------------------------------

    def _manifest_path(self) -> str:
        return os.path.join(self.cache_dir, "manifest.json")

    def _load_manifest(self) -> dict:
        try:
            with open(self._manifest_path()) as f:
                m = json.load(f)
            return m if isinstance(m, dict) else {}
        except (OSError, ValueError):
            return {}

    def _save_manifest(self, m: dict) -> None:
        tmp = self._manifest_path() + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(m, f, indent=1, sort_keys=True)
            os.replace(tmp, self._manifest_path())
        except OSError:
            pass                              # metadata only — never fatal

    def _record_manifest(self, key, label, compile_secs, nbytes, mesh,
                         compiled=None, extra=None):
        jax_v, jaxlib_v = _versions()
        dev = _mesh_device(mesh)
        m = self._load_manifest()
        m[key] = {"label": label, "compile_secs": round(compile_secs, 2),
                  "bytes": int(nbytes), "jax": jax_v, "jaxlib": jaxlib_v,
                  "platform": getattr(dev, "platform", "?"),
                  "device_kind": getattr(dev, "device_kind", "?"),
                  "created": time.time(), "hits": 0}
        if extra:
            # the key_extra dict that went into the program key, so
            # `scripts/explain_program.py --diff` can name WHICH knob
            # split two entries instead of shrugging at opaque hashes
            m[key]["extra"] = dict(extra)
        if compiled is not None:
            # cost/memory summary taken at write time, so a later cache
            # HIT still tells you what you're running (flops, bytes, HBM
            # estimate) — scripts/explain_program.py prints and diffs it
            cost = program_summary(compiled)
            if cost:
                m[key]["cost"] = cost
        self._save_manifest(m)

    def _bump_manifest(self, key, label):
        m = self._load_manifest()
        if key in m:
            m[key]["hits"] = int(m[key].get("hits", 0)) + 1
            m[key]["last_hit"] = time.time()
            self._save_manifest(m)

    # -- reporting ---------------------------------------------------------

    def describe(self) -> str:
        c = self.counters
        return (f"{c['hits']} hit / {c['misses']} miss / "
                f"{c['deserialize_fallbacks']} deserialize-fallback / "
                f"{c['serialize_unsupported']} unserializable "
                f"(dir={self.cache_dir})")


_DISABLED = CompileCache(None, enabled=False)


def get(cache_dir: Optional[str]) -> CompileCache:
    """Shared per-directory instance (process-wide counters)."""
    if not cache_dir:
        return _DISABLED
    cache_dir = os.path.abspath(cache_dir)
    inst = _INSTANCES.get(cache_dir)
    if inst is None:
        inst = _INSTANCES[cache_dir] = CompileCache(cache_dir)
    return inst


def resolve(config: Optional[dict] = None) -> CompileCache:
    """The one resolution rule every entry point shares: the model/worker
    config key ``compile_cache`` (a path enables, ``False``/``""`` force-
    disables), else the ``THEANOMPI_COMPILE_CACHE`` env var, else off.
    ``aot_cache=False`` in the config force-disables regardless (escape
    hatch: keep lazy first-call jit even with a cache dir configured)."""
    config = config or {}
    if config.get("aot_cache", True) is False:
        return _DISABLED
    if "compile_cache" in config:
        d = config["compile_cache"]
        return get(str(d)) if d else _DISABLED
    return get(os.environ.get(ENV_CACHE_DIR) or None)
