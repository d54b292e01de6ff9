"""The compile/dispatch observatory: a process-wide compile ledger.

``JitRetraceProbe`` (counters.py) counts cache growth per wrapped
callable; this module generalizes it into one process-wide ledger every
surface reads from the same place:

  * per-symbol compile count (jit-cache growth observed across calls),
  * cumulative compile milliseconds (wall time of the calls during
    which the cache grew — cold-call attribution: the compile dominates
    those calls, and it is exactly the figure the r05/r06 bench bugs
    needed machine-visible: a "warm" measurement region whose ledger
    shows compiles was not warm),
  * warm-vs-cold call split (cold = the cache grew during the call),
  * shape-grid / cache-key occupancy (the jit cache's current size per
    symbol — the RETRACE_HAZARD budget is log2-bounded grids, so a
    symbol whose occupancy outgrows its grid is a leaked signature).

Two feeding paths, both lock-cheap:

  * ``JitRetraceProbe`` calls :func:`note_call` transparently for every
    wrapped kernel (kernel.paged_apply, kernel.paged_apply_keep,
    kernel.extract_gather, ...).
  * Call sites that must NOT wrap their jitted callable (the serving
    dispatches — fluidlint's donated-buffer dataflow resolves
    ``serve_step.serve_megakernel`` to its partial-jit wrapper by name, and
    a wrapper object would blind it) register the callable once with
    :func:`watch` and stamp each call with :func:`note_call`; the
    ledger reads the jit cache size itself.

Surfaces: ``/health`` (``compileLedger``), ``/metrics.prom``
(``fluid_compile_*`` per-symbol gauges — symbol cardinality is the
fixed probe set, so no label-fan-out guard is needed), and
:func:`bench_stamp` rides top-level in every bench record.

Kept stdlib-only, like counters.py, so every layer can import it.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

from . import counters as _counters
from . import tracing as _tracing


class _Entry:
    __slots__ = ("name", "fn", "compiles", "retraces", "cold_calls",
                 "warm_calls", "compile_ms", "warm_ms", "cache_size",
                 "_last_size", "_seen_compile")

    def __init__(self, name: str, fn: Optional[Callable]):
        self.name = name
        self.fn = fn
        self.compiles = 0
        self.retraces = 0
        self.cold_calls = 0
        self.warm_calls = 0
        self.compile_ms = 0.0
        self.warm_ms = 0.0
        self.cache_size = -1
        self._last_size: Optional[int] = None
        self._seen_compile = False


def _cache_size(fn) -> int:
    size = getattr(fn, "_cache_size", None)
    if size is None:
        return -1  # not a jitted callable (or an old jax): occupancy off
    try:
        return int(size())
    except (TypeError, ValueError):
        return -1


class CompileLedger:
    """Registry of watched jitted symbols + their compile attribution."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, _Entry] = {}

    # -- registration -------------------------------------------------------
    def watch(self, name: str, fn: Optional[Callable] = None) -> str:
        """Register ``name`` (idempotent). ``fn`` — when it is the jitted
        callable itself — gives the ledger cache-size occupancy; probes
        that track their own cache pass fn=None and report growth via
        ``note_call(grew=...)``."""
        with self._lock:
            entry = self._entries.get(name)
            if entry is None:
                self._entries[name] = entry = _Entry(name, fn)
                if fn is not None:
                    # Baseline at registration: compiles other callers
                    # made earlier are not charged here, but the FIRST
                    # call through this site attributes its own compile
                    # (the warm-up fact bench records need).
                    size = _cache_size(fn)
                    if size >= 0:
                        entry._last_size = size
                        entry.cache_size = size
            elif fn is not None and entry.fn is None:
                entry.fn = fn
                size = _cache_size(fn)
                if size >= 0 and entry._last_size is None:
                    entry._last_size = size
                    entry.cache_size = size
            return name

    # -- attribution --------------------------------------------------------
    def note_call(self, name: str, dur_ms: float,
                  grew: Optional[int] = None) -> None:
        """Attribute one call of a watched symbol. ``grew`` — when the
        caller already measured cache growth (JitRetraceProbe) — is
        authoritative; otherwise the ledger diffs the watched callable's
        jit-cache size across calls. A call during which the cache grew
        is COLD: its wall time lands in compile_ms (the compile
        dominates it), growth past the first observed compile counts as
        a retrace (a leaked signature on a shape-stable path)."""
        with self._lock:
            entry = self._entries.get(name)
            if entry is None:
                self._entries[name] = entry = _Entry(name, None)
            if grew is None and entry.fn is not None:
                size = _cache_size(entry.fn)
                if size >= 0:
                    last = entry._last_size
                    grew = size - last if last is not None \
                        and size > last else 0
                    entry._last_size = size
                    entry.cache_size = size
            grew = int(grew or 0)
            if grew > 0:
                entry.compiles += grew
                entry.cold_calls += 1
                entry.compile_ms += dur_ms
                if entry._seen_compile:
                    entry.retraces += grew
                entry._seen_compile = True
            else:
                entry.warm_calls += 1
                entry.warm_ms += dur_ms

    def track(self, name: str, fn: Callable) -> "_Tracked":
        """Context manager for un-wrappable call sites::

            with ledger.track("serve.megakernel",
                              serve_step.serve_megakernel):
                out = serve_step.serve_megakernel(...)
        """
        self.watch(name, fn)
        return _Tracked(self, name)

    # -- views --------------------------------------------------------------
    def snapshot(self) -> dict:
        """{"symbols": {name: {...}}, "totals": {...}} — the /health and
        bench view. Occupancy refreshes lazily here for watched
        callables that have not been stamped since their cache last
        grew (a /health read must not under-report)."""
        with self._lock:
            symbols: Dict[str, dict] = {}
            tot_compiles = tot_retraces = 0
            tot_compile_ms = 0.0
            tot_cold = tot_warm = 0
            for name, e in sorted(self._entries.items()):
                if e.fn is not None:
                    size = _cache_size(e.fn)
                    if size >= 0:
                        e.cache_size = size
                symbols[name] = {
                    "compiles": e.compiles,
                    "retraces": e.retraces,
                    "coldCalls": e.cold_calls,
                    "warmCalls": e.warm_calls,
                    "compileMs": round(e.compile_ms, 3),
                    "warmMs": round(e.warm_ms, 3),
                    "cacheSize": e.cache_size,
                }
                tot_compiles += e.compiles
                tot_retraces += e.retraces
                tot_compile_ms += e.compile_ms
                tot_cold += e.cold_calls
                tot_warm += e.warm_calls
        return {
            "symbols": symbols,
            "totals": {
                "compiles": tot_compiles,
                "retraces": tot_retraces,
                "compileMs": round(tot_compile_ms, 3),
                "coldCalls": tot_cold,
                "warmCalls": tot_warm,
                "backendCompileMs": round(
                    _counters.get("compile.backend_ms"), 3),
            },
        }

    def bench_stamp(self) -> dict:
        """The bench-record form: per-symbol {compiles, compileMs,
        cacheSize} + totals — compact enough to ride every record, rich
        enough that a warm-up bug (compiles observed inside a measured
        region) is machine-visible instead of re-diagnosed."""
        snap = self.snapshot()
        return {
            "total_compiles": snap["totals"]["compiles"],
            "total_compile_ms": snap["totals"]["compileMs"],
            "retraces": snap["totals"]["retraces"],
            "symbols": {
                name: {"compiles": s["compiles"],
                       "compile_ms": s["compileMs"],
                       "cache_size": s["cacheSize"]}
                for name, s in snap["symbols"].items()},
        }

    def reset(self) -> None:
        """Test isolation only."""
        with self._lock:
            self._entries.clear()


class _Tracked:
    __slots__ = ("_ledger", "_name", "_t0")

    def __init__(self, ledger: CompileLedger, name: str):
        self._ledger = ledger
        self._name = name
        self._t0 = 0.0

    def __enter__(self) -> "_Tracked":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *_exc) -> None:
        self._ledger.note_call(
            self._name, (time.perf_counter() - self._t0) * 1000.0)


ledger = CompileLedger()

# -- jax backend-compile listener (best effort) ------------------------------
# The program's one jax.monitoring compile listener (the ledger's per-call
# attribution is the per-symbol view; this is the ground truth). Each
# backend compile (a persistent-cache load included: it is a compile-path
# stall too) counts compile.backend, adds its wall time to
# compile.backend_ms, and lands in the flight recorder as a `compile`
# span under the ambient span, so /trace says which stage compiled.
# Persistent-cache lookups count compile.cache_hits / compile.cache_misses.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "compile.cache_hits",
                 "/jax/compilation_cache/cache_misses": "compile.cache_misses"}
_listener_installed = False
_listener_lock = threading.Lock()


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event != _BACKEND_COMPILE_EVENT:
        return
    _counters.increment("compile.backend")
    _counters.increment("compile.backend_ms", duration_secs * 1000.0)
    parent = _tracing.current()
    if parent is not None:
        # The event fires as the compile returns: the span ends now.
        t1 = time.perf_counter()
        _tracing.record_span("compile", parent, t1 - duration_secs, t1,
                             fun_name=str(kw.get("fun_name", "?")),
                             seconds=duration_secs)


def _on_event(event: str, **_kw) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        _counters.increment(name)


def install_jax_listener() -> bool:
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return True
        try:
            from jax import monitoring as _mon

            _mon.register_event_duration_secs_listener(_on_duration)
            _mon.register_event_listener(_on_event)
            _listener_installed = True
            return True
        except Exception:  # noqa: BLE001 — observatory is best-effort
            _counters.record_swallow("compile_ledger.jax_listener")
            return False
