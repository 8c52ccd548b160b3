"""Runtime accounting: the start-up ledger + device-memory gauges.

Two failure modes fail dark without this module:

- **Silent retraces, and set-up nobody can explain.**  A shape or
  static-arg change recompiles inside the steady-state loop, and a
  program's start-up spends tens of seconds nobody can split.  JAX
  times each phase that turns a Python function into an executable
  with ``jax.monitoring`` spans: tracing to a jaxpr
  (``jaxpr_trace_duration``), lowering to MLIR
  (``jaxpr_to_mlir_module_duration``) and the backend step
  (``backend_compile_duration``), which either compiles or loads the
  executable from the persistent compilation cache: JAX reports a
  ``/jax/compilation_cache/cache_hits`` event on the compiling thread
  inside that span when it loads.  :class:`RecompileTracker` hears
  all of them and keeps the **start-up ledger**: one row a function
  (JAX's ``fun_name``, or the innermost :func:`compile_label` where
  one is set: ``StepTimer`` labels its warmup, the serving engine its
  prefill/decode compiles, ``make_ddp_train_step`` its step) with its
  trace, lower, compile and load seconds and counts, and a bounded
  list of the spans on ``time.perf_counter()``.  Every instant is
  booked once: spans nest (a jitted ``jnp`` function's trace inside
  the step's, a Pallas kernel traced while its caller lowers), and an
  instant belongs to the innermost open span of the highest phase
  (backend over lowering over tracing).  :func:`startup_summary`
  gives the rows and the seconds of each phase between two instants.
  Into a configured registry it mirrors ``compile.{count,ms}`` (misses
  only) overall and per label, ``compile.load.{count,ms}`` and
  ``compile.trace_lower.ms``.
- **HBM creep.**  Fragmentation or a cache that grows per request eats
  headroom until an OOM with no history.
  :func:`sample_device_memory` reads
  ``jax.local_devices()[i].memory_stats()`` into ``hbm.{bytes_in_use,
  peak_bytes}`` gauges (summed over local devices, per-device under
  ``hbm.dev<i>.*`` when more than one) — sampled by ``StepTimer`` and
  the serving engine, so the JSONL stream and the trace timeline carry
  a memory time series next to the step times.

The tracker needs no configured registry and is always on: importing
this module installs it (``make_train_step`` imports it), so set-up is
on the ledger from the first step built; ``bench.py`` attaches
:func:`runtime_summary` to the BENCH JSON line.  JAX fires these
events only where it traces, lowers or compiles: a warm jitted call
fires none, so the listeners cost nothing in steady state.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, Optional

__all__ = [
    "RecompileTracker",
    "compile_label",
    "current_compile_label",
    "install_recompile_tracker",
    "recompile_tracker",
    "runtime_summary",
    "sample_device_memory",
    "startup_summary",
]

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_RANK = {"trace": 0, "lower": 1, "backend": 2}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}
SPAN_LIMIT = 1 << 16        # the ordered list keeps the newest spans

_TRACKER: Optional["RecompileTracker"] = None
_LISTENER_REGISTERED = False
_LABELS = threading.local()
_OPEN = threading.local()       # .stack: this thread's open spans


class compile_label:
    """Name the region whose compiles should be attributed to ``label``.

    ``with compile_label("gpt2"): step(...)`` — any backend compile
    triggered inside the block (a jit cache miss, i.e. a first compile
    or a retrace) is accounted to ``compile.gpt2.*``.  Labels nest;
    the innermost wins.  Pure host-side thread-local bookkeeping: two
    list ops per block, safe on the disabled fast path."""

    __slots__ = ("label",)

    def __init__(self, label: str):
        self.label = label

    def __enter__(self):
        stack = getattr(_LABELS, "stack", None)
        if stack is None:
            stack = _LABELS.stack = []
        stack.append(self.label)
        return self

    def __exit__(self, *exc):
        _LABELS.stack.pop()
        return False


def current_compile_label() -> Optional[str]:
    stack = getattr(_LABELS, "stack", None)
    return stack[-1] if stack else None


def _row_key(fun_name: Optional[str]) -> str:
    """JAX's name of the function: ``jit(f)``, as lowering and the
    backend name it, is ``f``, as tracing does."""
    name = fun_name or "unlabeled"
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    return name


class _Open:
    """A span whose start JAX has reported and whose end it has not."""

    __slots__ = ("phase", "start", "inner", "hit")

    def __init__(self, phase: str, start: float):
        self.phase, self.start = phase, start
        self.inner = 0.0        # seconds inside it booked to other spans
        self.hit = False        # a backend span that loaded from the cache


def _new_row() -> dict:
    return {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "load_s": 0.0, "n_compiled": 0, "n_loaded": 0}


def _add(row: dict, phase: str, booked_s: float) -> None:
    row[phase + "_s"] += booked_s
    if phase in ("compile", "load"):
        row["n_compiled" if phase == "compile" else "n_loaded"] += 1


def _union_s(intervals) -> float:
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class RecompileTracker:
    """Per-process start-up ledger fed by ``jax.monitoring``.

    ``rows`` is ``{function: {trace_s, lower_s, compile_s, load_s,
    n_compiled, n_loaded}}`` over the process's life; ``spans`` the
    newest :data:`SPAN_LIMIT` spans in the order they ended, each
    ``(start, end, phase, key, booked_s)`` on ``time.perf_counter()``
    with ``phase`` one of ``trace``, ``lower``, ``compile``, ``load``.
    Mirrors into the live registry when one is configured: ``compile.
    {count,ms}`` and ``compile.<label>.{count,ms}`` for compiles
    (persistent-cache misses), ``compile.load.{count,ms}`` for loads,
    ``compile.trace_lower.ms``.  ``ms`` counters are integer
    milliseconds (counters are ints)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.rows: Dict[str, dict] = {}          # guarded-by: self._lock
        self.spans = collections.deque(maxlen=SPAN_LIMIT)  # guarded-by: self._lock
        self.n_spans = 0                         # guarded-by: self._lock
        self.cache = {"hits": 0, "misses": 0}    # guarded-by: self._lock
        self.n_events = 0                        # guarded-by: self._lock
        self.callback_s = 0.0                    # guarded-by: self._lock
        self._trace_lower_s = 0.0                # guarded-by: self._lock

    # -- what the listeners call ------------------------------------------
    def on_compile(self, dur_s: float, label: Optional[str]) -> None:
        """Book one compile of ``dur_s`` to ``label``."""
        label = label or "unlabeled"
        self._book("compile", label, label, dur_s, dur_s)

    def _book(self, phase: str, key: str, label: str, dur_s: float,
              booked_s: float, span: Optional[tuple] = None) -> None:
        trace_lower_ms = 0
        with self._lock:
            if span is not None:
                self.spans.append(span)
                self.n_spans += 1
            _add(self.rows.setdefault(key, _new_row()), phase, booked_s)
            if phase in ("trace", "lower"):
                before = int(self._trace_lower_s * 1e3)
                self._trace_lower_s += booked_s
                trace_lower_ms = int(self._trace_lower_s * 1e3) - before
        from apex_tpu.observability import metrics as _metrics

        reg = _metrics.registry()
        if reg is None:
            return
        ms = int(round(dur_s * 1e3))
        if phase == "compile":
            reg.counter("compile.count").inc()
            reg.counter("compile.ms").inc(ms)
            reg.counter(f"compile.{label}.count").inc()
            reg.counter(f"compile.{label}.ms").inc(ms)
            reg.event("compile", label=label, ms=round(dur_s * 1e3, 3))
        elif phase == "load":
            reg.counter("compile.load.count").inc()
            reg.counter("compile.load.ms").inc(ms)
        elif trace_lower_ms:
            reg.counter("compile.trace_lower.ms").inc(trace_lower_ms)

    def _span_started(self, phase: str, start_wall: float) -> None:
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        stack.append(_Open(phase, start_wall))

    def _cache_event(self, kind: str) -> None:
        for span in reversed(getattr(_OPEN, "stack", None) or ()):
            if span.phase == "backend":
                span.hit = span.hit or kind == "hits"
                break
        with self._lock:
            self.cache[kind] += 1

    def _span_ended(self, phase: str, start_wall: float, end_wall: float,
                    fun_name: Optional[str]) -> None:
        stack = getattr(_OPEN, "stack", None) or []
        span = None
        for i in range(len(stack) - 1, -1, -1):
            if stack[i].phase == phase and stack[i].start == start_wall:
                span = stack[i]
                del stack[i:]
                break
        dur = end_wall - start_wall
        inner = span.inner if span is not None else 0.0
        # the innermost open span of the highest phase owns an instant
        if any(_RANK[s.phase] > _RANK[phase] for s in stack):
            booked, covered = 0.0, inner
        else:
            booked, covered = max(dur - inner, 0.0), dur
        if stack:
            stack[-1].inner += covered
        if phase == "backend":
            phase = "load" if span is not None and span.hit else "compile"
        label = current_compile_label()
        key = label or _row_key(fun_name)
        offset = time.perf_counter() - time.time()
        self._book(phase, key, label or "unlabeled", dur, booked,
                   (start_wall + offset, end_wall + offset, phase, key,
                    booked))

    def _charge(self, t0: float) -> None:
        with self._lock:
            self.n_events += 1
            self.callback_s += time.perf_counter() - t0

    # -- readers ----------------------------------------------------------
    def total_count(self) -> int:
        """Locked count of backend spans, compiles and loads: the
        jax.monitoring listeners mutate ``rows`` from compile threads,
        so readers on the telemetry path must not iterate it bare."""
        with self._lock:
            return sum(r["n_compiled"] + r["n_loaded"]
                       for r in self.rows.values())

    def summary(self) -> dict:
        with self._lock:
            rows = {k: dict(v) for k, v in self.rows.items()}
            trace_lower_s = self._trace_lower_s
        by_label = {k: {"count": v["n_compiled"],
                        "ms": round(v["compile_s"] * 1e3, 3),
                        "load_count": v["n_loaded"],
                        "load_ms": round(v["load_s"] * 1e3, 3)}
                    for k, v in rows.items()
                    if v["n_compiled"] or v["n_loaded"]}
        return {
            "count": sum(v["count"] for v in by_label.values()),
            "ms": round(sum(v["ms"] for v in by_label.values()), 3),
            "load": {
                "count": sum(v["load_count"] for v in by_label.values()),
                "ms": round(sum(v["load_ms"] for v in by_label.values()),
                            3)},
            "trace_lower": {"ms": round(trace_lower_s * 1e3, 3)},
            "by_label": by_label,
        }

    def startup(self, since: Optional[float] = None,
                until: Optional[float] = None) -> dict:
        """The ledger between two ``perf_counter`` instants: spans that
        ended by ``until``, cut at ``since``.  Each phase's seconds are
        the union of its spans' time less that of the higher phases
        (compile, load, lower, trace), so none is counted twice and
        the four add up to the time any span was open."""
        lo = float("-inf") if since is None else since
        hi = float("inf") if until is None else until
        with self._lock:
            spans = [s for s in self.spans if s[1] <= hi and s[1] > lo]
            out = {"dropped": self.n_spans - len(self.spans),
                   "cache": dict(self.cache),
                   "callbacks": {"count": self.n_events,
                                 "s": self.callback_s}}
        rows: Dict[str, dict] = {}
        for _, _, phase, key, booked in spans:
            _add(rows.setdefault(key, _new_row()), phase, booked)
        covered, reached = [], 0.0
        for phase in ("compile", "load", "lower", "trace"):
            covered += [(max(s[0], lo), s[1]) for s in spans
                        if s[2] == phase]
            union = _union_s(covered)
            out[phase + "_s"] = union - reached
            reached = union
        out["rows"] = rows
        out["n_spans"] = len(spans)
        return out

    def reset(self) -> None:
        with self._lock:
            self.rows.clear()
            self.spans.clear()
            self.n_spans = 0
            self.cache = {"hits": 0, "misses": 0}
            self._trace_lower_s = 0.0


# The three listeners.  JAX calls them for every event of their kind;
# the fast path (another event, or no tracker) is a dict lookup.
def _on_span_start(name: str, value: float, **kw) -> None:
    tracker, phase = _TRACKER, _PHASES.get(name)
    if tracker is None or phase is None:
        return
    t0 = time.perf_counter()
    tracker._span_started(phase, value)
    tracker._charge(t0)


def _on_span_end(name: str, start: float, end: float, **kw) -> None:
    tracker, phase = _TRACKER, _PHASES.get(name)
    if tracker is None or phase is None:
        return
    t0 = time.perf_counter()
    tracker._span_ended(phase, start, end, kw.get("fun_name"))
    tracker._charge(t0)


def _on_event(name: str, **kw) -> None:
    tracker, kind = _TRACKER, _CACHE_EVENTS.get(name)
    if tracker is None or kind is None:
        return
    t0 = time.perf_counter()
    tracker._cache_event(kind)
    tracker._charge(t0)


def install_recompile_tracker() -> Optional[RecompileTracker]:
    """Install (or return the existing) process-wide tracker.

    Registers the ``jax.monitoring`` listeners on first call (a span's
    start arrives as a scalar event, its end as a time span, a cache
    hit or miss as an event); there is no unregister API, so they stay
    and fast-path out when the tracker is later discarded.  Returns
    None when jax.monitoring is unavailable (the tracker degrades to
    absent, never raises)."""
    global _TRACKER, _LISTENER_REGISTERED
    if _TRACKER is not None:
        return _TRACKER
    if not _LISTENER_REGISTERED:
        try:
            from jax import monitoring
        except Exception:   # pragma: no cover - jax without monitoring
            return None
        monitoring.register_scalar_listener(_on_span_start)
        monitoring.register_event_time_span_listener(_on_span_end)
        monitoring.register_event_listener(_on_event)
        _LISTENER_REGISTERED = True
    _TRACKER = RecompileTracker()
    return _TRACKER


def recompile_tracker() -> Optional[RecompileTracker]:
    return _TRACKER


def startup_summary(until: Optional[float] = None,
                    since: Optional[float] = None) -> Optional[dict]:
    """The start-up ledger up to the ``time.perf_counter()`` instant
    ``until`` (spans that ended by then; all of them by default), cut
    at ``since``: ``trace_s``, ``lower_s``, ``compile_s``, ``load_s``
    (each instant once), ``rows`` by function, ``cache`` hits and
    misses, ``callbacks`` (how many calls the listeners took and their
    seconds: the ledger's own cost), ``n_spans`` and ``dropped`` (spans
    the bounded list let go).  None without a tracker."""
    tracker = _TRACKER
    return None if tracker is None else tracker.startup(since, until)


def sample_device_memory(emit: bool = True) -> Optional[dict]:
    """Read ``memory_stats()`` across local devices into gauges.

    Returns ``{"bytes_in_use", "peak_bytes", "devices"}`` (sums over
    local devices) or None when the platform reports nothing (CPU
    returns no stats).  With ``emit`` and a configured registry, sets
    ``hbm.bytes_in_use`` / ``hbm.peak_bytes`` (+ per-device
    ``hbm.dev<i>.*`` when more than one device is attached).  Reading
    memory_stats is a cheap local runtime query — no device sync."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:   # pragma: no cover - no backend at all
        return None
    total_in_use = total_peak = 0
    per_dev = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        in_use = int(stats.get("bytes_in_use", 0))
        peak = int(stats.get("peak_bytes_in_use", in_use))
        per_dev.append((in_use, peak))
        total_in_use += in_use
        total_peak += peak
    if not per_dev:
        return None
    out = {"bytes_in_use": total_in_use, "peak_bytes": total_peak,
           "devices": len(per_dev)}
    if emit:
        from apex_tpu.observability import metrics as _metrics

        reg = _metrics.registry()
        if reg is not None:
            reg.gauge("hbm.bytes_in_use").set(total_in_use)
            reg.gauge("hbm.peak_bytes").set(total_peak)
            if len(per_dev) > 1:
                for i, (in_use, peak) in enumerate(per_dev):
                    reg.gauge(f"hbm.dev{i}.bytes_in_use").set(in_use)
                    reg.gauge(f"hbm.dev{i}.peak_bytes").set(peak)
    return out


def runtime_summary() -> dict:
    """The accounting block ``bench.py`` attaches to the BENCH JSON
    line: compile counts/ms (misses, per label), ``load`` counts/ms and
    ``trace_lower`` ms + HBM usage when the platform reports it.  Works
    with or without a configured registry."""
    out: dict = {}
    tracker = _TRACKER
    if tracker is not None:
        out["compile"] = tracker.summary()
    mem = sample_device_memory(emit=False)
    if mem is not None:
        out["hbm"] = mem
    return out


install_recompile_tracker()
