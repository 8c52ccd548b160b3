"""Spans + StepTimer: the shared timing path for training and benches.

JAX dispatch is asynchronous: ``fn(x)`` returns a future-like array, so
host wall time between two ``time.perf_counter()`` calls measures
*dispatch*, not device work.  Two tools here handle that:

- :func:`fence` — block until a value's computation really finished,
  by materializing one scalar of it through numpy: a device-to-host
  read cannot return before the value exists.  :class:`StepTimer`
  times through it so every row is fenced the same way.
- :class:`StepTimer` — the steady-state step-timing protocol of
  ``bench.py``: warmup calls each fenced (absorbing compilation), then
  ``iters`` back-to-back dispatches with ONE trailing fence, so queue
  drain amortizes across the timed iterations.

:func:`span` measures host wall time (enter → exit) and is the right
tool for host-side phases (data loading, a whole train step including
its host work, a measurement-campaign stage); pass ``fence_on=`` to
fence a device value at exit when the span closes over async device
work.  Never use spans *inside* a jit body — they would measure
trace-time only; record step-boundary values instead
(``metrics.record_step_metrics``); inside a jitted program the span is
``jax.named_scope``, read by ``benchmark/scope_times.py``.
"""

from __future__ import annotations

import threading
import time
from contextlib import ContextDecorator
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.observability import metrics as _metrics

__all__ = ["span", "StepTimer", "fence"]


def fence(x: Any) -> None:
    """Block until the computation producing ``x`` has finished.

    Materializes ONE scalar of the first leaf via numpy (a
    device-to-host read cannot return before the value exists).
    Non-scalar leaves are sliced down to one
    element *on device* first, so fencing a large tensor (a grad tree,
    a logits array) costs a one-scalar transfer, not a full
    device-to-host copy inside the timed window — the same recipe as
    the ad-hoc ``_sync`` helpers this replaced.  Falls back to
    ``block_until_ready`` for values numpy cannot materialize.
    """
    leaves = jax.tree_util.tree_leaves(x)
    if not leaves:
        return
    leaf = leaves[0]
    try:
        if getattr(leaf, "ndim", 0) and getattr(leaf, "size", 1):
            leaf = jnp.ravel(leaf)[0]   # device-side: 1 scalar crosses
        if getattr(leaf, "size", 1):
            float(np.asarray(leaf))
    except (TypeError, ValueError):
        jax.block_until_ready(leaf)


class span(ContextDecorator):
    """Measure a named region: ``with span("fwd"): ...`` or as a
    decorator ``@span("fwd")``.

    When telemetry is disabled the context manager is a no-op (no
    timestamp taken — the fast path).  When enabled it records a
    ``span`` observation named ``name`` and, if the registry's
    ``profiler`` feature flag is set, additionally wraps the region in
    ``jax.profiler.TraceAnnotation`` so xprof shows the same names.
    """

    def __init__(self, name: str, fence_on: Any = None,
                 tags: Optional[dict] = None):
        self.name = name
        self.tags = tags
        self._fence_on = fence_on
        # per-thread stack of (t0, annotation): ContextDecorator reuses
        # ONE instance for every call of a decorated function, so
        # nested / recursive / multi-threaded entries must not clobber
        # each other's start time (a single _t0 slot dropped the outer
        # span record and leaked the outer TraceAnnotation)
        self._local = threading.local()

    def _thread_stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def __enter__(self):
        reg = _metrics.registry()
        if reg is None:
            self._thread_stack().append(None)   # mark: telemetry off
            return self
        ann = None
        if reg.profiler:
            try:
                from jax.profiler import TraceAnnotation

                ann = TraceAnnotation(self.name)
                ann.__enter__()
            except Exception:
                ann = None
        self._thread_stack().append((time.perf_counter(), ann))
        return self

    def __exit__(self, exc_type, exc, tb):
        stack = self._thread_stack()
        entry = stack.pop() if stack else None
        if entry is None:
            return False
        t0, ann = entry
        if self._fence_on is not None:
            fence(self._fence_on)
        dur = time.perf_counter() - t0
        if ann is not None:
            ann.__exit__(exc_type, exc, tb)
        reg = _metrics.registry()
        if reg is not None:
            extra = {"tags": self.tags} if self.tags else {}
            reg.observe_span(self.name, dur, **extra)
        return False


class StepTimer:
    """Steady-state step timing with BENCH_r0x protocol + fencing.

    Two protocols, matching the two call shapes the repo's benches use:

    - :meth:`time` — carry protocol (``bench.py``): ``fn(carry) ->
      carry`` where ``carry`` is ``None`` on the first call and the
      returned tuple's LAST element is fenced (by convention the loss).
      Warmup iterations are fenced individually; the timed iterations
      dispatch back-to-back with one trailing fence.
    - :meth:`time_call` — fixed-args protocol: ``fn(*args)``
      repeatedly; the whole output's first leaf is fenced.

    Both return mean seconds per timed iteration, keep the last output
    on ``self.last`` (donating steps thread state through the loop),
    and record a ``step.<name>`` span observation when telemetry is on.

    ISSUE 4 wiring (all no-ops when telemetry is off): warmup runs
    under ``compile_label(name)`` and the timed window under
    ``compile_label(f"{name}.retrace")`` so the recompile tracker
    attributes expected compiles vs silent retraces; each recording
    samples the HBM gauges and feeds the throughput-regression
    detector (via ``observe_span``).
    """

    def __init__(self, name: str, warmup: int = 2, iters: int = 10,
                 fence_fn: Callable[[Any], None] = fence):
        self.name = name
        self.warmup = warmup
        self.iters = iters
        self._fence = fence_fn
        self.last: Any = None

    def _record(self, avg_s: float) -> None:
        reg = _metrics.registry()
        if reg is not None:
            reg.observe_span(f"step.{self.name}", avg_s,
                             iters=self.iters, warmup=self.warmup)
            # HBM time series rides the step cadence (no device sync —
            # memory_stats is a local runtime query; None on CPU)
            from apex_tpu.observability import device as _device

            _device.sample_device_memory()

    def time(self, fn: Callable[[Any], Any]) -> float:
        from apex_tpu.observability.device import compile_label

        out = None
        # warmup absorbs compilation — label it so the recompile
        # tracker attributes compile.{count,ms} to this timer's name
        with compile_label(self.name):
            for _ in range(self.warmup):
                out = fn(out)
                self._fence(out[-1])
        t0 = time.perf_counter()
        with compile_label(f"{self.name}.retrace"):
            # a compile in the TIMED window is a silent retrace — the
            # label makes it visible as compile.<name>.retrace.*
            for _ in range(self.iters):
                out = fn(out)
            self._fence(out[-1])
        avg = (time.perf_counter() - t0) / self.iters
        self.last = out
        self._record(avg)
        return avg

    def time_call(self, fn: Callable[..., Any], *args) -> float:
        from apex_tpu.observability.device import compile_label

        out = None
        with compile_label(self.name):
            for _ in range(self.warmup):
                out = fn(*args)
                self._fence(out)
        t0 = time.perf_counter()
        with compile_label(f"{self.name}.retrace"):
            for _ in range(self.iters):
                out = fn(*args)
            self._fence(out)
        avg = (time.perf_counter() - t0) / self.iters
        self.last = out
        self._record(avg)
        return avg
