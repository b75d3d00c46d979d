"""Tracing/profiling hooks (SURVEY §5.1 — a full subsystem since r9).

Parity: reference util/tracing (opt-in opentelemetry wrapping) + the
nsight runtime-env plugin + `ray timeline`. Three layers, coarsest to
finest:

* :func:`task_timeline` — the cross-process runtime timeline, backed
  by the r9 tracing plane (`_private/tracing_plane.py`): every
  process's flight recorder is drained over the wire (``trace_dump``),
  clocks are aligned, and the result is a Chrome/Perfetto JSON with
  one track per process (driver, each agent, each worker) and flow
  arrows stitching a task's submit → queue/lease → recv/exec/put →
  done spans across processes. Open the output at https://ui.perfetto.dev
  or chrome://tracing. (``ray_tpu.util.metrics.timeline`` remains the
  LEGACY head-events view: head-side RUNNING→FINISHED pairs only, no
  cross-process spans — see its docstring.)

* :func:`annotate` / :func:`annotate_fn` — named user spans. These
  land BOTH in the jax profiler capture (TraceAnnotation, when a
  profile() trace is active) and in the flight recorder, so user code
  shows up on the same task_timeline() as the runtime's own spans.

* :func:`profile` — the device-level jax.profiler capture (XLA ops,
  TPU activity) for TensorBoard/XProf; orthogonal to the task plane.

Knobs: ``RAY_TPU_TRACE`` (master switch, default on) and
``RAY_TPU_TRACE_RING`` (per-process recorder capacity, default 4096;
0 disables). See README "Distributed tracing".

    with ray_tpu.util.tracing.profile("/tmp/tb"):   # device+host trace
        train_step(...)

    with ray_tpu.util.tracing.annotate("sample"):    # named span
        ...

    ray_tpu.util.tracing.task_timeline("out.json")   # Perfetto JSON
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

from ray_tpu._private import tracing_plane as _tp


@contextlib.contextmanager
def profile(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace (XLA ops, TPU activity, host) under
    `log_dir` for TensorBoard/XProf."""
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_trace_annotation = None     # TraceAnnotation, or False without jax


def _profiler_annotation():
    """`jax.profiler.TraceAnnotation`, looked up once and on first use,
    so that a module on the hot path can use `annotate` and still import
    no JAX; False where JAX is unavailable or broken (recorder only)."""
    global _trace_annotation
    if _trace_annotation is None:
        try:
            from jax.profiler import TraceAnnotation
            _trace_annotation = TraceAnnotation
        except Exception:
            _trace_annotation = False
    return _trace_annotation


class annotate:
    """Named span with two sinks: the flight recorder (so it shows on
    task_timeline() next to the runtime's spans, joining the ambient
    trace when called inside a traced task, else starting its own;
    gated by RAY_TPU_TRACE) AND a jax TraceAnnotation, which lands in a
    profile() capture on the clock the device's trace is aligned to.
    Keyword attributes go to both (the recorder's `extra`, the
    annotation's arguments). Near-zero cost when tracing is disabled
    and no jax trace is active. `__enter__` returns the recorder's
    (trace_id, span_id), or None while the recorder is off. What is
    known only once the block has run (`add`) goes to the recorder,
    which writes a span when it ends; the annotation took its arguments
    when it opened."""

    __slots__ = ("_span", "_ta")
    kind = "user"           # the recorder's span kind

    def __init__(self, name: str, **attrs):
        self._span = _tp.span(self.kind, name, root=True,
                              extra=attrs or None)
        ta = _profiler_annotation()
        self._ta = ta(name, **attrs) if ta else None

    def __enter__(self) -> Optional[tuple]:
        ctx = self._span.__enter__()
        if self._ta is not None:
            self._ta.__enter__()
        return ctx

    def add(self, **attrs) -> None:
        """Attributes for the recorder's span, before it ends."""
        if self._span.extra is None:
            self._span.extra = attrs
        else:
            self._span.extra.update(attrs)

    def __exit__(self, *exc) -> None:
        if self._ta is not None:
            self._ta.__exit__(*exc)
        self._span.__exit__(*exc)


def annotate_fn(name: Optional[str] = None):
    """Decorator flavor of `annotate` (reference tracing_helper's
    function wrapping)."""
    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with annotate(name or fn.__qualname__):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def task_timeline(filename: Optional[str] = None,
                  trace_id: Optional[int] = None) -> list:
    """Cross-process Perfetto timeline from the tracing plane's flight
    recorders (r9). Drains every process's recorder via the
    ``trace_dump`` state op (head + local workers + each agent + its
    workers), aligns clocks on the head's monotonic clock (RTT-
    midpoint offsets), and returns Chrome trace-event JSON: one
    Perfetto process per runtime process, spans as complete events,
    parent→child flow arrows across processes. `trace_id` filters to
    one trace. Load the file in https://ui.perfetto.dev.

    For the legacy head-events-only view (task RUNNING→FINISHED pairs,
    no per-process recorders needed) see `ray_tpu.util.metrics
    .timeline`."""
    import json

    from ray_tpu._private import context as _ctx
    dump = _ctx.get_ctx().state_op("trace_dump")
    trace = _tp.chrome_trace(dump.get("processes", []),
                             trace_id=trace_id)
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace
