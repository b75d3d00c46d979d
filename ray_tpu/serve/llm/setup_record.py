"""What an engine paid before its first steady step, kept for as long as
the engine lives: the seconds of each construction phase and a table of
every program it built.

The flight recorder keeps a few seconds of a busy replica, so the spans of
its start are gone when somebody asks why it took a minute. A
`SetupRecord` keeps the same facts as plain numbers (`engine_stats()`'s
`setup` and `programs`), and they go to the metrics plane as they are made
(`ray_tpu_llm_setup_s` by the engine's phases, `ray_tpu_llm_program_build_s`
and `ray_tpu_llm_program_builds` here, a build at a time).

A program is built by the call that first dispatches it, inside JAX. JAX
tells: it raises monitoring events where it traces, lowers and compiles,
and nowhere else, each with the function's name
(`jax/_src/dispatch.py:LogElapsedTimeContextManager`: a scalar when a phase
starts, its duration when it ends; `jax/_src/compiler.py`: whether the
persistent cache held the program). One set of listeners a process,
registered with the first record, hears them; a thread-local says whose
engine runs on the calling thread (`watch`, set by `EngineCore.step()`), so
a dispatch that builds nothing costs the engine no call, no clock and no
wrapper: only a test of `open` after it. A build that begins on a watched
thread opens an `engine.build_program` span there and then (the
profiler's annotation covers the trace, the lowering and the compile; what
is known when the build ends rides the recorder), and the engine closes it
where it sees `open` set. A program built a second time (another shape,
`jax.clear_caches()`) is a row of its own with `rebuild` set.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from ray_tpu.serve.llm import spans as _sp

# the engine's jitted functions, by the name JAX reports them under
PROGRAMS = frozenset(("_step", "_next", "_place", "_pre", "init"))
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    # the backend's compile, or the persistent cache's retrieval
    "/jax/core/compile/backend_compile_duration": "compile_s"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
# rebuilds kept beside the first builds (which the buckets bound)
MAX_REBUILDS = 64

_watching = threading.local()   # .record: whose engine runs on this thread
_listen_lock = threading.Lock()
_listening = False


def _row(fun_name: str) -> Optional[dict]:
    """The open row of the build JAX reports a phase of, in the record
    that watches this thread (opened if this is the build's first phase);
    None where nobody watches or `fun_name` ('_step', or 'jit(_step)' from
    the lowering on) is no engine's program."""
    rec = getattr(_watching, "record", None)
    if rec is None:
        return None
    if fun_name.startswith("jit("):
        fun_name = fun_name[4:-1]
    return rec._build(fun_name) if fun_name in PROGRAMS else None


def _on_start(event: str, value, fun_name: str = "", **kw) -> None:
    if event in _PHASES:
        _row(fun_name)


def _on_duration(event: str, seconds: float, fun_name: str = "",
                 **kw) -> None:
    key = _PHASES.get(event)
    if key is not None:
        row = _row(fun_name)
        if row is not None:
            row[key] += seconds
    elif event == _SAVED:
        _note_cache("saved_s", seconds)


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT:
        _note_cache("cache_hit", True)


def _note_cache(key: str, value) -> None:
    """The compile cache speaks inside the backend's phase and names no
    function: it means the build that is open on this thread."""
    rec = getattr(_watching, "record", None)
    if rec is not None and rec.open is not None:
        rec.open[key] = value


def _listen() -> None:
    """Register the process's listeners, once. JAX calls them only where
    it traces or compiles."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        import jax.monitoring as monitoring
        monitoring.register_scalar_listener(_on_start)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _listening = True


class SetupRecord:
    """`phases`: seconds by span name (`engine.setup` and its children;
    the engine fills it, `engine._SetupPhase`).
    `programs`: a row a build, in the order they were made: `program`,
    `bucket` (a `_pre`'s padded length, else 0), `rebuild`, `step` (the
    engine step it fell in; 0 before the first), `t_mono_ns` at its start,
    `wall_s` from the start of JAX's first phase to the return of the call,
    `trace_s` / `lower_s` / `compile_s` inside it, `cache_hit` (the
    persistent cache held it: `compile_s` is a retrieval) and then
    `saved_s`, the compile the cache says it saved."""

    def __init__(self, series: Optional[dict] = None):
        self.phases: Dict[str, float] = {}
        self.programs: List[dict] = []
        self.open: Optional[dict] = None    # the build JAX is in, if any
        self.bucket = 0         # of the `_pre` about to be dispatched
        self._step = 0
        self._span: Optional[_sp.span] = None
        self._built: set = set()            # (program, bucket)
        self.series = series                # the metrics plane's, or None
        _listen()

    def watch(self, step: int) -> None:
        """What JAX builds on this thread from here on is this engine's,
        in engine step `step`."""
        self._step = step
        _watching.record = self

    @staticmethod
    def unwatch() -> None:
        _watching.record = None

    def count(self, program: str) -> int:
        """Programs of that name built (their rebuilds apart)."""
        return sum(1 for p, _ in self._built if p == program)

    def of_step(self, step: int) -> List[dict]:
        return [e for e in self.programs if e["step"] == step]

    def _build(self, program: str) -> dict:
        """JAX is in a phase of `program`'s build: its row, opened (and the
        one before closed, whose call has returned) unless it is open."""
        if self.open is None or self.open["program"] != program:
            self.close()
            bucket = self.bucket if program == "_pre" else 0
            rebuild = (program, bucket) in self._built
            self._span = _sp.span(_sp.BUILD, program=program, bucket=bucket,
                                  rebuild=int(rebuild))
            self._span.__enter__()
            self.open = {
                "program": program, "bucket": bucket, "rebuild": rebuild,
                "step": self._step, "t_mono_ns": time.monotonic_ns(),
                "wall_s": 0.0, "trace_s": 0.0, "lower_s": 0.0,
                "compile_s": 0.0, "cache_hit": False}
        return self.open

    def close(self) -> None:
        """The call that built the open row has returned."""
        row, self.open = self.open, None
        if row is None:
            return
        row["wall_s"] = (time.monotonic_ns() - row["t_mono_ns"]) * 1e-9
        span, self._span = self._span, None
        span.add(**{k: row[k] for k in (
            "cache_hit", "trace_s", "lower_s", "compile_s")})
        span.__exit__(None, None, None)
        self._built.add((row["program"], row["bucket"]))
        self.programs.append(row)
        if row["rebuild"]:
            rebuilds = [e for e in self.programs if e["rebuild"]]
            if len(rebuilds) > MAX_REBUILDS:
                self.programs.remove(rebuilds[0])
        if self.series:
            inside = 0.0
            for phase in ("trace", "lower", "compile"):
                inside += row[phase + "_s"]
                self.series["build_s"].inc(
                    row[phase + "_s"],
                    {"program": row["program"], "phase": phase})
            # dispatch, argument handling, the cache's bookkeeping
            self.series["build_s"].inc(
                max(0.0, row["wall_s"] - inside),
                {"program": row["program"], "phase": "rest"})
            self.series["builds"].inc(1, {
                "cache": "hit" if row["cache_hit"] else "miss",
                "rebuild": str(int(row["rebuild"]))})
