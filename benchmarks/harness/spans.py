"""The program's own spans (`ray_tpu/serve/llm/spans.py`: `engine.*`,
`stream.*`) read back from a run's profiler trace with their attributes,
and the device's idle time split by them.

`xplane.Trace` keeps no attribute but `run_id`, so the `.xplane.pb` is read
a second time here (it is still on disk when a run's metrics are read).
The step thread is the host thread that holds `engine.step`. Each idle gap
of device 0 (`xplane.idle_gaps`, the device's clock already shifted onto
the host's) is cut at the boundaries of the step thread's program spans
and every piece goes to the innermost span that covers it, or to
`OUTSIDE`; JAX's own host spans are ignored. The pieces add up to the
idle time. A trace of a program that writes no such span (the parent of
PR 26) gives `None`, and every metric built on it leaves its line.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from benchmarks.harness import xplane
from benchmarks.harness.xplane import Event     # `stats` holds attributes

PREFIXES = ("engine.", "stream.")
STEP = "engine.step"
WAIT = "engine.wait_for_work"
PREFILL = "engine.prefill"
TABLES = "engine.page_tables"
DISPATCH = "engine.decode_dispatch"
FETCH = "engine.fetch_tokens"
INGEST = "engine.ingest"
PUBLISH = "stream.publish"
OUTSIDE = "outside"


def load(path: str) -> Dict[str, List[Event]]:
    """Program spans by host thread, each thread's sorted by start (a
    parent before its children)."""
    from jax.profiler import ProfileData
    out: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans = [Event(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                           dict(ev.stats))
                     for ev in line.events if ev.name.startswith(PREFIXES)]
            if spans:
                spans.sort(key=lambda s: (s.start, -s.dur))
                # two threads may carry one name
                out[f"{line.name}#{len(out)}" if line.name in out
                    else line.name] = spans
    return out


def step_thread(by_thread: Dict[str, List[Event]]) -> Optional[str]:
    for name, spans in by_thread.items():
        if any(s.name == STEP for s in spans):
            return name
    return None


def parents(spans: List[Event]) -> List[Optional[Event]]:
    """For one thread's spans, sorted as `load` sorts them: the span each
    is nested in, or None."""
    out: List[Optional[Event]] = []
    stack: List[Event] = []
    for s in spans:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        out.append(stack[-1] if stack else None)
        stack.append(s)
    return out


def split_gaps(trace: xplane.Trace, spans: List[Event]) -> Dict[str, float]:
    """Idle seconds of device 0 by the innermost of `spans` covering them:
    `xplane.attribute_gaps` with these spans as the only host thread."""
    only = dataclasses.replace(trace, host={"step": spans})
    gaps = xplane.attribute_gaps(only)
    if "no_host_span" in gaps:
        gaps[OUTSIDE] = gaps.pop("no_host_span")
    return gaps


@dataclasses.dataclass
class Reading:
    spans: List[Event]              # the step thread's
    gaps: Dict[str, float]          # idle seconds of device 0 by span name
    idle_s: float

    def named(self, name: str) -> List[Event]:
        return [s for s in self.spans if s.name == name]

    def gap_s(self, *names: str) -> float:
        """Idle seconds under the spans called one of `names`."""
        return sum(self.gaps.get(n, 0.0) for n in names)

    def attr_sum(self, name: str, key: str) -> int:
        return sum(int(s.stats[key]) for s in self.named(name))


def read(trace: xplane.Trace, path: str) -> Optional[Reading]:
    by_thread = load(path)
    thread = step_thread(by_thread)
    if thread is None:
        return None
    spans = by_thread[thread]
    return Reading(spans, split_gaps(trace, spans),
                   sum(b - a for a, b in xplane.idle_gaps(trace)))


def of_run(run: dict) -> Optional[Reading]:
    """The reading of a benchmark run (`run.py`'s `run`), made once; None
    for an untraced run or a program without spans of its own."""
    if "_spans" not in run:
        traced = run["result"].get("traced")
        run["_spans"] = None
        if run.get("trace") is not None and traced and traced.get("dir"):
            run["_spans"] = read(run["trace"],
                                 xplane.find_xplane(traced["dir"]))
    return run["_spans"]


def per_decode_step_ms(run: dict, *names: str) -> Optional[float]:
    """Idle milliseconds under the named spans (all idle time not under
    `engine.wait_for_work` when none is named) per decode dispatch."""
    r = of_run(run)
    if r is None:
        return None
    steps = len(r.named(DISPATCH))
    if not steps:
        return None
    idle = r.gap_s(*names) if names else r.idle_s - r.gap_s(WAIT)
    return 1e3 * idle / steps


def kernel_calls(run: dict, names: List[str]
                 ) -> Optional[Tuple[int, float]]:
    """A kernel in a traced run: how many device events are called after
    `names[0]` (its calls), and the device seconds of the events of all
    `names` (a backward may be two kernels). None where the trace holds
    none; what the calls require is the metric's to work out, from the
    cell's shapes and `required_ops`."""
    trace = run.get("trace")
    if trace is None:
        return None
    events = [xplane.kernel_events(trace, rf"^%{n}[.\d]* = ") for n in names]
    calls, spent = len(events[0]), sum(e.dur for evs in events for e in evs)
    return (calls, spent) if calls and spent else None
