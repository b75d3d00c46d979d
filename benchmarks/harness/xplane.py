"""From a profiler trace (`.xplane.pb`) to numbers: device busy time as the
union of the intervals in which an operation ran, device time per program
and per operation, and the idle gaps attributed to what the host was doing.
Reads with `jax.profiler.ProfileData` alone; checked against the small
recorded traces in `benchmarks/tests/data/`.

Times are seconds on the trace's own clock. The device's clock runs a
little ahead of the host's in these traces (a program shows as starting
before the host enqueued it), so wherever device time meets a host span
(the window, the gaps) the device is shifted by the largest such lead over
all programs (`clock_shift_s`).

The traced window is a span inside the trace: the harness wraps what it
traces in one `TraceAnnotation` called `WINDOW`, opened after `start_trace`
has returned and closed before `stop_trace` is called. `traced_window` is
the one reader of it: the window's length is the marker's, and busy time is
what the device did inside it, so busy time cannot pass the window however
long the profiler takes to start and to stop. What is a sum over the window
(busy, idle gaps, the operations' seconds of the breakdown) is clipped to
it; what is a mean per event (a program's time, a kernel's) keeps every
event of the trace, so that a count and its time stay a pair.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.trace_window"
EVER = (float("-inf"), float("inf"))
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%?([\w.\-]+)")
_SUFFIX = re.compile(r"[.\d]+$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    dur: float
    stats: dict = dataclasses.field(default_factory=dict, compare=False)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    modules: Dict[int, List[Event]]      # device id -> program executions
    ops: Dict[int, List[Event]]          # device id -> operations
    host: Dict[str, List[Event]]         # host thread name -> spans
    enqueues: Dict[int, float]           # run_id -> host enqueue start


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = Trace({}, {}, {}, {})
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    trace.modules[dev] = _events(line, want_stats=True)
                elif line.name == "XLA Ops":
                    trace.ops[dev] = _events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = _events(line, want_stats=True)
                # two threads may carry one name
                trace.host[f"{line.name}#{len(trace.host)}"
                           if line.name in trace.host else line.name] = evs
                for ev in evs:
                    if ev.name == "DoEnqueueProgram" and "run_id" in ev.stats:
                        trace.enqueues[int(ev.stats["run_id"])] = ev.start
    return trace


def _events(line, want_stats: bool = False) -> List[Event]:
    out = []
    for ev in line.events:
        stats = {}
        if want_stats:
            try:
                stats = {k: v for k, v in ev.stats if k == "run_id"}
            except Exception:
                stats = {}
        out.append(Event(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                         stats))
    out.sort(key=lambda e: e.start)
    return out


# ------------------------------------------------------------ names
def program_name(module_event_name: str) -> str:
    """'jit__step(1234)' -> 'jit__step'."""
    return module_event_name.split("(", 1)[0]


def op_label(op_event_name: str) -> str:
    """An operation's HLO text -> 'category:name', e.g. 'fusion:copy_fusion',
    'kernel:flash_fwd' (a Pallas custom call), 'copy:copy'."""
    m = _OP_NAME.match(op_event_name)
    name = m.group(1) if m else op_event_name[:40]
    name = _SUFFIX.sub("", name) or name
    if "tpu_custom_call" in op_event_name:
        cat = "kernel"
    else:
        head = op_event_name.split(" = ", 1)[-1]
        # the opcode is the word before the first '(' that follows the shape
        m2 = re.search(r"\s([a-z][\w\-]*)\(", head)
        cat = m2.group(1) if m2 else "op"
    return f"{cat}:{name}"


# ------------------------------------------------------------ busy / idle
def union_intervals(spans: List[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(spans):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def busy_intervals(trace: Trace, dev: int, lo: float = EVER[0],
                   hi: float = EVER[1]) -> List[Tuple[float, float]]:
    """The intervals in which an operation ran on device `dev`, on the
    host's clock, cut to `[lo, hi]`."""
    shift = clock_shift_s(trace)
    merged = union_intervals([(e.start + shift, e.end + shift)
                              for e in trace.ops.get(dev, [])])
    return [(max(a, lo), min(b, hi)) for a, b in merged if b > lo and a < hi]


def busy_seconds(trace: Trace, lo: float = EVER[0],
                 hi: float = EVER[1]) -> float:
    """Seconds with an operation running inside `[lo, hi]` of the host's
    clock, averaged over the devices traced."""
    if not trace.ops:
        return 0.0
    return sum(b - a for dev in trace.ops
               for a, b in busy_intervals(trace, dev, lo, hi)) / len(trace.ops)


@dataclasses.dataclass(frozen=True)
class Window:
    lo: float           # the marker's start and end, on the trace's clock
    hi: float
    busy_s: float       # of the device inside it: above no window_s

    @property
    def window_s(self) -> float:
        return self.hi - self.lo


def traced_window(trace: Trace) -> Window:
    """The traced window and the device's busy time inside it: the one
    place either comes from."""
    marks = [e for evs in trace.host.values() for e in evs
             if e.name == WINDOW]
    if len(marks) != 1:
        raise ValueError(
            f"the trace holds {len(marks)} spans called {WINDOW!r}: the "
            "traced window is that span and nothing else, so whoever traces "
            "wraps what it traces in exactly one, between start_trace's "
            "return and the call of stop_trace")
    lo, hi = marks[0].start, marks[0].end
    return Window(lo, hi, busy_seconds(trace, lo, hi))


def device_span(trace: Trace) -> Tuple[float, float]:
    starts = [evs[0].start for evs in trace.ops.values() if evs]
    ends = [max(e.end for e in evs) for evs in trace.ops.values() if evs]
    return (min(starts), max(ends)) if starts else (0.0, 0.0)


def program_times(trace: Trace) -> Dict[str, List[float]]:
    """Device seconds of each execution, by program name (device 0)."""
    out: Dict[str, List[float]] = {}
    for ev in trace.modules.get(min(trace.modules, default=0), []):
        out.setdefault(program_name(ev.name), []).append(ev.dur)
    return out


def self_times(events: List[Event]) -> List[Tuple[Event, float]]:
    """Each event with its duration less that of the events nested in it (a
    `while` holds the operations of its body): self times add up to busy."""
    out: List[List] = []
    stack: List[int] = []
    for ev in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and out[stack[-1]][0].end <= ev.start:
            stack.pop()
        if stack and ev.end <= out[stack[-1]][0].end:
            out[stack[-1]][1] -= ev.dur
        out.append([ev, ev.dur])
        stack.append(len(out) - 1)
    return [(e, max(t, 0.0)) for e, t in out]


def op_times(trace: Trace, lo: float = EVER[0],
             hi: float = EVER[1]) -> Dict[str, float]:
    """Device seconds of self time inside `[lo, hi]` of the host's clock,
    summed by operation label (device 0): they add up to its busy time."""
    shift = clock_shift_s(trace)
    lo, hi = lo - shift, hi - shift             # on the device's clock
    cut = [e if lo <= e.start and e.end <= hi else dataclasses.replace(
               e, start=max(e.start, lo),
               dur=min(e.end, hi) - max(e.start, lo))
           for e in trace.ops.get(min(trace.ops, default=0), [])
           if e.end > lo and e.start < hi]
    out: Dict[str, float] = {}
    for ev, own in self_times(cut):
        label = op_label(ev.name)
        out[label] = out.get(label, 0.0) + own
    return out


def kernel_events(trace: Trace, pattern: str) -> List[Event]:
    """Operations on device 0 whose HLO text matches `pattern`."""
    rx = re.compile(pattern)
    return [e for e in trace.ops.get(min(trace.ops, default=0), [])
            if rx.search(e.name)]


# ------------------------------------------------------------ gaps
def clock_shift_s(trace: Trace) -> float:
    """How far the device clock leads the host's: the largest lead of a
    program's device start over its own host enqueue. 0 with no pairs."""
    lead = 0.0
    for evs in trace.modules.values():
        for ev in evs:
            rid = ev.stats.get("run_id")
            if rid is not None and int(rid) in trace.enqueues:
                lead = max(lead, trace.enqueues[int(rid)] - ev.start)
    return lead


def idle_gaps(trace: Trace, min_gap_s: float = 50e-6,
              lo: float = EVER[0], hi: float = EVER[1]
              ) -> List[Tuple[float, float]]:
    """Gaps between busy intervals of device 0, in host-clock seconds.
    Given a window `[lo, hi]`, the gaps inside it, those at its two ends
    among them: with the busy time they make up the window, but for what
    `min_gap_s` leaves out."""
    busy = busy_intervals(trace, min(trace.ops, default=0), lo, hi)
    if (lo, hi) != EVER:
        busy = [(lo, lo), *busy, (hi, hi)]
    return [(a_end, b_start) for (_, a_end), (b_start, _)
            in zip(busy, busy[1:]) if b_start - a_end >= min_gap_s]


def attribute_gaps(trace: Trace, threads: Optional[List[str]] = None,
                   min_gap_s: float = 50e-6, lo: float = EVER[0],
                   hi: float = EVER[1]) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap (of `[lo, hi]`,
    where given) is cut at the boundaries of the host spans that overlap
    it, and every piece goes to the innermost (shortest) span covering it,
    or to 'no_host_span'. The window's own marker covers everything and
    says nothing, so it is no span here.
    `threads`: substrings of the host thread names to read (default all)."""
    spans: List[Event] = []
    for name, evs in trace.host.items():
        if threads and not any(t in name for t in threads):
            continue
        spans.extend(e for e in evs if e.dur > 0 and e.name != WINDOW)
    spans.sort(key=lambda e: e.start)
    starts = [e.start for e in spans]
    import bisect
    out: Dict[str, float] = {}
    longest = max((e.dur for e in spans), default=0.0)
    for a, b in idle_gaps(trace, min_gap_s, lo, hi):
        first = bisect.bisect_left(starts, a - longest)
        last = bisect.bisect_right(starts, b)
        over = [e for e in spans[first:last] if e.end > a and e.start < b]
        cuts = sorted({a, b, *[min(max(e.start, a), b) for e in over],
                       *[min(max(e.end, a), b) for e in over]})
        for x, y in zip(cuts, cuts[1:]):
            mid = 0.5 * (x + y)
            cover = [e for e in over if e.start <= mid < e.end]
            name = (min(cover, key=lambda e: e.dur).name if cover
                    else "no_host_span")
            out[name] = out.get(name, 0.0) + (y - x)
    return out


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
