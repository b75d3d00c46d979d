"""A traced program's device time by what the program says it was doing: the
regions of `ray_tpu/models/regions.py` (`jax.named_scope("r.<name>")` in
the model blocks) read back from the operations' metadata.

`jax.profiler.ProfileData` gives an `XLA Ops` event's name, times and its
own three stats; the `.xplane.pb` holds more on the event's *metadata*: the
operation's `tf_op` (JAX's `op_name` path: named scopes and transformations,
`jit(_step)/r.ffn/dot_general`,
`jit(loss)/transpose(jvp())/while/body/closed_call/checkpoint/r.ffn/...`),
`source` (file and line), `hlo_category`, `program_id` (the number in the
`XLA Modules` event's `jit__step(<id>)`), `flops` and `bytes_accessed`. So
the file is read here as a protobuf on the wire (four message types,
varints and length-delimited fields; no generated module is imported), for
device 0's `XLA Ops` and `XLA Modules` lines alone. `Async XLA Ops` overlap
the operations and are not counted.

An operation's region is the innermost `r.*` name anywhere in its path
(inside `jvp(...)` / `transpose(...)` too), else `UNSCOPED`. Its pass is
`recompute` where the path holds `rematted_computation`, else `backward`
where it holds `transpose(`, else `forward` where it holds `jvp(`, else
`plain` (a serving program; in a training step what the step adds around
the loss: the optimiser). A fusion belongs to the region of the instruction
XLA names it by (its root, as a rule): a residual addition fused into the
next norm's reduction counts with the norm. An event's time is its self
time (a `while` holds its body's operations), so a program's regions add up
to its operations' time. Events are filed under an execution by
containment in an `XLA Modules` event, and the execution under a program by
that event's name; an operation whose `program_id` is another program's is
left out. A program none of whose operations carries an `r.*` name (a tree
from before the regions; an executable loaded from a compile cache filled
before them, since metadata is no part of the cache's key) reads `None`,
never 0.
"""
from __future__ import annotations

import dataclasses
import re
import statistics
import struct
from typing import Dict, Iterator, List, Optional, Tuple

UNSCOPED = "unscoped"
PASSES = ("forward", "backward", "recompute", "plain")
DEVICE_PLANE = "/device:TPU:0"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_REGION = re.compile(r"\br\.[a-z_]+")
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")


# ------------------------------------------------------------ the wire
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the message in `buf[i:end]`: an int for a
    varint, `(start, end)` for a length-delimited field, the raw bytes of
    a fixed one."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span: Tuple[int, int]):
    key = value = None
    for no, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _stat(buf: bytes, span: Tuple[int, int], stat_names: Dict[int, str]):
    """An XStat -> (its metadata's name, its value): a string (`str_value`
    or a `ref_value` into the stat metadata's names), a number, or None."""
    name = value = None
    for no, v in _fields(buf, *span):
        if no == 1:
            name = stat_names.get(v)
        elif no == 2:
            value = struct.unpack("<d", v)[0]
        elif no in (3, 4):
            value = v
        elif no in (5, 6):
            value = _text(buf, v)
        elif no == 7:
            value = stat_names.get(v, "")
    return name, value


# ------------------------------------------------------------ the trace
@dataclasses.dataclass(frozen=True)
class OpMeta:
    """What the file says of one operation (an `XLA Ops` event's metadata)."""
    name: str                   # the HLO text the event is called by
    tf_op: str = ""             # JAX's op_name path; "" where XLA made it
    source: str = ""
    hlo_category: str = ""
    program_id: Optional[int] = None
    flops: float = 0.0
    bytes_accessed: float = 0.0


@dataclasses.dataclass(frozen=True)
class Op:
    meta: OpMeta
    start_ps: int
    dur_ps: int


@dataclasses.dataclass
class Execution:
    program: str                # 'jit__step'
    program_id: int
    start_ps: int
    dur_ps: int
    ops: List[Tuple[OpMeta, int]]       # (operation, self time in ps)


@dataclasses.dataclass
class DeviceOps:
    ops: List[Op]                       # device 0's `XLA Ops`, by start
    executions: List[Execution]         # its `XLA Modules`, ops filed
    tables: dict = dataclasses.field(default_factory=dict)  # `table`'s


def _line(buf: bytes, span: Tuple[int, int]):
    """An XLine -> (name, timestamp in ps, its events' spans)."""
    name, t0, events = "", 0, []
    for no, v in _fields(buf, *span):
        if no == 2:
            name = _text(buf, v)
        elif no == 3:
            t0 = v * 1000
        elif no == 4:
            events.append(v)
    return name, t0, events


def _events(buf: bytes, t0: int, spans) -> List[Tuple[int, int, int]]:
    """(metadata id, start in ps, duration in ps) of a line's events."""
    out = []
    for span in spans:
        meta = offset = dur = 0
        for no, v in _fields(buf, *span):
            if no == 1:
                meta = v
            elif no == 2:
                offset = v
            elif no == 3:
                dur = v
        out.append((meta, t0 + offset, dur))
    return out


def load(path: str) -> Optional[DeviceOps]:
    """Device 0's operations with their metadata and its program
    executions; None for a file without that plane or those lines."""
    with open(path, "rb") as f:
        buf = f.read()
    for no, plane in _fields(buf, 0, len(buf)):
        if no != 1:
            continue
        parts = list(_fields(buf, *plane))
        if not any(n == 2 and _text(buf, v) == DEVICE_PLANE
                   for n, v in parts):
            continue
        stat_names, metas, lines = {}, {}, {}
        for n, v in parts:
            if n == 5:
                key, value = _map_entry(buf, v)
                stat_names[key] = next(
                    (_text(buf, s) for m, s in _fields(buf, *value)
                     if m == 2), "")
        for n, v in parts:
            if n == 4:
                key, value = _map_entry(buf, v)
                metas[key] = value
            elif n == 3:
                name, t0, events = _line(buf, v)
                if name in (OPS_LINE, MODULES_LINE):
                    lines[name] = _events(buf, t0, events)
        if OPS_LINE not in lines or MODULES_LINE not in lines:
            return None

        def meta_of(span) -> OpMeta:
            name, stats = "", {}
            for n, v in _fields(buf, *span):
                if n == 2:
                    name = _text(buf, v)
                elif n == 5:
                    key, value = _stat(buf, v, stat_names)
                    stats[key] = value
            return OpMeta(
                name, str(stats.get("tf_op") or ""),
                str(stats.get("source") or ""),
                str(stats.get("hlo_category") or ""),
                stats.get("program_id"), float(stats.get("flops") or 0),
                float(stats.get("bytes_accessed") or 0))

        found: Dict[int, OpMeta] = {}
        for mid, _, _ in lines[OPS_LINE] + lines[MODULES_LINE]:
            if mid not in found:
                found[mid] = meta_of(metas[mid]) if mid in metas else OpMeta(
                    "")
        ops = sorted((Op(found[m], s, d) for m, s, d in lines[OPS_LINE]),
                     key=lambda o: (o.start_ps, -o.dur_ps))
        return DeviceOps(ops, _file(ops, [
            (found[m].name, s, d) for m, s, d in sorted(
                lines[MODULES_LINE], key=lambda e: e[1])]))
    return None


def _file(ops: List[Op], modules) -> List[Execution]:
    """Each operation under the program execution that contains it, with
    its self time: its duration less that of the operations nested in it."""
    out = []
    i = 0
    for name, start, dur in modules:
        m = _PROGRAM.match(name)
        program, pid = (m.group(1), int(m.group(2))) if m else (name, -1)
        ex = Execution(program, pid, start, dur, [])
        end = start + dur
        while i < len(ops) and ops[i].start_ps < start:
            i += 1
        own: List[List] = []
        stack: List[int] = []
        while i < len(ops) and ops[i].start_ps + ops[i].dur_ps <= end:
            op = ops[i]
            i += 1
            while stack and (own[stack[-1]][0].start_ps
                             + own[stack[-1]][0].dur_ps) <= op.start_ps:
                stack.pop()
            if stack:
                own[stack[-1]][1] -= op.dur_ps
            own.append([op, op.dur_ps])
            stack.append(len(own) - 1)
        ex.ops = [(op.meta, max(t, 0)) for op, t in own
                  if op.meta.program_id in (None, pid)]
        out.append(ex)
    return out


# ------------------------------------------------------------ the reading
def region_of(tf_op: str) -> str:
    found = _REGION.findall(tf_op)
    return found[-1] if found else UNSCOPED


def pass_of(tf_op: str) -> str:
    if "rematted_computation" in tf_op:
        return "recompute"
    if "transpose(" in tf_op:
        return "backward"
    if "jvp(" in tf_op:
        return "forward"
    return "plain"


def executions(dev: Optional[DeviceOps], program: str) -> List[Execution]:
    """The traced executions of `program` that hold operations."""
    if dev is None:
        return []
    return [ex for ex in dev.executions if ex.program == program and ex.ops]


def by_region_and_pass(ex: Execution) -> Dict[Tuple[str, str], float]:
    """One execution's operation time in ms by (region, pass)."""
    out: Dict[Tuple[str, str], float] = {}
    for meta, own in ex.ops:
        key = (region_of(meta.tf_op), pass_of(meta.tf_op))
        out[key] = out.get(key, 0.0) + own * 1e-9
    return out


def table(dev: Optional[DeviceOps], program: str
          ) -> Optional[Dict[Tuple[str, str], float]]:
    """(region, pass) -> ms an execution of `program`, the median over the
    traced executions (a pair an execution lacks counts as 0 there). None
    where the trace holds no execution of it, or none of its operations
    carries a region."""
    if dev is None:
        return None
    if program not in dev.tables:
        runs = [by_region_and_pass(ex) for ex in executions(dev, program)]
        keys = {k for r in runs for k in r}
        dev.tables[program] = {
            k: statistics.median(r.get(k, 0.0) for r in runs)
            for k in keys} if any(
                region != UNSCOPED for region, _ in keys) else None
    return dev.tables[program]


def of_run(run: dict) -> Optional[DeviceOps]:
    """The reading of a benchmark run (`run.py`'s `run`), made once; None
    for an untraced run."""
    if "_op_scopes" not in run:
        from benchmarks.harness import xplane
        traced = run["result"].get("traced")
        run["_op_scopes"] = None
        if run.get("trace") is not None and traced and traced.get("dir"):
            run["_op_scopes"] = load(xplane.find_xplane(traced["dir"]))
    return run["_op_scopes"]


def region_ms(run: dict, program: str, regions) -> Optional[float]:
    """Ms an execution of `program` spends in `regions` (names of
    `ray_tpu/models/regions.py`), all passes; None where `table` is."""
    t = table(of_run(run), program)
    if t is None:
        return None
    return sum(ms for (region, _), ms in t.items() if region in regions)


def pass_ms(run: dict, program: str, which: str) -> Optional[float]:
    """Ms an execution of `program` spends in pass `which`, all regions."""
    t = table(of_run(run), program)
    if t is None:
        return None
    return sum(ms for (_, p), ms in t.items() if p == which)


def unscoped_share(run: dict, program: str) -> Optional[float]:
    """Percent of `program`'s operation time under no region."""
    t = table(of_run(run), program)
    if t is None or not sum(t.values()):
        return None
    return 100.0 * sum(ms for (region, _), ms in t.items()
                       if region == UNSCOPED) / sum(t.values())
