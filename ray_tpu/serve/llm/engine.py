"""Continuous-batching LLM engine: one instance per replica group.

`EngineCore` is the pure scheduler + model driver: a step loop where
every iteration first ADMITS waiting requests (prefill into free KV
pages) and then DECODES every in-flight sequence by one token — so a
short request admitted mid-flight finishes while a long one is still
generating, and a long generation never convoys short ones behind it
(vLLM's iteration-level scheduling, PAPERS.md serving economics).

The decode loop is a pipeline one step deep. A call of `step()`
dispatches this iteration's prefills and decode step from what the
host can count (lengths, positions, page lists, `max_tokens`), and
only then reads the tokens of the decode step dispatched by the call
before: its events are one step behind its dispatch, and the host's
work on them runs beside the device's next step. The next input
tokens never visit the host: greedy sampling is a program on the
device, whose token vector feeds the next step as it is (a sequence
keeps one lane from admission to its end) and is fetched for emission
a step late. A sequence that ends by `max_tokens` gives up its lane
and pages when its last step is dispatched (the device runs programs
in the order given, so pages reused by a later prefill are written
after the step that last touched them); one that ends by a stop token
is known a step late and runs one step more, whose token is dropped.
Eviction and `drain()` read what is in flight first; `cancel()` does
not wait. The core has no threads, which is what the tier-1 tests
drive: a last call with nothing to dispatch reads what is in flight.

`LLMEngine` wraps the core as a Serve deployment class: a background
step thread, and a `TokenStreamServer` pushing tokens to peer-dialed
subscribers the moment the step that produced them completes — the one
way tokens leave a replica. The per-request token buffers are that
stream's backlog (a subscriber that connects late or again is replayed
from its cursor) and where TTFT / TPOT are taken for the metrics plane.

Failure semantics: every emitted token carries (incarnation, attempt,
seq). A replica that restarts gets a fresh incarnation; a request
re-prefilled elsewhere gets a fresh attempt — the client fences
anything stale, so a zombie replica that keeps decoding into a
partition can never duplicate or interleave tokens at the consumer.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import threading
import time
import traceback
import uuid
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ray_tpu._private import tracing_plane as _tp
from ray_tpu.serve.llm import spans as _sp
from ray_tpu.serve.llm.kv_cache import PageAllocator, pages_needed
from ray_tpu.serve.llm.setup_record import SetupRecord
from ray_tpu.serve.llm.stream import TokenStreamServer

FINISH_STOP = "stop"
FINISH_LENGTH = "length"
FINISH_DRAINED = "drained"
FINISH_ERROR = "error"

# a step this long is kept in EngineCore.slow_steps (PERF.md Findings 4)
SLOW_STEP_S = 1.0

_clock = time.monotonic     # a step's and its phases' seconds


class _Phase:
    """A child span of one step whose seconds also add up in the step's
    own record (`EngineCore.slow_steps` keeps them for a slow step)."""

    __slots__ = ("_acc", "_key", "_span", "_t0")

    def __init__(self, acc: dict, name: str, **attrs):
        self._acc = acc
        self._key = name
        self._span = _sp.span(name, **attrs)

    def __enter__(self) -> None:
        self._t0 = _clock()
        self._span.__enter__()

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        self._add(_clock() - self._t0)

    def _add(self, seconds: float) -> None:
        self._acc[self._key] = self._acc.get(self._key, 0.0) + seconds


class _SetupPhase(_Phase):
    """A phase of an engine's construction: its seconds stay in the
    engine's record and go to the metrics plane (`ray_tpu_llm_setup_s`),
    both under the span's name. `__enter__` gives the span, for what is
    known only at the phase's end (`span.add`: the recorder's)."""

    __slots__ = ("_series",)

    def __init__(self, record: SetupRecord, name: str):
        super().__init__(record.phases, name)
        self._series = record.series

    def __enter__(self) -> _sp.span:
        super().__enter__()
        return self._span

    def _add(self, seconds: float) -> None:
        super()._add(seconds)
        if self._series:
            self._series["setup"].inc(seconds, {"phase": self._key})


def _name_os_thread(name: str) -> None:
    """Give the calling thread its name at the OS too (Python 3.12 names a
    thread only for itself). A profiler's trace files host events by the
    OS name, and every Python thread is `python3` there: two of them
    collapse into one line for a reader that keys lines by name."""
    try:
        import ctypes
        prctl = ctypes.CDLL(None).prctl
        prctl.argtypes = [ctypes.c_int, ctypes.c_char_p,
                          *[ctypes.c_ulong] * 3]
        prctl.restype = ctypes.c_int
        prctl(15, name.encode()[:15], 0, 0, 0)      # PR_SET_NAME
    except (OSError, AttributeError):   # not Linux: it stays `python3`
        pass


def _tree_bytes(tree) -> int:
    """Bytes of a tree of arrays, whole (over every shard)."""
    import jax
    return sum(a.nbytes for a in jax.tree.leaves(tree))


def _pool_shapes(cache) -> str:
    """A cache's pools by name and shape, for a span: `k:6x2048x16x1024
    v:...`; what a model counts beside them (scalars, a row a layer) is
    left out."""
    return " ".join(f"{name}:{'x'.join(map(str, a.shape))}"
                    for name, a in cache.items()
                    if getattr(a, "ndim", 0) >= 3)


def _bucket(n: int, lo: int = 16, hi: int = 1 << 30) -> int:
    """Prefill pad bucket: next power of two — bounds distinct compiled
    prefill shapes at log2(max_seq_len)."""
    b = lo
    while b < n:
        b <<= 1
    return min(b, hi)


# What `model.fixed_step_counts` names on the `engine.decode_dispatch`
# span, and the counter that sums each over the dispatches.
_FIXED_COUNTERS = {
    "window_positions_live": "kv_window_positions_live",
    "window_positions_read": "kv_window_positions_read",
    "window_walk_blocks": "kv_window_walk_blocks",
    "window_positions_attended": "kv_window_positions_attended",
    "state_slots": "state_slots_live",
    "state_bytes": "state_bytes_moved"}


@dataclasses.dataclass
class _Seq:
    rid: str
    prompt: List[int]
    max_tokens: int
    stop: frozenset
    attempt: int = 0
    submit_t: float = 0.0
    admit_t: Optional[float] = None
    emitted: List[int] = dataclasses.field(default_factory=list)
    pages: List[int] = dataclasses.field(default_factory=list)
    evictions: int = 0
    trace_id: int = 0           # the llm.* spans of this request
    first_token_t: Optional[float] = None
    lane: int = -1              # the decode lane held, or -1
    # tokens asked of the device: those emitted and those not read yet
    asked: int = 0

    @property
    def device_len(self) -> int:
        """Positions the cache holds once everything asked for ran."""
        return len(self.prompt) + self.asked

    @property
    def remaining(self) -> int:
        return max(0, self.max_tokens - len(self.emitted))


@dataclasses.dataclass
class _Flight:
    """A decode step dispatched and not read yet."""
    tokens: Any                         # (max_batch,) int32, on the device
    counts: Dict[str, Any]              # the model's, of this step alone
    lanes: List[Tuple[int, _Seq]]       # whose token each lane's is


class EngineCore:
    """Deterministic (greedy) continuous-batching scheduler.

    step() events are dicts: {rid, token, seq, done, reason, first,
    attempt}, those of the decode step dispatched one call earlier and
    of this call's prefills. `seq` indexes into this attempt's emitted
    tokens; a client that re-prefilled elsewhere offsets by its resume
    base.
    """

    def __init__(self, config, params, mesh=None,
                 num_pages: int = 0, page_size: int = 16,
                 max_batch: int = 8,
                 record: Optional[SetupRecord] = None):
        import jax
        from ray_tpu.models import build_model
        # what this engine's start cost, phase by phase and program by
        # program (`stats()`'s `setup` and `programs`); `LLMEngine` hands
        # in the one it began before there was a core
        self.record = record or SetupRecord(_serving_metrics())
        self.config = config
        self.page_size = int(page_size)
        self.max_batch = int(max_batch)
        # the config's type names the model class; the engine asks the
        # model for its cache and programs and names no class itself
        with _SetupPhase(self.record, _sp.SETUP_MODEL):
            self.model = build_model(config, mesh)
        self.params = params
        # a table's entries: a full-length sequence's pages, or where the
        # class keeps a fixed page and asks for runs, its fixed entries
        # and then whole runs (the model's word: the step's walks read it)
        self.max_pages_per_seq = self.model.table_pages(
            self.page_size, pages_needed(config.max_seq_len, self.page_size))
        if not num_pages:
            # default pool: every decode lane can hold a full-length
            # sequence (the mesh-budget path goes through
            # kv_cache.pages_from_budget at engine construction)
            num_pages = self.max_batch * self.max_pages_per_seq
        self.num_pages = int(num_pages)
        # what a model keeps of a sequence for ever (a window layer's
        # ring of pages, a recurrent layer's state) is named by the first
        # `_fixed` pages a sequence holds: the allocator's fixed class
        # (`kv_cache.py`), `_fixed` a decode lane; 0: one class, one pool
        self._fixed = self.model.fixed_pages(self.page_size)
        # and the model says how many pages one copy of its decode walk
        # brings: the allocator hands the growing class out in such runs
        self.alloc = PageAllocator(
            self.num_pages, fixed=self._fixed, sequences=self.max_batch,
            run=self.model.page_run(self.page_size, self.max_pages_per_seq))
        with _SetupPhase(self.record, _sp.SETUP_CACHE) as span:
            self._cache = self.model.init_cache(
                self.num_pages, self.page_size,
                **({"fixed_pages": self.alloc.fixed_pages} if self._fixed
                   else {}))
            # a model whose pool has a row an attention says how many
            # (`pool_rows`: two a layer where a layer has two attentions)
            rows = self.model.pool_rows
            span.add(bytes=_tree_bytes(self._cache),
                     num_pages=self.num_pages,
                     fixed_pages=self.alloc.fixed_pages,
                     pools=_pool_shapes(self._cache),
                     **({} if rows is None else {"pool_rows": rows}))
        self._waiting: deque = deque()
        # the sequences that hold a lane, oldest admission first
        self._running: List[_Seq] = []
        self._lanes: List[Optional[_Seq]] = [None] * self.max_batch
        # every sequence that waits or runs (one runs until its last
        # token is read, which is after it gave up its lane)
        self._by_rid: Dict[str, _Seq] = {}
        self._flight: Optional[_Flight] = None
        # this call's prefills: (sequence, its first token on the device)
        self._firsts: List[Tuple[_Seq, Any]] = []
        self._queue_waits: deque = deque(maxlen=1024)  # (t, wait_s)
        self._prefill_fns: Dict[int, Any] = {}
        self._np = __import__("numpy")
        with _SetupPhase(self.record, _sp.SETUP_PROGRAMS):
            self._init_programs(mesh)
        self.counters = {
            "admitted": 0, "evictions": 0, "finished": 0, "tokens": 0,
            "steps": 0,
            # prompt tokens prefilled, and the same after padding to
            # their bucket (what the prefill programs computed)
            "prefill_tokens": 0, "prefill_padded_tokens": 0,
            # prefill programs built: `record.count("_pre")`
            "prefill_programs": 0,
            # decode dispatches, those whose attention was the paged
            # kernel, and the lanes that held a sequence
            "decode_steps": 0, "decode_kernel_steps": 0,
            "decode_lane_steps": 0,
            # cache positions those lanes held / the dispatches read, a
            # layer whose cache is whole; and what the lanes' fixed parts
            # cost the same dispatches (`_FIXED_COUNTERS`, summed from the
            # model's `fixed_step_counts`): a window layer holds and reads
            # a sequence's last positions only, a recurrent layer moves a
            # state of one size
            "kv_positions_live": 0, "kv_positions_read": 0,
            # the shape of the kernel's walk over such a layer: the blocks
            # the lanes' pages came in, and the positions its matmuls
            # multiplied (the part of each block a lane holds, in pieces)
            "kv_walk_blocks": 0, "kv_positions_attended": 0,
            # the copies that walk started, a layer and pool: one a run of
            # the allocator's that holds a live page (a page, at runs of 1)
            "kv_walk_copies": 0,
            # and the lanes whose first block the lane before them started
            # behind its own last: of `decode_lane_steps`, all but one a
            # dispatch wait for no copy that nothing hides
            "kv_walk_first_blocks_hidden": 0,
            **dict.fromkeys(_FIXED_COUNTERS.values(), 0),
            # and what the model counts on the device in a decode step,
            # under the model's own names (`step_stats`: the counts come
            # back with the step's tokens and are summed over the steps)
            **dict.fromkeys(self.model.step_stats(self._cache), 0),
            # decode steps dispatched before the one before was read;
            # times what was in flight was read with nothing dispatched
            # behind it (eviction, drain, no lane left to decode); and
            # lane-steps whose token nobody got (a step past a stop
            # token, a request cancelled with its step in flight)
            "decode_steps_ahead": 0, "pipeline_flushes": 0,
            "discarded_lane_steps": 0}
        # the steps that took SLOW_STEP_S or more: wall seconds, when,
        # the decode batch, the seconds in each phase and the programs
        # built in it
        self.slow_steps: deque = deque(maxlen=16)
        self._step_span = 0     # span id of the step running, or 0

    def _init_programs(self, mesh) -> None:
        """The jitted wrappers (built by the calls that first dispatch
        them) and what the host needs to count a dispatch."""
        import jax
        from ray_tpu.models.regions import SAMPLE, region
        # both programs update the pool in place: the cache argument is
        # donated (whoever holds the old one holds a deleted buffer), and
        # on a mesh every step hands the cache back as it lay, whatever
        # the partitioner would have preferred for one call
        self._jit = jax.jit if mesh is None else functools.partial(
            jax.jit, out_shardings=(None, jax.tree.map(
                lambda a: a.sharding, self._cache)))

        def _step(params, cache, tokens, positions, pts, active):
            return self.model.decode_step(params, cache, tokens,
                                          positions, pts, active,
                                          self.page_size)
        self._decode_fn = self._jit(_step, donate_argnums=(1,))
        # greedy sampling where the logits are: the token vector is the
        # next step's argument as it stands, replicated on a mesh
        replicated = None if mesh is None else jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        small = jax.jit if mesh is None else functools.partial(
            jax.jit, out_shardings=replicated)

        def _next(logits, counts):
            # the step's counts are copied out of the cache before the
            # next step is given it
            with region(SAMPLE):
                return (logits.argmax(axis=-1).astype("int32"),
                        jax.tree.map(lambda n: n.copy(), counts))

        def _place(tokens, lane, logits):
            with region(SAMPLE):
                first = logits.argmax().astype("int32")
                return tokens.at[lane].set(first), first
        self._next_fn = small(_next)
        self._place_fn = small(_place)      # a prefill's token to its lane
        self._tokens = jax.device_put(
            self._np.zeros((self.max_batch,), "int32"), replicated)
        # which attention the decode step holds (a paged kernel's name
        # or "einsum"), decided where the step is traced: here
        self._attention = self.model.decode_attention(self.page_size)
        self._devices = (list(mesh.devices.flat) if mesh is not None
                         else jax.devices()[:1])
        chips = os.environ.get("TPU_VISIBLE_CHIPS", "")
        self._device_info = {
            "platform": self._devices[0].platform,
            "device_kind": self._devices[0].device_kind,
            "device_ids": [d.id for d in self._devices],
            "chips": [int(c) for c in chips.split(",") if c]}
        # what one decode step's einsum gathers per layer, whatever its
        # lanes hold; the kernel reads the pages they hold
        self._table_positions = (self.max_batch * self.max_pages_per_seq
                                 * self.page_size)
        # the kernel's walk over a lane by the pages it holds: (blocks,
        # positions its matmuls multiply), by the kernel's own rule
        # (a table's entries lie `pad` places into the walk where runs
        # open behind a fixed class's entries)
        from ray_tpu.ops.paged_attention import (run_pad, walk_counts,
                                                 walk_first_blocks_hidden)
        self._walk_hidden = walk_first_blocks_hidden
        # the table entries a walk copies a page each before its runs
        self._walk_head = self._fixed if self.alloc.run > 1 else 0
        pad = run_pad(self._walk_head, self.alloc.run)
        self._walk_block = self.model.walk_block_pages(
            self.page_size, self.max_pages_per_seq + pad)
        self._walk = [walk_counts(n, self._walk_block, self.page_size, pad)
                      for n in range(self.max_pages_per_seq + 1)]

    # ------------------------------------------------------ intake
    def submit(self, prompt: Sequence[int], max_tokens: int = 16,
               stop: Sequence[int] = (), rid: Optional[str] = None,
               attempt: int = 0,
               submit_t: Optional[float] = None) -> str:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        max_tokens = int(max_tokens)
        if max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {max_tokens}")
        total = len(prompt) + max_tokens
        if total > self.config.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens ({max_tokens}) "
                f"exceeds max_seq_len {self.config.max_seq_len}")
        if not self.alloc.fits(pages_needed(total, self.page_size)):
            raise ValueError(
                f"request needs {pages_needed(total, self.page_size)} "
                f"pages; pool holds {self.num_pages}")
        rid = rid or uuid.uuid4().hex[:12]
        if rid in self._by_rid:
            raise ValueError(f"duplicate request id {rid!r}")
        seq = _Seq(rid=rid, prompt=prompt, max_tokens=max_tokens,
                   stop=frozenset(int(t) for t in stop),
                   attempt=int(attempt),
                   submit_t=(time.monotonic() if submit_t is None
                             else submit_t),
                   trace_id=_tp.new_id() if _tp.enabled() else 0)
        self._waiting.append(seq)
        self._by_rid[rid] = seq
        return rid

    def cancel(self, rid: str) -> bool:
        """Forget a request now. A step in flight is not waited for: the
        token its lane yields is dropped when the step is read."""
        seq = self._by_rid.pop(rid, None)
        if seq is None:
            return False
        if seq.lane >= 0:
            self._release(seq)
        elif seq in self._waiting:
            self._waiting.remove(seq)
        flight = self._flight
        if flight is not None:
            if any(s is seq for _, s in flight.lanes):
                self.counters["discarded_lane_steps"] += 1
            if not any(self._owns(s) for _, s in flight.lanes):
                self._flight = None     # nobody's: abandoned, not read
        return True

    def _owns(self, seq: _Seq) -> bool:
        """Whether `seq` still waits or runs under its request id."""
        return self._by_rid.get(seq.rid) is seq

    def _release(self, seq: _Seq) -> None:
        """Give back the lane and the pages `seq` holds. Whatever was
        dispatched before this reads and writes them first."""
        self._running.remove(seq)
        self._lanes[seq.lane] = None
        seq.lane = -1
        self.alloc.free(seq.pages)
        seq.pages = []

    def drain(self) -> Tuple[List[dict], List[dict]]:
        """Stop everything in flight: returns the events of what the
        device had been asked for, then re-dispatchable descriptors of
        what is left (SUSPECT drain: the router re-prefills these on a
        surviving replica; `emitted` rides along so the survivor
        continues rather than restarts)."""
        self._step_span = 0         # no step: its request spans are roots
        events = self._flush({})
        out = []
        for seq in list(self._running) + list(self._waiting):
            out.append({"rid": seq.rid, "prompt": list(seq.prompt),
                        "emitted": list(seq.emitted),
                        "max_tokens": seq.max_tokens,
                        "stop": sorted(seq.stop),
                        "attempt": seq.attempt})
            self.cancel(seq.rid)
        return events, out

    # ------------------------------------------------------- stepping
    @property
    def has_work(self) -> bool:
        """True while a sequence waits or runs (one runs until its last
        token is read)."""
        return bool(self._by_rid)

    def _prefill_fn(self, s_pad: int):
        fn = self._prefill_fns.get(s_pad)
        if fn is None:
            def _pre(params, tokens, true_len, page_table, cache):
                return self.model.prefill(params, tokens, true_len,
                                          page_table, cache,
                                          self.page_size)
            fn = self._jit(_pre, donate_argnums=(4,))
            self._prefill_fns[s_pad] = fn
        # every bucket's function is called `_pre`: a build that JAX
        # reports from the call about to be made is this bucket's
        self.record.bucket = s_pad
        return fn

    def _emit(self, events: List[dict], seq: _Seq, token: int) -> None:
        first = not seq.emitted
        seq.emitted.append(token)
        self.counters["tokens"] += 1
        done, reason = False, None
        if token in seq.stop:
            done, reason = True, FINISH_STOP
        elif len(seq.emitted) >= seq.max_tokens:
            done, reason = True, FINISH_LENGTH
        events.append({"rid": seq.rid, "token": token,
                       "seq": len(seq.emitted) - 1, "first": first,
                       "done": done, "reason": reason,
                       "attempt": seq.attempt})
        if first or done:
            now = time.monotonic()
            if first:
                self._request_span(seq, _sp.REQ_PREFILL, seq.admit_t, now)
                seq.first_token_t = now
            if done:
                self._request_span(seq, _sp.REQ_DECODE,
                                   seq.first_token_t, now)
        if done:
            self.counters["finished"] += 1
            self.cancel(seq.rid)

    def _request_span(self, seq: _Seq, name: str, t0: float,
                      t1: float) -> None:
        """One stage of a request's life, to the flight recorder: under
        the request's trace id, child of the step that ended it."""
        if seq.trace_id and _tp.enabled():
            _tp.record("llm", name, int(t0 * 1e9), int(t1 * 1e9),
                       seq.trace_id, _tp.new_id(), self._step_span,
                       {"rid": seq.rid})

    def _evict_one(self, keep: _Seq) -> bool:
        """Preempt the youngest running sequence other than `keep`,
        returning its pages to the pool; the victim re-queues at the
        FRONT of the waiting line with its emitted tokens intact (it
        re-prefills prompt+emitted and continues — work is delayed,
        never lost). Nothing may be in flight: `emitted` has to be
        whole."""
        for victim in reversed(self._running):
            if victim is keep:
                continue
            self._release(victim)
            victim.evictions += 1
            self._waiting.appendleft(victim)
            self.counters["evictions"] += 1
            return True
        return False

    def _note_asked(self, seq: _Seq) -> None:
        """One more token of `seq` was asked of the device. If it is the
        last, by `max_tokens`, the lane and the pages come free now."""
        seq.asked += 1
        if seq.asked >= seq.max_tokens:
            self._release(seq)

    def step(self) -> List[dict]:
        """One engine iteration: admit, dispatch the next decode step of
        everyone, then read the tokens of the step before."""
        self.counters["steps"] += 1
        t0, t_mono_ns = _clock(), time.monotonic_ns()
        phases: Dict[str, float] = {}
        # a program JAX builds on this thread inside the step is this
        # engine's (`setup_record.py`); a dispatch tests `record.open`
        self.record.watch(self.counters["steps"])
        with _sp.span(_sp.STEP, step=self.counters["steps"],
                      t_mono_ns=t_mono_ns) as ctx:
            self._step_span = ctx[1] if ctx else 0
            events, lanes = self._step(phases)
        self.record.unwatch()
        wall = _clock() - t0
        if wall >= SLOW_STEP_S:
            self.slow_steps.append({
                "step": self.counters["steps"], "wall_s": wall,
                "t_mono_ns": t_mono_ns, "lanes": lanes,
                "phases": phases,
                # a first bucket, or a program built again: why it was slow
                "builds": self.record.of_step(self.counters["steps"])})
        return events

    def _step(self, phases: Dict[str, float]):
        """The step's work; returns (events, lanes dispatched)."""
        import jax.numpy as jnp
        np = self._np
        c = self.counters
        events: List[dict] = []

        # ---- per-iteration admission: prefill into free pages
        while self._waiting and len(self._running) < self.max_batch:
            seq = self._waiting[0]
            toks = seq.prompt + seq.emitted
            pages = self.alloc.alloc(pages_needed(len(toks), self.page_size))
            if pages is None:
                break                      # pool dry: decode continues
            s_pad = _bucket(len(toks), hi=self.config.max_seq_len)
            with _Phase(phases, _sp.PREFILL, rid=seq.rid, tokens=len(toks),
                        bucket=s_pad,
                        new_program=int(s_pad not in self._prefill_fns),
                        **self.model.prefill_counts(len(toks), s_pad)):
                self._waiting.popleft()
                seq.pages = pages
                now = time.monotonic()
                if seq.admit_t is None:    # first admission only
                    seq.admit_t = now
                    self._queue_waits.append((now, now - seq.submit_t))
                    self._request_span(seq, _sp.REQ_QUEUE, seq.submit_t,
                                       now)
                padded = np.zeros((s_pad,), np.int32)
                padded[:len(toks)] = toks
                pt = np.full((self.max_pages_per_seq,), -1, np.int32)
                pt[:len(pages)] = pages
                logits, self._cache = self._prefill_fn(s_pad)(
                    self.params, jnp.asarray(padded),
                    jnp.int32(len(toks)), jnp.asarray(pt), self._cache)
                seq.lane = self._lanes.index(None)
                self._lanes[seq.lane] = seq
                self._running.append(seq)
                self._tokens, first = self._place_fn(
                    self._tokens, np.int32(seq.lane), logits)
                if self.record.open:    # JAX built a program in here
                    self.record.close()
                    c["prefill_programs"] = self.record.count("_pre")
                first.copy_to_host_async()
                self._firsts.append((seq, first))
                c["admitted"] += 1
                c["prefill_tokens"] += len(toks)
                c["prefill_padded_tokens"] += s_pad
                seq.asked = len(seq.emitted)
                self._note_asked(seq)

        # ---- decode every in-flight sequence by one token
        for seq in list(self._running):
            # page for the incoming token's KV write, evicting the
            # youngest other sequence if the pool is dry
            while seq.lane >= 0 and pages_needed(
                    seq.device_len, self.page_size) > len(seq.pages):
                got = self.alloc.alloc(1, held=len(seq.pages))
                if got is not None:
                    seq.pages.extend(got)
                elif self._flight is not None or self._firsts:
                    # a victim re-queues what it has emitted, so that has
                    # to be whole first; a stop token read here may end a
                    # sequence (this one too) and free the page wanted
                    events += self._flush(phases)
                elif not self._evict_one(seq):
                    # alone and out of pages: feasibility was checked
                    # at submit, so this cannot happen; guard anyway
                    self.cancel(seq.rid)
                    events.append({"rid": seq.rid, "token": None,
                                   "seq": len(seq.emitted), "first": False,
                                   "done": True, "reason": "oom",
                                   "attempt": seq.attempt})
        batch = list(self._running)
        if not batch:
            # nothing to dispatch behind what is in flight
            return events + self._flush(phases), 0
        with _Phase(phases, _sp.TABLES):
            B = self.max_batch
            positions = np.zeros((B,), np.int32)
            pts = np.full((B, self.max_pages_per_seq), -1, np.int32)
            active = np.zeros((B,), bool)
            kernel = self._attention != "einsum"
            run, head = self.alloc.run, self._walk_head
            live = copies = read = blocks = attended = 0
            walked: List[int] = []
            fixed: Dict[str, int] = {}
            for seq in batch:
                i = seq.lane
                positions[i] = seq.device_len - 1
                pts[i, :len(seq.pages)] = seq.pages
                active[i] = True
                live += seq.device_len
                pages = pages_needed(seq.device_len, self.page_size)
                # the walk's copies: a page each of the table's fixed
                # entries, then a run each, whole
                runs = -(-max(pages - head, 0) // run)
                copies += min(pages, head) + runs
                read += (min(pages, head) + runs * run) * self.page_size
                if kernel:
                    blocks += self._walk[pages][0]
                    attended += self._walk[pages][1]
                    walked.append(pages)
                if self._fixed:     # what the lane's fixed part costs
                    for name, n in self.model.fixed_step_counts(
                            seq.device_len, self.page_size, kernel).items():
                        fixed[name] = fixed.get(name, 0) + n
            args = (jnp.asarray(positions), jnp.asarray(pts),
                    jnp.asarray(active))
        # the kernel copies in each lane's live pages, whole, a run of the
        # allocator's a copy; the gather reads every table entry and
        # multiplies all it reads, in one
        if not kernel:
            read = attended = self._table_positions
            copies = 0
        hidden = self._walk_hidden(walked)
        c["decode_steps"] += 1
        c["decode_kernel_steps"] += int(kernel)
        c["decode_lane_steps"] += len(batch)
        c["kv_positions_live"] += live
        c["kv_positions_read"] += read
        c["kv_walk_blocks"] += blocks
        c["kv_walk_copies"] += copies
        c["kv_positions_attended"] += attended
        c["kv_walk_first_blocks_hidden"] += hidden
        for name, n in fixed.items():
            c[_FIXED_COUNTERS[name]] += n
        # an annotation's attributes are fixed when it opens, so the
        # step's counts ride the first span that opens once they are known
        with _Phase(phases, _sp.DISPATCH, lanes=len(batch),
                    live_positions=live, read_positions=read,
                    walk_blocks=blocks, walk_copies=copies,
                    attended_positions=attended,
                    walk_first_blocks_hidden=hidden, **fixed):
            logits, self._cache = self._decode_fn(
                self.params, self._cache, self._tokens, *args)
            self._tokens, counts = self._next_fn(
                logits, self.model.step_stats(self._cache))
            if self.record.open:        # JAX built a program in here
                self.record.close()
            # the copies to the host start as soon as the step has run
            for a in (self._tokens, *counts.values()):
                a.copy_to_host_async()
        before, self._flight = self._flight, _Flight(
            self._tokens, counts, [(s.lane, s) for s in batch])
        c["decode_steps_ahead"] += int(before is not None)
        for seq in batch:
            self._note_asked(seq)
        return events + self._read(phases, before), len(batch)

    def _flush(self, phases: Dict[str, float]) -> List[dict]:
        """Read what is in flight with nothing dispatched behind it."""
        flight, self._flight = self._flight, None
        self.counters["pipeline_flushes"] += int(flight is not None)
        return self._read(phases, flight)

    def _read(self, phases: Dict[str, float],
              flight: Optional[_Flight]) -> List[dict]:
        """Fetch and emit the tokens of `flight`, a decode step, and the
        first tokens of the prefills dispatched since the last read."""
        firsts, self._firsts = self._firsts, []
        if flight is None and not firsts:
            return []
        np = self._np
        with _Phase(phases, _sp.FETCH):     # the copies were started
            tokens = np.asarray(flight.tokens) if flight else ()
            first_tokens = [int(np.asarray(t)) for _, t in firsts]
            counts = {name: int(np.asarray(n)) for name, n in (
                flight.counts if flight else {}).items()}
        for name, n in counts.items():
            self.counters[name] += n
        events: List[dict] = []
        # the model's counts of the step whose tokens these are: known
        # only now, they ride the span that emits them
        with _Phase(phases, _sp.EMIT, **counts):
            for lane, seq in (flight.lanes if flight else ()):
                if self._owns(seq):
                    self._emit(events, seq, int(tokens[lane]))
            for (seq, _), token in zip(firsts, first_tokens):
                self._emit(events, seq, token)
        return events

    # ------------------------------------------------------- signals
    def queue_wait_p95(self, window_s: float = 30.0) -> float:
        now = time.monotonic()
        waits = [w for t, w in self._queue_waits if now - t <= window_s]
        if not waits:
            return 0.0
        waits.sort()
        return waits[min(len(waits) - 1,
                         int(0.95 * (len(waits) - 1) + 0.999))]

    def outstanding_tokens(self) -> int:
        return sum(s.remaining for s in self._by_rid.values())

    def device_stats(self, in_cache: Optional[dict] = None) -> dict:
        """Where this engine runs, as JAX reports it, plus the chips the
        scheduler granted the process (device ids are per process: four
        one-chip replicas all hold device 0, each of another chip),
        which attention its compiled decode step holds (a paged kernel's
        name or "einsum"), what a cache position costs, and what the
        model keeps in the cache beside the pages (pairs an expert):
        `in_cache`, for a caller that read `cache_stats()` where no step
        could run, else read here."""
        return {**self._device_info,
                "decode_attention": self._attention,
                "cache_bytes_per_position":
                    self.model.cache_page_bytes(self.page_size)
                    // self.page_size,
                **(self.cache_stats() if in_cache is None else in_cache),
                "bytes_in_use": [
                    (d.memory_stats() or {}).get("bytes_in_use")
                    for d in self._devices]}

    def cache_stats(self) -> dict:
        """What the model keeps in the cache beside the pages, once the
        step in flight has run. Not while a step is being dispatched:
        the cache is donated to it."""
        fixed = ({"fixed_pages": self.alloc.fixed_pages,
                  "fixed_pages_used": self.alloc.fixed_used}
                 if self._fixed else {})
        # pages in runs, and those of the pool that make no whole run
        runs = ({"page_run": self.alloc.run,
                 "unused_pages": self.alloc.unused_pages}
                if self.alloc.run > 1 else {})
        return {**self.model.cache_stats(self._cache), **fixed, **runs}

    def stats(self) -> dict:
        return {"waiting": len(self._waiting),
                "running": len(self._running),
                "free_pages": self.alloc.free_pages,
                "num_pages": self.num_pages,
                "outstanding_tokens": self.outstanding_tokens(),
                "queue_wait_p95": self.queue_wait_p95(),
                "slow_steps": list(self.slow_steps),
                # what the engine's start cost: seconds by phase (the
                # spans' names) and a row for every program built
                "setup": dict(self.record.phases),
                "programs": [dict(row) for row in self.record.programs],
                **self.counters}


class LLMEngine:
    """Serve deployment class: one continuous-batching engine per
    replica group.

    init is serve-replica friendly: `model` is a preset name, a dict of
    config fields or a config object (`models.model_config`; the
    config's type names the model class); `weights` is an ObjectRef (cold
    replicas pull it through the object plane, which the r12 broadcast
    relay pre-seeds on every node) or None to init from `seed`;
    `mesh` is an axes dict (e.g. {"dp": 1, "tp": 2}) building this
    replica's own device mesh — each replica group shards the model
    across its local devices.
    """

    def __init__(self, model="tiny", weights=None, mesh=None,
                 num_pages: int = 0, page_size: int = 0,
                 max_batch: int = 0, kv_budget_bytes: int = 0,
                 seed: int = 0):
        # the start's cost stays readable when its spans have left the
        # ring: `engine_stats()`'s `setup` and `programs`
        record = SetupRecord(_serving_metrics())
        with _SetupPhase(record, _sp.SETUP):
            self._setup(record, model, weights, mesh, num_pages, page_size,
                        max_batch, kv_budget_bytes, seed)

    def _setup(self, record: SetupRecord, model, weights, mesh,
               num_pages: int, page_size: int, max_batch: int,
               kv_budget_bytes: int, seed: int) -> None:
        import jax
        from ray_tpu._private.config import CONFIG
        from ray_tpu.models import build_model, model_config
        from ray_tpu.util.compile_cache import use_compile_cache
        use_compile_cache()
        with _SetupPhase(record, _sp.SETUP_MODEL):
            config = model_config(model)
            built_mesh = None
            if mesh:
                # the replica's own mesh, over as many of its devices as
                # the axes name (a replica has no data axis unless asked
                # for one)
                from ray_tpu.parallel.mesh import AXIS_ORDER, MeshSpec
                spec = MeshSpec(**{"dp": 1, **mesh})
                sizes = [getattr(spec, a) for a in AXIS_ORDER]
                n = (len(jax.devices()) if -1 in sizes
                     else math.prod(sizes))
                if n > len(jax.devices()):
                    raise ValueError(
                        f"mesh {mesh} needs {n} devices, this replica has "
                        f"{len(jax.devices())}: grant it the chips "
                        f"(ray_actor_options={{'num_tpus': {n}}})")
                built_mesh = spec.build(jax.devices()[:n])
            page_size = int(page_size or CONFIG.llm_page_size)
            max_batch = int(max_batch or CONFIG.llm_max_batch)
            if not num_pages and kv_budget_bytes:
                from ray_tpu.serve.llm.kv_cache import pages_from_budget
                tp = built_mesh.shape.get("tp", 1) if built_mesh else 1
                num_pages = pages_from_budget(config, page_size,
                                              kv_budget_bytes, tp_shards=tp,
                                              sequences=max_batch)
            model = build_model(config, built_mesh)
            shardings = None
            if built_mesh is not None:
                # the replica's weights lie on its mesh as the training
                # rules say (heads/mlp/vocab over tp, embed over fsdp) and
                # the cache splits its kv heads over tp — which is what
                # pages_from_budget(tp_shards=tp) above assumed
                from ray_tpu.parallel.sharding import param_shardings
                shardings = param_shardings(built_mesh,
                                            model.param_logical_axes())
        with _SetupPhase(record, _sp.SETUP_WEIGHTS) as span:
            if weights is not None:
                import ray_tpu
                params = jax.device_put(ray_tpu.get(weights), shardings)
            else:
                record.watch(0)         # `init` is this engine's program
                params = jax.jit(model.init, out_shardings=shardings)(
                    jax.random.PRNGKey(seed))
                record.close()
                record.unwatch()
            span.add(bytes=_tree_bytes(params))
        self.core = EngineCore(config, params, mesh=built_mesh,
                               num_pages=num_pages, page_size=page_size,
                               max_batch=max_batch, record=record)
        self.incarnation = uuid.uuid4().hex[:8]
        self._lock = threading.Lock()        # core + buffers
        # rid -> {"toks": [...], "done", "reason", "err", "t_done",
        #         "attempt", "submit_t", "last_tok_t"}
        self._buf: Dict[str, dict] = {}
        self._metrics = _serving_metrics()
        self._stream = TokenStreamServer(self.incarnation, self._backlog,
                                         self._lock)
        # the step thread's traceback once core.step() has raised: the
        # engine is dead from then on and says so, it does not look slow
        self._failed: Optional[str] = None
        self._serve_stats = {"queue_wait_p95": 0.0,
                             "outstanding_tokens": 0}
        # times the step thread found its lock held, and the seconds it
        # then waited (`engine.lock_wait`)
        self._lock_waits, self._lock_wait_s = 0, 0.0
        self._stop = threading.Event()
        self._kick = threading.Event()
        self._thread = threading.Thread(target=self._loop,
                                        name="llm-engine-step",
                                        daemon=True)
        self._thread.start()

    # ---------------------------------------------------- step thread
    def _loop(self) -> None:
        from ray_tpu._private.config import CONFIG
        _name_os_thread(self._thread.name)
        while not self._stop.is_set():
            self._take_lock()
            busy = self.core.has_work
            self._lock.release()
            if not busy:
                with _sp.span(_sp.WAIT):
                    self._kick.wait(0.05)
                self._kick.clear()
                continue
            self._take_lock()
            try:
                t0 = _clock()
                try:
                    events = self.core.step()
                except Exception:
                    # a compile error or exhausted HBM is not transient
                    self._fail(traceback.format_exc())
                    return
                self._ingest(events)
                if self._metrics:
                    self._metrics["step"].observe(_clock() - t0)
            finally:
                self._lock.release()
            # chaos pacing; at 0 (production) still a yield, or this
            # thread re-takes its lock before generate() and subscribers
            # waiting on it ever run
            with _sp.span(_sp.YIELD):
                time.sleep(CONFIG.llm_step_delay_s)

    def _take_lock(self) -> None:
        """The step thread's acquisitions: the only things it does under
        no other span. A lock that is free costs the try; one that a
        caller's `generate()` / `cancel()`, `engine_stats()` or a
        subscriber's backlog holds is waited for under a span and counted,
        so idle time of the device under no span of the program is the
        process standing still, not this wait."""
        if self._lock.acquire(blocking=False):
            return
        t0 = _clock()
        with _sp.span(_sp.LOCK_WAIT):
            self._lock.acquire()
        self._lock_waits += 1
        self._lock_wait_s += _clock() - t0

    def _fail(self, err: str) -> None:          # holds self._lock
        """core.step() raised: every open request ends now with `err`,
        generate() refuses from here on, check_health() fails."""
        self._failed = err
        now = time.monotonic()
        events = []
        for rid, b in self._buf.items():
            if b["done"]:
                continue
            b.update(done=True, reason=FINISH_ERROR, err=err, t_done=now)
            events.append({"rid": rid, "token": None,
                           "seq": len(b["toks"]), "first": False,
                           "done": True, "reason": FINISH_ERROR,
                           "attempt": b["attempt"], "err": err})
        if events:
            self._stream.publish(events)

    def check_health(self) -> None:
        """Raises once the step thread has died (the replica's report
        loop calls this; a replica that fails it stops reporting and
        the controller replaces it)."""
        if self._failed is not None:
            raise RuntimeError(f"llm engine step failed:\n{self._failed}")

    def _ingest(self, events: List[dict]) -> None:
        """Record step output into the stream's backlog buffers, then
        push it to the subscribers, OUTSIDE any model time."""
        with _sp.span(_sp.INGEST):
            now = time.monotonic()
            for ev in events:
                b = self._buf.get(ev["rid"])
                if b is None:
                    continue
                if ev["token"] is not None:
                    if not b["toks"] and self._metrics:
                        self._metrics["ttft"].observe(now - b["submit_t"])
                    elif b["toks"] and self._metrics:
                        self._metrics["tpot"].observe(
                            now - b["last_tok_t"])
                    b["last_tok_t"] = now
                    b["toks"].append(ev["token"])
                    if self._metrics:
                        self._metrics["tokens"].inc()
                if ev["done"]:
                    b["done"] = True
                    b["reason"] = ev["reason"]
                    b["t_done"] = now
            self._sweep(now)
            self._stream.publish(events)

    def _sweep(self, now: float) -> None:     # holds self._lock
        dead = [rid for rid, b in self._buf.items()
                if b["done"] and now - b["t_done"] > 120.0]
        for rid in dead:
            self._buf.pop(rid, None)

    def _backlog(self, rid: str, cursor: int) -> Optional[dict]:
        """Stream-subscribe replay: everything from `cursor` on. The
        stream server calls this holding self._lock."""
        b = self._buf.get(rid)
        if b is None:
            return None
        return {"rid": rid, "attempt": b["attempt"],
                "base": cursor, "toks": list(b["toks"][cursor:]),
                "done": b["done"], "reason": b["reason"],
                "err": b["err"]}

    # ------------------------------------------------------ serve API
    def ping(self):
        return "pong"

    def generate(self, prompt, max_tokens: int = 16, stop=(),
                 rid: Optional[str] = None, attempt: int = 0) -> dict:
        """Accept one generation; its tokens arrive on the push stream:
        subscribe at the returned `stream` address with `rid`."""
        submit_t = time.monotonic()
        with _sp.span(_sp.SUBMIT, rid=rid or ""):
            self._lock.acquire()
        try:
            self.check_health()
            rid = self.core.submit(prompt, max_tokens=max_tokens,
                                   stop=stop, rid=rid, attempt=attempt,
                                   submit_t=submit_t)
            self._buf[rid] = {"toks": [], "done": False, "reason": None,
                              "err": None, "t_done": 0.0,
                              "attempt": int(attempt),
                              "submit_t": submit_t, "last_tok_t": 0.0}
        finally:
            self._lock.release()
        self._kick.set()
        return {"rid": rid, "attempt": int(attempt),
                "incarnation": self.incarnation,
                "stream": self._stream.addr}

    def cancel(self, rid: str) -> bool:
        with self._lock:
            self._buf.pop(rid, None)
            return self.core.cancel(rid)

    def drain(self) -> List[dict]:
        """Stop admission + decode, return re-dispatchable in-flight
        descriptors. Subscribers see a terminal 'drained' frame and
        fail over; the descriptors carry emitted tokens so the
        survivor resumes mid-generation."""
        with self._lock:
            events, descs = self.core.drain()
            # the tokens that were in flight are the clients' before the
            # terminal frame (a request may have ended with them)
            self._ingest(events)
            now = time.monotonic()
            drained_events = []
            for d in descs:
                b = self._buf.get(d["rid"])
                if b is not None:
                    b["done"] = True
                    b["reason"] = FINISH_DRAINED
                    b["t_done"] = now
                drained_events.append(
                    {"rid": d["rid"], "token": None, "seq": 0,
                     "first": False, "done": True,
                     "reason": FINISH_DRAINED, "attempt": d["attempt"]})
            if drained_events:
                self._stream.publish(drained_events)
        return descs

    def engine_stats(self) -> dict:
        with self._lock:        # no step is being dispatched
            st = self.core.stats()
            in_cache = self.core.cache_stats()
        st.update(self.core.device_stats(in_cache))
        st["lock_waits"] = self._lock_waits
        st["lock_wait_s"] = self._lock_wait_s
        st["pid"] = os.getpid()
        st["failed"] = self._failed
        st["incarnation"] = self.incarnation
        st["stream"] = self._stream.addr
        return st

    def __serve_stats__(self) -> dict:
        """Merged into the replica's pushed report — the r11-style
        injectable queue-latency p95 the controller's latency-target
        autoscaling consumes."""
        # This runs on the replica's report thread, whose reports are
        # also its liveness: it must never wait for the step thread,
        # which holds the lock through a whole step and so through
        # every first compile (a replica compiling for 11 s was killed
        # as dead). A busy engine reports what it last could.
        if self._lock.acquire(blocking=False):
            try:
                self._serve_stats = {
                    "queue_wait_p95": self.core.queue_wait_p95(),
                    "outstanding_tokens": self.core.outstanding_tokens()}
            finally:
                self._lock.release()
        return self._serve_stats

    def close(self):
        self._stop.set()
        self._kick.set()
        self._stream.close()

    def __del__(self):
        try:
            self.close()
        except BaseException:
            pass


def _serving_metrics() -> Optional[dict]:
    """Serving histograms on the cluster metrics plane (merged by the
    head's ClusterCollector like every other per-process registry)."""
    try:
        from ray_tpu._private.metrics_plane import serving_metrics
        return serving_metrics()
    except BaseException:
        return None
