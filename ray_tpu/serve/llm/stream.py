"""Push token transport: tokens ride peer-dialed r18-plane connections.

The engine replica opens its own listener (exactly the worker-direct
idiom: accept loop + `protocol.Connection(server=True)`); consumers
dial it once per replica through the same `dial_cached` machinery the
direct actor caller uses and send one `llm_sub` frame per request.
After that every token is server-PUSHED on that connection — the head
sees zero frames per token, the client polls nothing. Everything one
engine step owes one connection leaves in ONE `llm_tok` frame: a record
a request, however many of the connection's requests the step moved
(one encode, one write, one wake of the peer's reader a step, where a
frame a request cost the step thread 0.12-0.15 ms each: PERF.md,
Findings PR 59). Nothing is held for a later step. Once the frames are
written the step thread gives the interpreter up for `HANDOFF_S`, so a
reader thread of its own process reads them before the next step's host
work and not at whatever point that work next lets it in.

Fencing: every frame carries the engine's incarnation and every record
its request's attempt number. The client registered an expectation at
subscribe time; stale records — a zombie replica still decoding into a
partition, or a superseded attempt after failover — are counted and
dropped, never delivered, and their neighbours in the frame are.
Duplicate suppression uses the `base` sequence offset: subscribe
replays the backlog from the client's cursor, and overlap trimming
makes replay + live racing harmless.

Wire frames use "req" for the request id — the envelope reserves
"rid" for its own integer reply-id field:
  client -> engine  {"type": "llm_sub", "req", "cursor"}
                    {"type": "llm_unsub", "req"}
  engine -> client  {"type": "llm_tok", "inc", "recs": [record, ...]}
                    record: {"req", "attempt", "base", "toks", "done",
                             "reason", "err"} ("unknown": True when the
                    rid isn't on this replica — the consumer fails over)
A subscribe's replay and the `unknown` answer are frames of one record.
The client hands a sink one dict a record, the frame's `type` and `inc`
beside the record's keys and `unknown`.
"""
from __future__ import annotations

import socket
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from ray_tpu._private import protocol
from ray_tpu.serve.llm import spans as _sp

STREAM_STATS = {
    "frames_out": 0,        # server: token frames pushed
    "records_out": 0,       # server: per-request records inside them
    "frames_in": 0,         # client: token frames received
    "tokens_in": 0,         # client: tokens accepted
    "zombie_dropped": 0,    # client: records fenced (stale inc/attempt)
    "conn_drops": 0,        # client: stream connections lost
    "subscribes": 0,        # client: llm_sub frames sent
}


# What the step thread sleeps once a step's frames are written. A consumer
# inside this process (a test's, the benchmark's, a caller beside the
# replica) reads on a thread the write has woken and that needs the
# interpreter: `conn.send` gives it up for the microseconds of its system
# call, which a reader catches only where the kernel runs it at once, and
# the next release is wherever the next step's host work blocks. With a
# frame a request the reader had a chance a lane; with one frame it has
# this one. The kernel rounds any sleep up by its timer slack, so this
# asks for the least there is (some 0.06 ms).
HANDOFF_S = 1e-6


def _record(rid: str, src: dict, base: int, toks: List[int]) -> dict:
    """A request's part of a frame: `toks` from position `base` on, and
    how the request stands in `src` (a step's record or a backlog)."""
    return {"req": rid, "attempt": src["attempt"], "base": base,
            "toks": toks, "done": src["done"], "reason": src["reason"],
            "err": src["err"]}


class TokenStreamServer:
    """Engine-side push fan-out. Runs inside the replica actor's
    process; `publish` is called by the engine step thread with each
    step's events."""

    def __init__(self, incarnation: str,
                 backlog: Callable[[str, int], Optional[dict]],
                 engine_lock):
        self._inc = incarnation
        # `backlog` and `publish` both run under `engine_lock` (the
        # engine's, held by its step thread while it ingests a step)
        self._backlog = backlog
        self._engine_lock = engine_lock
        self._lock = threading.Lock()
        # rid -> list of (conn, sent_cursor)
        self._subs: Dict[str, List[list]] = {}
        self._conns: List[protocol.Connection] = []
        lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lsock.bind(("0.0.0.0", 0))
        lsock.listen(64)
        self._lsock = lsock
        self._port = lsock.getsockname()[1]
        self._closed = threading.Event()
        threading.Thread(target=self._accept_loop,
                         name="llm-stream-accept", daemon=True).start()

    @property
    def addr(self) -> Tuple[str, int]:
        return (_advertise_host(), self._port)

    def _accept_loop(self) -> None:
        while not self._closed.is_set():
            try:
                sock, _ = self._lsock.accept()
            except OSError:
                return
            conn = protocol.Connection(sock, self._handle,
                                       on_close=self._on_close,
                                       name="llm-stream", server=True)
            with self._lock:
                self._conns.append(conn)
            conn.start()

    def _on_close(self, conn) -> None:
        with self._lock:
            self._conns = [c for c in self._conns if c is not conn]
            for rid in list(self._subs):
                self._subs[rid] = [s for s in self._subs[rid]
                                   if s[0] is not conn]
                if not self._subs[rid]:
                    del self._subs[rid]

    def _handle(self, conn, msg: dict) -> None:
        mtype = msg.get("type")
        if mtype == "llm_sub":
            rid = msg["req"]
            cursor = int(msg.get("cursor", 0))
            # Replay and registration are one step under the engine's
            # lock, so no live frame can reach this subscriber ahead of
            # its replay: the client drops a frame that starts past its
            # cursor as a gap, and a stream whose first frames were
            # dropped ends short. (Registering first and replaying when
            # the lock came free lost whole generations to that: the
            # step loop re-takes its lock at once and a waiter can
            # starve until the request is done.)
            with self._engine_lock:
                back = self._backlog(rid, cursor)
                if back is None:
                    self._send(conn, [{"req": rid, "unknown": True,
                                       "attempt": -1, "base": cursor,
                                       "toks": [], "done": True,
                                       "reason": None,
                                       "err": "unknown_rid"}])
                    return
                if back["toks"] or back["done"]:
                    self._send(conn, [_record(rid, back, back["base"],
                                              back["toks"])])
                if not back["done"]:
                    with self._lock:
                        self._subs.setdefault(rid, []).append(
                            [conn, back["base"] + len(back["toks"])])
        elif mtype == "llm_unsub":
            rid = msg["req"]
            with self._lock:
                subs = self._subs.get(rid)
                if subs:
                    self._subs[rid] = [s for s in subs
                                       if s[0] is not conn]
                    if not self._subs[rid]:
                        del self._subs[rid]
        elif mtype == protocol.PING:
            conn.reply(msg, ok=True)

    def _send(self, conn, recs: List[dict]) -> None:
        """One frame: the incarnation once, and a record a request."""
        try:
            conn.send({"type": "llm_tok", "inc": self._inc, "recs": recs})
            STREAM_STATS["frames_out"] += 1
            STREAM_STATS["records_out"] += len(recs)
        except protocol.ConnectionClosed:
            pass

    def publish(self, events: List[dict]) -> None:
        """Push one step's events: one frame a connection, holding a
        record for each of its requests the step moved (a step emits at
        most one token per sequence, but a drain can batch terminals)."""
        per_rid: Dict[str, dict] = {}
        for ev in events:
            rec = per_rid.setdefault(
                ev["rid"], {"base": ev["seq"], "toks": [],
                            "done": False, "reason": None, "err": None,
                            "attempt": ev["attempt"]})
            if ev["token"] is not None:
                rec["toks"].append(ev["token"])
            if ev["done"]:
                rec["done"] = True
                rec["reason"] = ev["reason"]
                rec["err"] = ev.get("err")
        owed: Dict[protocol.Connection, List[dict]] = {}
        with self._lock:
            for rid, rec in per_rid.items():
                for s in self._subs.get(rid, ()):
                    conn, sent = s
                    base, toks = rec["base"], rec["toks"]
                    if sent > base:
                        # replay already covered part of this record
                        skip = min(sent - base, len(toks))
                        base, toks = base + skip, toks[skip:]
                        if not toks and not rec["done"]:
                            continue
                    owed.setdefault(conn, []).append(
                        _record(rid, rec, base, toks))
                    s[1] = base + len(toks)
                if rec["done"]:
                    self._subs.pop(rid, None)
        if not owed:
            return
        # one span for all of a step's frames: a span around each send
        # cost the step thread more than the bound of the cell it was
        # measured in (PERF.md, Findings PR 26)
        with _sp.span(_sp.PUBLISH, frames=len(owed),
                      records=sum(map(len, owed.values()))):
            for conn, recs in owed.items():
                self._send(conn, recs)
            time.sleep(HANDOFF_S)

    def close(self) -> None:
        self._closed.set()
        try:
            self._lsock.close()
        except OSError:
            pass
        with self._lock:
            conns, self._conns = self._conns, []
        for c in conns:
            try:
                c.close()
            except BaseException:
                pass


class StreamClient:
    """Consumer-side demux: one cached connection per engine endpoint
    (shared across requests, `direct_actor.dial_cached`), frames
    routed to per-request sinks with incarnation/attempt fencing."""

    def __init__(self):
        self._lock = threading.Lock()
        self._conns: Dict[tuple, protocol.Connection] = {}
        # rid -> (sink, expect_inc, expect_attempt, addr)
        self._routes: Dict[str, tuple] = {}

    def subscribe(self, addr: Tuple[str, int], rid: str,
                  expect_inc: str, expect_attempt: int,
                  cursor: int, sink) -> bool:
        """Route rid's frames from `addr` into `sink` (a Queue);
        returns False when the endpoint is unreachable (caller fails
        over). Re-subscribing the same rid (failover to a new replica
        / new attempt) replaces the route and its fence."""
        addr = (addr[0], int(addr[1]))
        from ray_tpu._private.direct_actor import dial_cached
        with self._lock:
            self._routes[rid] = (sink, expect_inc, int(expect_attempt),
                                 addr)
        conn = dial_cached(self._conns, self._lock, addr,
                           handler=self._on_msg,
                           on_close=self._on_close)
        if conn is None:
            with self._lock:
                self._routes.pop(rid, None)
            return False
        try:
            conn.send({"type": "llm_sub", "req": rid,
                       "cursor": int(cursor)})
            STREAM_STATS["subscribes"] += 1
        except protocol.ConnectionClosed:
            with self._lock:
                self._routes.pop(rid, None)
            return False
        return True

    def unsubscribe(self, rid: str) -> None:
        with self._lock:
            route = self._routes.pop(rid, None)
            conn = self._conns.get(route[3]) if route else None
        if conn is not None and not conn.closed:
            try:
                conn.send({"type": "llm_unsub", "req": rid})
            except protocol.ConnectionClosed:
                pass

    def _on_msg(self, conn, msg: dict) -> None:
        if msg.get("type") != "llm_tok":
            return
        STREAM_STATS["frames_in"] += 1
        inc, recs = msg.get("inc"), msg.get("recs", ())
        with self._lock:
            routes = [self._routes.get(rec.get("req")) for rec in recs]
        for rec, route in zip(recs, routes):
            if route is None:
                continue
            sink, expect_inc, expect_attempt, _addr = route
            if not rec.get("unknown") and (
                    inc != expect_inc
                    or rec.get("attempt") != expect_attempt):
                # zombie fence: a stale incarnation (replica restarted /
                # partitioned survivor) or superseded attempt never
                # reaches the consumer
                STREAM_STATS["zombie_dropped"] += 1
                continue
            STREAM_STATS["tokens_in"] += len(rec.get("toks", ()))
            # the decoded record is this frame's alone: it becomes the
            # message, the frame's `type` and `inc` beside its own keys
            rec.update(type="llm_tok", inc=inc)
            rec.setdefault("unknown", False)
            sink.put(rec)

    def _on_close(self, conn) -> None:
        STREAM_STATS["conn_drops"] += 1
        with self._lock:
            dead = [a for a, c in self._conns.items() if c is conn]
            for a in dead:
                self._conns.pop(a, None)
            victims = [(rid, r) for rid, r in self._routes.items()
                       if r[3] in dead]
            for rid, _r in victims:
                self._routes.pop(rid, None)
        for rid, (sink, _i, _a, _ad) in victims:
            sink.put({"type": "llm_closed", "rid": rid})


_client: Optional[StreamClient] = None
_client_lock = threading.Lock()


def stream_client() -> StreamClient:
    """Process-wide client (one connection per engine, shared by every
    in-flight request in this process)."""
    global _client
    with _client_lock:
        if _client is None:
            _client = StreamClient()
        return _client


def _advertise_host() -> str:
    """Host this process's listeners are reachable at. Workers are
    host-local to their agent, so the source address of the runtime
    connection (loopback locally, the right NIC cross-machine) is the
    address peers on the cluster fabric can dial back."""
    try:
        from ray_tpu._private import context as _context
        ctx = _context.maybe_ctx()
        conn = getattr(ctx, "conn", None)
        sock = getattr(conn, "_sock", None)
        if sock is not None:
            return sock.getsockname()[0]
    except BaseException:
        pass
    return "127.0.0.1"
