"""Wire protocol for the ray_tpu runtime.

Design: a single full-duplex, length-prefixed-frame protocol over TCP
(localhost) or later unix sockets. Either endpoint may send *requests*
(carry a fresh ``rid``) and *replies* (echo the ``rid``). A ``Connection``
owns a reader thread that routes replies to waiting futures and hands
requests to a handler callback, so both sides can issue RPCs concurrently
(a worker blocked in a nested ``get()`` keeps receiving pushed tasks).

This replaces the reference's per-service gRPC stack (reference
src/ray/rpc/: gcs_server/, node_manager/, worker/) with one multiplexed
channel per process pair — appropriate because our control plane is
centralized in the driver process for the single-node runtime, and the
bulk data plane is shared memory, not the socket.

Frame bodies are versioned protobuf Envelopes (`ray_tpu/protos/
wire.proto` via `_private/wire.py`): control data is schema'd and
language-neutral; Python-only payloads ride an explicit `pickled`
bytes leaf. A peer with an incompatible wire MAJOR version is refused
at the first frame, before any pickled leaf is decoded.
"""
from __future__ import annotations

import itertools
import os
import select as _select
import socket
import struct
import threading
import time
from typing import Any, Callable, Optional

from ray_tpu import native
from ray_tpu._private.wire import (BATCH_MIN_MINOR, BATCH_TYPE,
                                   CHANNEL_MIN_MINOR,
                                   DECREF_DELTA_MIN_MINOR,
                                   DELEGATE_MIN_MINOR,
                                   DIRECT_ACTOR_MIN_MINOR,
                                   MANIFEST_MIN_MINOR, METRICS_MIN_MINOR,
                                   RAW_KEY, TRACE_KEY, TRACE_MIN_MINOR,
                                   WIRE_MAJOR, WireVersionError, dumps,
                                   dumps_batch, encode_batch_parts,
                                   encode_frame_parts, loads_ex)

_LEN = struct.Struct("<Q")

# Process-wide frame accounting (this process's connections only):
# physical socket frames vs logical messages, both directions. Read by
# bench_core.py to report control frames per completed task; plain int
# increments under the GIL are accurate enough for benchmarking.
WIRE_STATS = {"tx_frames": 0, "tx_msgs": 0, "rx_frames": 0, "rx_msgs": 0}

# r10 shared-read-loop accounting (this process's Poller, if any):
# plain ints bumped under the GIL on the loop thread — same accuracy
# contract as WIRE_STATS. The metrics plane samples these into gauges
# at scrape time, so the loop itself never touches a metrics lock.
#   passes       service passes that handled >= 1 ready fd
#   frames/bytes complete frames drained through the poller pumps
#   busy_ns      cumulative time spent servicing ready fds
#   max_pass_ns  slowest single servicing pass (the loop-lag ceiling:
#                while one pass runs, every other connection's reads
#                wait this long)
POLLER_STATS = {"passes": 0, "frames": 0, "bytes": 0,
                "busy_ns": 0, "max_pass_ns": 0}

# Message types (flat namespace; direction noted).
REGISTER = "register"            # worker -> driver
TASK = "task"                    # driver -> worker: run a normal task
ACTOR_CREATE = "actor_create"    # driver -> worker: instantiate actor
ACTOR_TASK = "actor_task"        # driver -> worker: run actor method
TASK_DONE = "task_done"          # worker -> driver (reply to TASK/ACTOR_*)
GET_OBJECT = "get_object"        # worker -> driver
PUT_OBJECT = "put_object"        # worker -> driver
WAIT = "wait"                    # worker -> driver
SUBMIT = "submit"                # worker -> driver: nested task submission
SUBMIT_ACTOR = "submit_actor"    # worker -> driver: nested actor creation
SUBMIT_ACTOR_TASK = "submit_actor_task"  # worker -> driver
KV_OP = "kv_op"                  # worker -> driver: internal KV get/put/del
DECREF = "decref"                # worker -> driver: ref-count release
ADDREF = "addref"                # worker -> driver
SHUTDOWN = "shutdown"            # driver -> worker
CANCEL_TASK = "cancel_task"      # driver -> worker: interrupt a running task
UNQUEUE_TASK = "unqueue_task"    # driver -> worker: drop a pipelined task
                                 #   that has not started (reply ok)
PING = "ping"                    # either
REPLY = "reply"                  # either (generic reply)
STATE_OP = "state_op"            # worker -> driver: state/metrics queries
DECREF_BATCH = "decref_batch"    # worker -> driver: N ref-count releases
BATCH = BATCH_TYPE               # either: coalesced sub-frames (MINOR>=1)
TRACE_DUMP = "trace_dump"        # collector -> any: drain the peer's
                                 #   flight recorder (reply: dump/processes
                                 #   + monotonic now for clock alignment)
METRICS_DUMP = "metrics_dump"    # collector -> any: snapshot the peer's
                                 #   metrics registry (r11; agents drain
                                 #   their own workers and reply with the
                                 #   whole node, like TRACE_DUMP)

# ---- multi-host: node agent <-> head (reference raylet <-> GCS,
# gcs_node_manager.h:62 HandleRegisterNode; ray_syncer.h:88 resource
# gossip; object_manager.cc node-to-node transfer) ----
NODE_REGISTER = "node_register"        # agent -> head (reply: node_id)
NODE_HEARTBEAT = "node_heartbeat"      # agent -> head: resource view
NODE_ENQUEUE = "node_enqueue"          # head -> agent: spec to queue
NODE_CANCEL_PENDING = "node_cancel_pending"  # head -> agent (reply found)
NODE_CANCEL_RUNNING = "node_cancel_running"  # head -> agent
NODE_KILL_WORKER = "node_kill_worker"  # head -> agent
NODE_SEND_ACTOR_TASK = "node_send_actor_task"  # head -> agent (reply ok)
NODE_RESERVE_BUNDLE = "node_reserve_bundle"    # head -> agent (reply ok)
NODE_RELEASE_BUNDLE = "node_release_bundle"    # head -> agent
NODE_EVENT = "node_event"              # agent -> head: dispatch/lost/
                                       #   object_at location registers/...
NODE_TASK_DONE = "node_task_done"      # agent -> head: control + results
NODE_DELETE_OBJECT = "node_delete_object"      # head -> agent
NODE_SHUTDOWN = "node_shutdown"        # head -> agent
OBJECT_LOOKUP = "object_lookup"        # agent -> head (reply: stored |
                                       #   location | timeout)
PULL_OBJECT = "pull_object"            # any -> holder (reply: pull meta)
PULL_CHUNK = "pull_chunk"              # any -> holder (reply: data)

# ---- object plane v2 (reference object_manager/object_directory.cc +
# pull_manager.cc): cluster object directory + multi-source pulls +
# tree broadcast ----
LOCATE_OBJECT = "locate_object"        # any -> head (reply: locations,
                                       #   head_has, nbytes) — non-blocking
                                       #   directory read for multi-source
OBJECT_ADDED = "object_added"          # agent -> head: local copy sealed
OBJECT_REMOVED = "object_removed"      # agent -> head: copy gone (holder
                                       #   lost it / stale location)
BCAST_PLAN = "bcast_plan"              # head -> agent: pull object_id from
                                       #   the given parent, then serve
                                       #   your subtree

# ---- delegated bulk-lease scheduling (r10; wire MINOR >= 3,
# negotiated by observation like BatchFrame). The head stops being a
# per-task participant: it grants agents BATCHES of queued tasks under
# one lease and learns completions in coalesced batches; per-task
# task_dispatched events are suppressed for leased tasks. ----
NODE_LEASE_BATCH = "node_lease_batch"  # head -> agent: specs + lease_id
                                       #   + resource budget snapshot
NODE_TASK_DONE_BATCH = "node_task_done_batch"  # agent -> head: N task
                                       #   completions (ctrl + inline/
                                       #   located results each)
NODE_LEASE_REVOKE = "node_lease_revoke"  # head -> agent, fire-and-
                                       #   forget: reclaim queued-not-
                                       #   started tasks (UNQUEUE
                                       #   steal-back for
                                       #   worker FIFOs); the hand-back
                                       #   is the agent's buffered
                                       #   "lease_reclaimed" NODE_EVENT,
                                       #   never a reply — a dropped
                                       #   reply must not strand work
NODE_FIND_TASK = "node_find_task"      # head -> agent (reply: state
                                       #   pending|running|None +
                                       #   worker_id) — cancel path's
                                       #   substitute for the
                                       #   suppressed dispatch events
NODE_HB_RESYNC = "node_hb_resync"      # head -> agent: heartbeat seq
                                       #   gap observed; send a full
                                       #   snapshot next beat (N10
                                       #   delta-sync)
NODE_DECREF_DELTA = "node_decref_delta"  # agent -> head (r16; wire
                                       #   MINOR >= 7): coalesced
                                       #   per-object refcount
                                       #   releases {oid: n} + a
                                       #   per-node seq the head
                                       #   watermarks so rejoin
                                       #   replays dedup (the r15
                                       #   done-batch discipline
                                       #   extended to decrefs)
# ---- direct actor call plane (r18; wire MINOR >= 8, negotiated by
# observation like BatchFrame). The head stops being a per-call party:
# a caller resolves the actor's endpoint ONCE, dials the hosting
# node's listener, streams calls over that one connection (per-handle
# submission order rides the stream), and replies return inline on the
# same connection. The head stays the owner of actor lifecycle via the
# caller's coalesced inflight mirror. ----
ACTOR_RESOLVE = "actor_resolve"        # caller -> head (reply: endpoint
                                       #   host/port + worker_id +
                                       #   restart epoch + node
                                       #   incarnation, or direct=False
                                       #   / state=dead|pending)
ACTOR_TASK_DIRECT = "actor_task_direct"  # caller -> hosting agent/head
                                       #   listener (reply: inline
                                       #   results / located hints, or
                                       #   redirect=True NACK with
                                       #   started flag — stale
                                       #   endpoint, fenced node,
                                       #   head-disconnected host)
ACTOR_INFLIGHT_DELTA = "actor_inflight_delta"  # remote caller -> head:
                                       #   coalesced mirror of direct
                                       #   in-flight calls (adds carry
                                       #   the spec so death/restart
                                       #   still produces
                                       #   ActorDiedError/requeue;
                                       #   dones carry located results
                                       #   + containment and release
                                       #   pins; fail/requeue entries
                                       #   route NACKed calls back
                                       #   through the head's retry
                                       #   machinery)
NODE_FENCED = "node_fenced"            # head -> agent (r17): a state-
                                       #   bearing frame arrived from a
                                       #   STALE node incarnation (the
                                       #   node was declared dead while
                                       #   still alive — partition/
                                       #   stall zombie). The frame was
                                       #   dropped; the agent must kill
                                       #   its workers, clear its
                                       #   scheduler/lease ledgers, and
                                       #   re-register fresh.


class ConnectionClosed(Exception):
    pass


class FrameTooLarge(ConnectionClosed):
    """A frame's length prefix exceeds wire_max_frame_bytes: corrupt
    (or hostile) stream. The connection dies before the reader
    attempts a multi-GB allocation; existing ConnectionClosed handling
    covers recovery."""


# ---- protocol-level network fault injection (r17) ----
# One process-wide ChaosNet, constructed lazily ONLY when
# RAY_TPU_CHAOS=1 — with chaos off the module global stays None and
# the hot-path hooks cost a single global load + None check, with
# byte-identical wire behavior. Both engines pass through the hook
# points: every decoded inbound frame funnels through
# Connection._handle_frame and every outbound write through
# Connection._emit_locked, regardless of native/python pump.
_CHAOS_NET: Optional["ChaosNet"] = None


def chaos_net() -> Optional["ChaosNet"]:
    """The process chaos controller, created on first call when
    RAY_TPU_CHAOS=1 (None otherwise). Once created it persists for
    the process; tests clear its rules rather than destroy it."""
    global _CHAOS_NET
    if _CHAOS_NET is None:
        from ray_tpu._private.config import CONFIG
        if not CONFIG.chaos:
            return None
        _CHAOS_NET = ChaosNet(CONFIG.chaos_seed)
    return _CHAOS_NET


class ChaosNet:
    """Deterministic protocol-level fault injection between this
    process and named peers (tests/chaos.py drives it).

    Rules are keyed by peer id — matched against a connection's
    ``meta["node_id"]`` (set at NODE_REGISTER), ``meta["chaos_peer"]``
    (explicit test tag), its ``name``, or the wildcard ``"*"`` — and
    carry a mode:

    - ``partition``: TCP-faithful link partition. Frames are PARKED
      (not lost — a partition makes TCP traffic late, not gone:
      retransmission delivers it after heal), inbound on a relay
      queue, outbound in a per-connection buffer flushed FIFO-ahead
      of the first post-heal write. ``Connection.close()`` on a
      matching connection is DEFERRED: a partitioned link delivers
      no FIN either, so the head declaring the node dead must not
      tear the stream down — after heal the zombie's frames arrive
      on the SAME connection under a stale incarnation, which is
      exactly the split-brain the fencing layer exists to stop. A
      blip shorter than the death timeout instead delivers
      everything late and loses nothing.
    - ``blackhole``: every matching frame vanishes permanently (a
      lossy/asymmetric link, stronger than any real partition).
    - ``drop``: each frame dropped with probability ``p`` from the
      seeded RNG (RAY_TPU_CHAOS_SEED — failing runs replay).
    - ``delay``: inbound frames relay ``delay_s`` late (per-arrival
      FIFO); outbound writes sleep in the emitter (a slow link with
      real backpressure).
    """

    _PARK_CAP = 100_000            # frames parked per direction/conn

    def __init__(self, seed: int = 0):
        import random as _random
        self._rnd = _random.Random(seed)
        self._lock = threading.Lock()
        self._rules: dict[str, dict] = {}
        self.active = False          # fast-path gate: False == no rules
        self.stats = {"dropped_in": 0, "dropped_out": 0, "delayed": 0,
                      "parked_in": 0, "parked_out": 0,
                      "deferred_closes": 0}
        # delay-mode relay: (release_t, conn, frame) in arrival order
        self._delayq: list = []
        # partition-mode parking: id(conn) -> (conn, [frames])
        self._parked_in: dict[int, tuple] = {}
        self._parked_out: dict[int, tuple] = {}
        self._cv = threading.Condition(self._lock)
        self._relay_thread: Optional[threading.Thread] = None
        self._deferred_close: list = []

    # ---- rule management (tests) ----
    def set_rule(self, peer: str, mode: str, direction: str = "both",
                 p: float = 1.0, delay_s: float = 0.0) -> None:
        assert mode in ("partition", "blackhole", "drop", "delay"), mode
        assert direction in ("in", "out", "both"), direction
        with self._lock:
            self._rules[peer] = {"mode": mode, "dir": direction,
                                 "p": float(p), "delay_s": float(delay_s)}
            self.active = True

    def clear(self, peer: Optional[str] = None) -> None:
        """Heal: drop one rule (or all). Parked partition traffic
        drains — the relay thread replays inbound frames FIFO and
        outbound buffers flush ahead of the next write (nudged here so
        an idle direction still delivers). Deferred closes are simply
        forgotten: the link is healthy again and the connection keeps
        serving; if its owner really wanted it gone, the peer's own
        close (or fencing) finishes the job."""
        with self._lock:
            if peer is None:
                self._rules.clear()
            else:
                self._rules.pop(peer, None)
            self.active = bool(self._rules)
            if not self.active:
                self._deferred_close.clear()
            self._ensure_relay_locked()
            self._cv.notify_all()
            flush = [conn for _cid, (conn, frames)
                     in self._parked_out.items() if frames]
        for conn in flush:
            threading.Thread(target=conn._chaos_flush,
                             name="ray-tpu-chaos-flush",
                             daemon=True).start()

    def _rule_for(self, conn: "Connection") -> Optional[dict]:
        rules = self._rules
        meta = conn.meta
        for key in (meta.get("node_id"), meta.get("chaos_peer"),
                    conn.name, "*"):
            if key is not None:
                r = rules.get(key)
                if r is not None:
                    return r
        return None

    def _parks(self, conn: "Connection", direction: str) -> bool:
        rule = self._rule_for(conn)
        return (rule is not None and rule["mode"] == "partition"
                and rule["dir"] in (direction, "both"))

    def _ensure_relay_locked(self) -> None:
        if self._relay_thread is None:
            self._relay_thread = threading.Thread(
                target=self._relay_loop, name="ray-tpu-chaos-relay",
                daemon=True)
            self._relay_thread.start()

    # ---- inbound hook ----
    def on_frame_in(self, conn: "Connection", data: bytes) -> bool:
        """True = the frame was consumed (parked/dropped/delayed);
        False = deliver normally. Loss rules (blackhole/drop) are
        evaluated BEFORE the heal-drain FIFO park: a rule installed
        while a previous partition's backlog is still draining must
        discard fresh frames, not smuggle them through the queue."""
        rule = self._rule_for(conn)
        applies = rule is not None and rule["dir"] in ("in", "both")
        mode = rule["mode"] if applies else None
        with self._lock:
            entry = self._parked_in.get(id(conn))
            if mode == "partition":
                if entry is None:
                    entry = self._parked_in[id(conn)] = (conn, [])
                if len(entry[1]) < self._PARK_CAP:
                    entry[1].append(data)
                    self.stats["parked_in"] += 1
                else:
                    self.stats["dropped_in"] += 1
                self._ensure_relay_locked()
                return True
            if mode == "blackhole" or (
                    mode == "drop"
                    and self._rnd.random() < rule["p"]):
                self.stats["dropped_in"] += 1
                return True
            if entry is not None:
                # heal flush still draining: keep FIFO — this frame
                # queues behind the parked backlog. The entry persists
                # (possibly empty) until the relay thread observes it
                # drained AFTER its last delivery completed, so a
                # fresh frame can never overtake an in-flight parked
                # one (seq-watermarked deltas would drop the late
                # frame as a replay otherwise).
                entry[1].append(data)
                self._ensure_relay_locked()
                self._cv.notify_all()
                return True
            if mode == "delay":
                self._delayq.append(
                    (time.monotonic() + rule["delay_s"], conn, data))
                self.stats["delayed"] += 1
                self._ensure_relay_locked()
                self._cv.notify_all()
                return True
        return False

    # ---- outbound hook (caller holds conn._send_lock) ----
    def filter_out(self, conn: "Connection", frames: list) -> list:
        with self._lock:
            entry = self._parked_out.get(id(conn))
            parks = self._parks(conn, "out")
            if parks:
                if entry is None:
                    entry = self._parked_out[id(conn)] = (conn, [])
                room = self._PARK_CAP - len(entry[1])
                entry[1].extend(frames[:room])
                self.stats["parked_out"] += min(len(frames), room)
                self.stats["dropped_out"] += max(0,
                                                 len(frames) - room)
                return []
            prefix = []
            if entry is not None:
                # healed: parked frames flush FIRST (the caller holds
                # the send lock, so FIFO with this write is exact)
                prefix = entry[1][:]
                del self._parked_out[id(conn)]
        rule = self._rule_for(conn)
        if rule is None or rule["dir"] == "in":
            return prefix + frames
        mode = rule["mode"]
        if mode == "blackhole":
            self.stats["dropped_out"] += len(frames)
            return prefix
        if mode == "drop":
            kept = []
            with self._lock:
                for f in frames:
                    if self._rnd.random() < rule["p"]:
                        self.stats["dropped_out"] += 1
                    else:
                        kept.append(f)
            return prefix + kept
        if mode == "delay":
            time.sleep(rule["delay_s"])  # slow link: real backpressure
        return prefix + frames

    def has_parked_out(self, conn: "Connection") -> bool:
        entry = self._parked_out.get(id(conn))
        return entry is not None and bool(entry[1])

    def defer_close(self, conn: "Connection") -> bool:
        """True when `conn` sits behind an active both-direction
        partition/blackhole: the close is swallowed (recorded) — a
        partitioned link delivers no FIN, so the stream must survive
        for the post-heal fencing exchange."""
        rule = self._rule_for(conn)
        if rule is None or rule["mode"] not in ("partition",
                                                "blackhole") \
                or rule["dir"] != "both":
            return False
        with self._lock:
            self._deferred_close.append(conn)
        self.stats["deferred_closes"] += 1
        return True

    # ---- relay thread: delayed frames + healed partition backlogs ----
    def _relay_loop(self) -> None:
        while True:
            item = None
            with self._lock:
                # healed partitions first: replay parked inbound FIFO
                for cid, (conn, frames) in list(self._parked_in.items()):
                    if self._parks(conn, "in"):
                        continue             # still partitioned
                    if frames:
                        item = (conn, frames.pop(0))
                        break
                    del self._parked_in[cid]
                if item is None and self._delayq:
                    release_t, conn, data = self._delayq[0]
                    wait = release_t - time.monotonic()
                    if wait <= 0:
                        self._delayq.pop(0)
                        item = (conn, data)
                    else:
                        self._cv.wait(min(wait, 0.2))
                        continue
                if item is None:
                    self._cv.wait(0.2)
                    continue
            conn, data = item
            try:
                conn._handle_frame(data, _chaos_checked=True)
            except Exception:
                pass                     # chaos must not kill the relay


def _auth_token() -> Optional[bytes]:
    """Shared listener secret (RAY_TPU_AUTH_TOKEN). When set, every
    accepted connection must present it in a RAW first frame, verified
    with a constant-time compare BEFORE any frame is unpickled — the
    wire is pickle, so an unauthenticated peer would otherwise get
    arbitrary code execution (reference scopes this via gRPC + tokened
    client/job servers, python/ray/util/client/server/)."""
    from ray_tpu._private.config import CONFIG
    tok = CONFIG.auth_token
    return tok.encode() if tok else None


class Connection:
    """Full-duplex framed-message channel with request/reply correlation."""

    def __init__(self, sock: socket.socket,
                 handler: Callable[["Connection", dict], None],
                 on_close: Optional[Callable[["Connection"], None]] = None,
                 name: str = "", server: bool = False,
                 poller: Optional["Poller"] = None):
        self._sock = sock
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # Bound sends only (recv stays blocking: connections idle for
        # minutes legitimately): waiter-registry replies run inline on
        # sealing threads, so a wedged peer (full TCP buffer) must
        # surface as a ConnectionClosed after this budget instead of
        # hanging the sender forever — peer-death recovery then runs.
        try:
            self._sock.setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDTIMEO,
                struct.pack("ll", 30, 0))
        except OSError:
            pass
        self._handler = handler
        self._on_close = on_close
        self.name = name
        self._send_lock = threading.Lock()
        self._rid_counter = itertools.count(1)
        self._pending: dict[int, _Future] = {}
        self._pending_lock = threading.Lock()
        self._closed = threading.Event()
        self._server = server
        self.meta: dict = {}  # endpoint-attached metadata (worker id, etc.)
        # Wire version observed on the peer's frames (0 = nothing seen
        # yet). Batch emission is gated on it: until the peer proves it
        # speaks MINOR >= BATCH_MIN_MINOR, coalesced flushes go out as
        # individual frames in one sendall (compatible with any peer).
        self.peer_wire_version = 0
        # Opt-in coalescing queue (enable_coalescing): fire-and-forget
        # frames park here briefly and flush as one write.
        self._lazy: list[dict] = []
        self._lazy_lock = threading.Lock()
        self._lazy_wake = threading.Event()
        self._lazy_thread: Optional[threading.Thread] = None
        # r10 epoll loop: when a process-level Poller is attached, the
        # read side is driven by its shared event loop instead of a
        # dedicated reader thread. Pump state (native nb-reader or the
        # Python reassembly buffer over a dup'd socket) is created at
        # registration time by the poller.
        self._poller = poller
        self._nb_reader = None          # native.FrameReader (poller)
        self._pump_sock: Optional[socket.socket] = None   # py fallback
        self._pump_buf: Optional[bytearray] = None
        self._pump_eof = False
        self._finished = False
        self._finish_lock = threading.Lock()
        self._reader = threading.Thread(
            target=self._read_loop, name=f"ray-tpu-conn-{name}", daemon=True)

    def start(self) -> None:
        if self._poller is not None and self._poller.alive:
            if self._server and _auth_token() is not None:
                # auth handshake keeps its blocking semantics (size
                # guard + 10s slowloris deadline, verified before ANY
                # unpickling) on a short-lived thread; the connection
                # joins the shared loop once authenticated
                threading.Thread(
                    target=self._auth_then_register,
                    name=f"ray-tpu-auth-{self.name}",
                    daemon=True).start()
            else:
                self._poller.register(self)
            return
        self._poller = None             # poller gone: thread fallback
        self._reader.start()

    def _auth_then_register(self) -> None:
        if not self._check_auth():
            self._finish_read()         # closed: error futures etc.
            return
        poller = self._poller
        if poller is not None and poller.alive:
            poller.register(self)
        else:
            self._poller = None
            self._reader.start()

    def send_auth(self) -> None:
        """Client side: present the shared secret as the raw first
        frame (no-op when auth is disabled)."""
        token = _auth_token()
        if token is None:
            return
        with self._send_lock:
            try:
                self._sock.sendall(_LEN.pack(len(token)) + token)
            except OSError as e:
                self.close()
                raise ConnectionClosed(str(e)) from e

    def _check_auth(self) -> bool:
        """Server side (reader thread): verify the raw first frame
        before ANY unpickling. Closes and returns False on mismatch."""
        token = _auth_token()
        if token is None:
            return True
        try:
            # hard deadline: a peer that connects and sends nothing
            # must not pin this thread + fd forever (slowloris)
            self._sock.settimeout(10.0)
            header = self._read_exact(_LEN.size)
            (length,) = _LEN.unpack(header)
            if length > 4096:           # token frames are tiny
                raise ConnectionClosed("oversized auth frame")
            presented = self._read_exact(length)
            self._sock.settimeout(None)
        except (ConnectionClosed, OSError):
            self.close()        # malformed/short/slow: drop the socket
            return False
        import hmac
        if not hmac.compare_digest(presented, token):
            import sys as _sys
            _sys.stderr.write(
                f"ray_tpu: rejected unauthenticated connection "
                f"({self.name})\n")
            self.close()
            return False
        return True

    # ---- sending ----
    def send(self, msg: dict) -> None:
        """Immediate send. If a coalescing queue is pending, its frames
        are flushed FIRST in the same write — per-connection FIFO order
        is preserved between lazy and eager sends (the refcount
        protocol depends on it: an ADDREF parked in the queue must
        never be overtaken by the TASK_DONE that releases the pin).
        The lazy-queue drain and the socket write happen under one
        lock (_send_lock): draining outside it would let this eager
        frame overtake frames the flusher thread has already swapped
        out of the queue but not yet written."""
        with self._send_lock:
            frames = self._drain_lazy()
            frames.append(msg)
            self._emit_locked(frames)

    def send_lazy(self, msg: dict) -> None:
        """Queue a fire-and-forget frame on the coalescing queue: it
        flushes with its neighbors as one write after ~wire_batch
        thresholds (count / delay), or earlier if an eager send/reply
        follows. Falls back to send() when coalescing is off."""
        from ray_tpu._private.config import CONFIG
        if self._lazy_thread is None or not CONFIG.wire_batch:
            self.send(msg)
            return
        with self._lazy_lock:
            self._lazy.append(msg)
            n = len(self._lazy)
        if n >= CONFIG.wire_batch_max_frames:
            self.flush()
        else:
            self._lazy_wake.set()

    def flush(self) -> None:
        if not self._lazy:
            return
        with self._send_lock:
            frames = self._drain_lazy()
            if frames:
                self._emit_locked(frames)

    def _drain_lazy(self) -> list[dict]:
        """Swap the coalescing queue out. Callers hold _send_lock so
        the drained frames cannot be overtaken by a concurrent eager
        send before they reach the socket (lock order: _send_lock ->
        _lazy_lock; send_lazy takes only _lazy_lock)."""
        if not self._lazy:
            return []
        with self._lazy_lock:
            frames, self._lazy = self._lazy, []
        return frames

    def enable_coalescing(self) -> None:
        """Opt this connection's send_lazy() into micro-batched
        flushing (hot emitters: workers, the dispatch path). Without
        this, send_lazy() behaves exactly like send()."""
        if self._lazy_thread is not None:
            return
        self._lazy_thread = threading.Thread(
            target=self._lazy_flush_loop,
            name=f"ray-tpu-conn-flush-{self.name}", daemon=True)
        self._lazy_thread.start()

    def _lazy_flush_loop(self) -> None:
        from ray_tpu._private.config import CONFIG
        delay = max(0.0, CONFIG.wire_batch_delay_ms / 1000.0)
        while not self._closed.is_set():
            self._lazy_wake.wait()
            if self._closed.is_set():
                return
            if delay:
                # Collect-then-flush: the first frame of a burst opens
                # a `delay`-wide window and every frame emitted inside
                # it rides the same write. A lazy frame therefore waits
                # at most ~delay; anything latency-critical uses the
                # eager send() path, which also drains this queue
                # first, so the window never reorders or starves it.
                time.sleep(delay)
            self._lazy_wake.clear()
            try:
                self.flush()
            except ConnectionClosed:
                return

    def _peer_speaks_batch(self) -> bool:
        v = self.peer_wire_version
        return v // 100 == WIRE_MAJOR and v % 100 >= BATCH_MIN_MINOR

    def peer_speaks_delegate(self) -> bool:
        """Whether the peer demonstrated the delegated-scheduling wire
        (MINOR >= 3). Unknown (0) counts as NO: lease/done-batch ops
        would be silently dropped by an old peer's handler, so the
        sender stays on the per-task protocol until the peer proves
        itself (registration traffic always arrives first in
        practice)."""
        v = self.peer_wire_version
        return v // 100 == WIRE_MAJOR and v % 100 >= DELEGATE_MIN_MINOR

    def peer_speaks_metrics(self) -> bool:
        """Whether the peer answers METRICS_DUMP (MINOR >= 4). Unknown
        (0) counts as NO — an old peer's handler drops the unknown
        type without replying and would burn the collector's shared
        fan-out deadline (same rule as peer_speaks_delegate)."""
        v = self.peer_wire_version
        return v // 100 == WIRE_MAJOR and v % 100 >= METRICS_MIN_MINOR

    def peer_speaks_manifest(self) -> bool:
        """Whether the peer understands the r12 manifest object plane
        (MINOR >= 5). The transfer protocol itself negotiates per
        message (reply-shape, see object_transfer) — this gate exists
        for partial-holder OBJECT_ADDED reports, which an old head
        would misread as full locations. Unknown (0) counts as NO."""
        v = self.peer_wire_version
        return v // 100 == WIRE_MAJOR and v % 100 >= MANIFEST_MIN_MINOR

    def peer_speaks_channel(self) -> bool:
        """Whether the peer's wire-channel endpoint lands Envelope
        `raw` CH_DATA payloads (MINOR >= 6). Unknown (0) counts as NO:
        an older endpoint would decode the frame but miss the raw
        field's tensor, so the writer ships the pickled-body fallback
        until the peer's attach frame demonstrates the MINOR (r13
        wire-channel transport, experimental/wire_channel.py)."""
        v = self.peer_wire_version
        return v // 100 == WIRE_MAJOR and v % 100 >= CHANNEL_MIN_MINOR

    def peer_speaks_decref_delta(self) -> bool:
        """Whether the peer applies NODE_DECREF_DELTA frames
        (MINOR >= 7). Unknown (0) counts as NO: an old head would
        silently drop the unknown type and every release in it would
        leak for the session, so agents forward the workers' own
        DECREF_BATCH frames until the head proves itself."""
        v = self.peer_wire_version
        return (v // 100 == WIRE_MAJOR
                and v % 100 >= DECREF_DELTA_MIN_MINOR)

    def peer_speaks_direct_actor(self) -> bool:
        """Whether the peer speaks the r18 direct actor call plane
        (MINOR >= 8): answers ACTOR_RESOLVE, hosts ACTOR_TASK_DIRECT,
        applies ACTOR_INFLIGHT_DELTA. Unknown (0) counts as NO — an
        old peer drops the unknown types without replying and the
        caller's future would burn its stall budget."""
        v = self.peer_wire_version
        return (v // 100 == WIRE_MAJOR
                and v % 100 >= DIRECT_ACTOR_MIN_MINOR)

    def _peer_speaks_trace(self) -> bool:
        """Whether trace context may ride this connection's envelopes.
        Unknown (0: nothing received yet) counts as yes — trace fields
        are SKIPPABLE unknown fields to any proto3 peer, so the worst
        case is a few wasted bytes on the first frames; once an older
        MINOR is observed, the sender stops spending them."""
        v = self.peer_wire_version
        return v == 0 or (v // 100 == WIRE_MAJOR
                          and v % 100 >= TRACE_MIN_MINOR)

    def _emit_locked(self, frames: list[dict]) -> None:
        """Encode + write a group of frames as ONE socket write: a
        single BatchFrame envelope when the peer negotiated batch
        support, else the individual frames concatenated (one syscall
        either way; the latter is valid toward ANY same-major peer).
        With the native engine the write is one scatter-gather
        sendmsg(2) over (length-prefix, header, payload) buffers — GIL
        released, and a Python-plane frame's pickled body goes from
        the pickler to the kernel with zero copies; the fallback joins
        and sendall()s. Caller holds _send_lock."""
        ch = _CHAOS_NET
        if ch is not None and (ch.active or ch.has_parked_out(self)):
            frames = ch.filter_out(self, frames)
            if not frames:
                return               # swallowed/parked: sender unaware
        if not self._peer_speaks_trace():
            # old-wire peer: strip trace context rather than spend
            # bytes it will skip (copies, not mutation — callers may
            # reuse their message dicts)
            frames = [({k: v for k, v in m.items() if k != TRACE_KEY}
                       if TRACE_KEY in m else m) for m in frames]
        eng_on = native.frame_engine_enabled()
        if len(frames) > 1 and self._peer_speaks_batch():
            parts = (encode_batch_parts(frames) if eng_on
                     else [dumps_batch(frames)])
            bufs = [_LEN.pack(sum(map(len, parts))), *parts]
            WIRE_STATS["tx_frames"] += 1
        else:
            bufs = []
            for msg in frames:
                parts = (encode_frame_parts(msg) if eng_on
                         else [dumps(msg)])
                bufs.append(_LEN.pack(sum(map(len, parts))))
                bufs.extend(parts)
            WIRE_STATS["tx_frames"] += len(frames)
        WIRE_STATS["tx_msgs"] += len(frames)
        total = sum(map(len, bufs))
        try:
            # Scatter-gather pays for its per-buffer setup once the
            # emit is a real burst or carries a big payload; a lone
            # small frame is cheaper joined. sendmsg(2) — not a raw-fd
            # C writev — so the fd stays owned by the socket object: a
            # concurrent close() surfaces as EBADF instead of racing
            # fd reuse and writing this frame into an unrelated
            # connection (the reader pins its fd with a dup for the
            # same reason).
            if eng_on and (len(bufs) > 4 or total >= 1 << 16):
                self._sendmsg_all(bufs, total)
            else:
                self._sock.sendall(b"".join(bufs))
        except OSError as e:
            # A failed write may have put a PARTIAL frame on the wire
            # (e.g. the SO_SNDTIMEO budget expired mid-write); the
            # stream is desynced, so the connection must die — a
            # later send would be parsed as garbage by the peer.
            self.close()
            raise ConnectionClosed(str(e)) from e

    def _sendmsg_all(self, bufs: list, total: int) -> None:
        """Write every buffer as few scatter-gather sendmsg(2)
        syscalls as possible (GIL released per call): chunked at 1024
        buffers (IOV_MAX), partial sends resumed with memoryview
        slices — no byte is ever copied into a joined payload. Raises
        OSError like sendall (EAGAIN = SO_SNDTIMEO expired: stream
        desynced, caller kills the connection)."""
        sent_total = 0
        pos = 0
        while sent_total < total:
            chunk = bufs[pos:pos + 1024]
            want = sum(map(len, chunk))
            sent = self._sock.sendmsg(chunk)
            sent_total += sent
            if sent == want:
                pos += len(chunk)
                continue
            # partial send (kernel buffer full): drop fully-written
            # buffers, slice the straddled one, retry from there
            bufs = bufs[pos:]
            pos = 0
            while sent >= len(bufs[0]):
                sent -= len(bufs[0])
                bufs.pop(0)
            if sent:
                bufs[0] = memoryview(bufs[0])[sent:]

    def request(self, msg: dict, timeout: Optional[float] = None) -> dict:
        """Send a request and block for the matching reply."""
        fut = self.request_async(msg)
        return fut.result(timeout)

    def request_async(self, msg: dict) -> "_Future":
        rid = next(self._rid_counter)
        msg["rid"] = rid
        fut = _Future()
        with self._pending_lock:
            self._pending[rid] = fut
        try:
            self.send(msg)
        except ConnectionClosed:
            with self._pending_lock:
                self._pending.pop(rid, None)
            raise
        return fut

    def reply(self, request_msg: dict, **fields) -> None:
        self.send({"type": REPLY, "rid": request_msg["rid"], **fields})

    # ---- receiving ----
    def _dispatch(self, msg: dict) -> None:
        if msg.get("type") == REPLY:
            with self._pending_lock:
                fut = self._pending.pop(msg["rid"], None)
            if fut is not None:
                fut.set(msg)
        else:
            self._handler(self, msg)

    def _read_exact(self, n: int) -> bytes:
        chunks = []
        remaining = n
        while remaining:
            chunk = self._sock.recv(min(remaining, 1 << 20))
            if not chunk:
                raise ConnectionClosed("peer closed")
            chunks.append(chunk)
            remaining -= len(chunk)
        return b"".join(chunks)

    def _handle_frame(self, data: bytes,
                      _chaos_checked: bool = False) -> None:
        """Decode one framed body and dispatch its message(s)."""
        ch = _CHAOS_NET
        if ch is not None and not _chaos_checked and (
                ch.active or ch._parked_in):
            if ch.on_frame_in(self, data):
                return               # parked / dropped / delayed
        msg, version = loads_ex(data)
        self.peer_wire_version = version
        WIRE_STATS["rx_frames"] += 1
        if msg.get("type") == BATCH:
            for sub in msg["frames"]:
                WIRE_STATS["rx_msgs"] += 1
                self._dispatch(sub)
        else:
            WIRE_STATS["rx_msgs"] += 1
            self._dispatch(msg)

    def _native_read_loop(self) -> None:
        """Native pump: blocking read(2) + length-prefix reassembly
        run in C with the GIL RELEASED — the Python loop below holds
        the GIL for every chunk recv and header parse, actively
        starving the handler/sender threads on few-core hosts. One
        pump call returns every complete frame it buffered."""
        from ray_tpu._private.config import CONFIG
        reader = native.FrameReader(self._sock.fileno(),
                                    CONFIG.wire_max_frame_bytes)
        try:
            while True:
                try:
                    frames = reader.pump()
                except native.PumpClosed:
                    raise ConnectionClosed("peer closed") from None
                except native.PumpOversized as e:
                    raise FrameTooLarge(str(e)) from None
                for frame in frames:
                    self._handle_frame(frame)
        finally:
            reader.close()

    def _py_read_loop(self) -> None:
        """Pure-Python fallback: one reassembly bytearray per
        connection (amortized append, no per-chunk bytes concat), with
        the same max-frame-size guard as the native pump."""
        from ray_tpu._private.config import CONFIG
        max_frame = CONFIG.wire_max_frame_bytes
        buf = bytearray()
        while True:
            while len(buf) < _LEN.size:
                chunk = self._sock.recv(1 << 20)
                if not chunk:
                    raise ConnectionClosed("peer closed")
                buf += chunk
            (length,) = _LEN.unpack_from(buf)
            if length > max_frame:
                raise FrameTooLarge(
                    f"frame length prefix {length} exceeds "
                    f"wire_max_frame_bytes ({max_frame})")
            total = _LEN.size + length
            while len(buf) < total:
                chunk = self._sock.recv(1 << 20)
                if not chunk:
                    raise ConnectionClosed("peer closed")
                buf += chunk
            frame = bytes(memoryview(buf)[_LEN.size:total])
            del buf[:total]
            self._handle_frame(frame)

    @staticmethod
    def _log_read_error(name: str, exc: BaseException) -> bool:
        """Shared reader-exit reporting (thread loop + poller): True
        when the exception was recognized and reported."""
        import sys as _sys
        if isinstance(exc, FrameTooLarge):
            _sys.stderr.write(
                f"ray_tpu: killing connection ({name}): {exc}\n")
            return True
        if isinstance(exc, (ConnectionClosed, OSError)):
            return True
        if isinstance(exc, WireVersionError):
            _sys.stderr.write(
                f"ray_tpu: refusing connection ({name}): {exc}\n")
            return True
        return False

    def _read_loop(self) -> None:
        try:
            if self._server and not self._check_auth():
                return
            if native.frame_engine_enabled():
                self._native_read_loop()
            else:
                self._py_read_loop()
        except Exception as e:
            if not self._log_read_error(self.name, e):
                import traceback
                traceback.print_exc()   # handler bug; don't kill silently
        finally:
            self._finish_read()

    def _finish_read(self) -> None:
        """Reader-exit finalization (thread loop finally / poller
        drop): the stream is dead — release fds, fail outstanding
        request futures, fire on_close. Idempotent: the poller and a
        racing close() may both arrive here."""
        with self._finish_lock:
            if self._finished:
                return
            self._finished = True
        self.close()     # reader exit = stream dead; release the fd
        if self._nb_reader is not None:
            self._nb_reader.close()
        if self._pump_sock is not None:
            try:
                self._pump_sock.close()
            except OSError:
                pass
        self._closed.set()
        with self._pending_lock:
            pending, self._pending = self._pending, {}
        for fut in pending.values():
            fut.set_error(ConnectionClosed("connection lost"))
        if self._on_close is not None:
            try:
                self._on_close(self)
            except Exception:
                pass

    # ---- poller-driven receiving (r10) ----
    def _attach_pump(self, use_native: bool) -> int:
        """Create this connection's non-blocking pump state and return
        the fd the poller should watch. Both engines read a DUP of the
        socket fd: the dup pins the open file description, so a
        concurrent Connection.close() (shutdown + close of the
        original) surfaces as EOF on the watched fd instead of racing
        fd reuse; the dup is closed in _finish_read."""
        from ray_tpu._private.config import CONFIG
        if use_native:
            self._nb_reader = native.FrameReader(
                self._sock.fileno(), CONFIG.wire_max_frame_bytes)
            return self._nb_reader.fd
        self._pump_sock = socket.socket(
            fileno=os.dup(self._sock.fileno()))
        self._pump_buf = bytearray()
        return self._pump_sock.fileno()

    def _poll_pump(self) -> list[bytes]:
        """Drain readable bytes (never blocking) and return the
        complete frame bodies buffered so far; [] when no complete
        frame is ready yet. Raises ConnectionClosed / FrameTooLarge
        exactly like the blocking read loops."""
        if self._nb_reader is not None:
            try:
                return self._nb_reader.pump_nb()
            except native.PumpClosed:
                raise ConnectionClosed("peer closed") from None
            except native.PumpOversized as e:
                raise FrameTooLarge(str(e)) from None
        from ray_tpu._private.config import CONFIG
        max_frame = CONFIG.wire_max_frame_bytes
        buf = self._pump_buf
        while not self._pump_eof:
            # mirror the C pump: stop reading the moment a complete
            # frame is buffered (the level-triggered poller re-reports
            # the fd while kernel bytes remain)
            if len(buf) >= _LEN.size:
                (length,) = _LEN.unpack_from(buf)
                if length > max_frame:
                    raise FrameTooLarge(
                        f"frame length prefix {length} exceeds "
                        f"wire_max_frame_bytes ({max_frame})")
                if len(buf) >= _LEN.size + length:
                    break
            try:
                chunk = self._pump_sock.recv(1 << 20,
                                             socket.MSG_DONTWAIT)
            except BlockingIOError:
                break
            except OSError as e:
                raise ConnectionClosed(str(e)) from e
            if not chunk:
                self._pump_eof = True
                break
            buf += chunk
        frames = []
        while len(buf) >= _LEN.size:
            (length,) = _LEN.unpack_from(buf)
            if length > max_frame:
                if frames:
                    break      # dispatch what's whole; next pass dies
                raise FrameTooLarge(
                    f"frame length prefix {length} exceeds "
                    f"wire_max_frame_bytes ({max_frame})")
            total = _LEN.size + length
            if len(buf) < total:
                break
            frames.append(bytes(memoryview(buf)[_LEN.size:total]))
            del buf[:total]
        if not frames and self._pump_eof:
            raise ConnectionClosed("peer closed")
        return frames

    def _chaos_flush(self) -> None:
        """Emit frames a healed chaos partition parked for this
        connection (filter_out prepends them to an empty write)."""
        try:
            with self._send_lock:
                self._emit_locked([])
        except ConnectionClosed:
            pass

    @property
    def closed(self) -> bool:
        return self._closed.is_set()

    def close(self) -> None:
        ch = _CHAOS_NET
        if ch is not None and ch.active and ch.defer_close(self):
            return                  # partitioned link: no FIN either
        self._closed.set()
        self._lazy_wake.set()       # release the coalescing flusher
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass


class FlushLoop:
    """Shared collect-then-flush pacer for message-level batching
    buffers (r10: the head-side lease buffer and the agent-side
    completion buffer) — the same window shape as the wire coalescer's
    lazy-queue flusher, factored out so the two sites cannot drift.

    wake() lazily starts a daemon thread, opens a delay_ms-wide
    window, then calls flush_fn(); callers flush inline themselves
    when a count threshold hits. stop() is race-free by construction:
    the dead flag is set BEFORE the event, and the loop re-checks it
    after every wait/sleep, so a stopped owner can never strand the
    thread in wait() forever."""

    def __init__(self, flush_fn: Callable[[], None],
                 delay_ms_fn: Callable[[], float], name: str):
        self._flush = flush_fn
        self._delay_ms = delay_ms_fn
        self._name = name
        self._wake_ev = threading.Event()
        self._dead = False
        self._thread: Optional[threading.Thread] = None
        self._spawn_lock = threading.Lock()

    def wake(self) -> None:
        if self._dead:
            return
        if self._thread is None:
            with self._spawn_lock:
                if self._thread is None and not self._dead:
                    self._thread = threading.Thread(
                        target=self._loop, name=self._name, daemon=True)
                    self._thread.start()
        self._wake_ev.set()

    def stop(self) -> None:
        self._dead = True           # BEFORE the wake: loop must see it
        self._wake_ev.set()

    def _loop(self) -> None:
        while True:
            self._wake_ev.wait()
            if self._dead:
                return
            delay = max(0.0, self._delay_ms() / 1000.0)
            if delay:
                time.sleep(delay)
            self._wake_ev.clear()
            if self._dead:
                return
            try:
                self._flush()
            except Exception:
                pass        # a failed flush must not kill the pacer
                            # (send paths already contain their errors)


class Poller:
    """Process-level read event loop (r10): ONE thread drives the read
    side of every registered connection, replacing thread-per-
    connection reads on the head and agents (reference raylet/GCS run
    their RPC stacks on shared asio event loops the same way).

    Engine: the native epoll API (``rtpu_poller_*`` in core.c —
    epoll_wait blocks with the GIL released, level-triggered, each
    ready fd drained through its connection's C reassembly buffer via
    the MSG_DONTWAIT pump) when the frame engine is on; a
    ``select.select`` Python fallback otherwise (RAY_TPU_DISABLE_NATIVE
    / RAY_TPU_WIRE_NATIVE=0). RAY_TPU_EPOLL=0 disables the loop
    entirely and every connection keeps its own reader thread.

    Liveness rules baked in here:
    - handlers run on the loop thread, so anything that might block on
      another poller-served connection's REPLY must not run here —
      connection teardown (whose on_close callbacks issue blocking
      bundle/cancel RPCs during node death), the cancel_task state op,
      and the lease-revoke hand-back are all dispatched to throwaway
      threads;
    - a connection that dies only kills itself: handler bugs and
      corrupt streams are contained exactly like the per-thread loop.

    Known tradeoff: handlers' plain SENDS (replies, forwarded events)
    still run on the loop thread, so a peer that stops draining its
    socket can stall the whole process's read plane for up to the
    send budget (SO_SNDTIMEO, 30s) instead of one connection's reader
    as under thread-per-connection. The budget bounds the stall and
    then kills the wedged connection; deployments that cannot accept
    it set RAY_TPU_EPOLL=0. Moving the send plane behind per-
    connection outbound queues is the designed escape hatch if this
    ever bites in practice.
    """

    def __init__(self):
        self._use_native = native.frame_engine_enabled()
        self._conns: dict[int, Connection] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._wake_r, self._wake_w = os.pipe()
        self._ep = None
        if self._use_native:
            self._ep = native.EpollPoller()
            self._ep.add(self._wake_r)
        self._thread = threading.Thread(
            target=self._loop, name="ray-tpu-poller", daemon=True)
        self._thread.start()

    @property
    def alive(self) -> bool:
        return not self._stop.is_set()

    @property
    def engine(self) -> str:
        return "epoll" if self._use_native else "select"

    def register(self, conn: Connection) -> None:
        """Attach a connection's read side to the loop. Falls back to
        the connection's own reader thread on any setup failure (e.g.
        the select() fd limit)."""
        try:
            fd = conn._attach_pump(self._use_native)
            if self._ep is None and fd >= 1024:
                # select() caps at FD_SETSIZE; a bigger fd would make
                # every select call raise. This connection reads on
                # its own thread instead (pump state is closed by
                # _finish_read there).
                raise ValueError("fd exceeds select() FD_SETSIZE")
            # epoll add BEFORE the _conns insert: if the kernel
            # refuses the watch, the thread fallback below must not
            # leave a stale fd->conn mapping behind (a later reuse of
            # that fd number would alias an unrelated connection)
            if self._ep is not None:
                self._ep.add(fd)
            with self._lock:
                self._conns[fd] = conn
            if self._ep is None:
                self._wake()
        except (OSError, ValueError):
            conn._poller = None
            conn._reader.start()

    def _wake(self) -> None:
        try:
            os.write(self._wake_w, b"x")
        except OSError:
            pass

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                if self._ep is not None:
                    ready = self._ep.wait(500)
                else:
                    with self._lock:
                        fds = list(self._conns)
                    fds.append(self._wake_r)
                    try:
                        ready, _, _ = _select.select(fds, [], [], 0.5)
                    except (OSError, ValueError):
                        # a fd closed between snapshot and select:
                        # prune dead entries and retry
                        self._prune()
                        continue
            except OSError:
                if self._stop.is_set():
                    return
                time.sleep(0.05)
                continue
            t0 = time.monotonic_ns() if ready else 0
            serviced = False
            for fd in ready:
                if fd == self._wake_r:
                    try:
                        os.read(self._wake_r, 4096)
                    except OSError:
                        pass
                    continue
                with self._lock:
                    conn = self._conns.get(fd)
                if conn is not None:
                    serviced = True
                    self._service(fd, conn)
            if serviced:
                dt = time.monotonic_ns() - t0
                POLLER_STATS["passes"] += 1
                POLLER_STATS["busy_ns"] += dt
                if dt > POLLER_STATS["max_pass_ns"]:
                    POLLER_STATS["max_pass_ns"] = dt

    def _prune(self) -> None:
        """Drop select-fallback entries whose fd died under us."""
        with self._lock:
            items = list(self._conns.items())
        for fd, conn in items:
            try:
                os.fstat(fd)
            except OSError:
                self._drop(fd, conn)

    def _service(self, fd: int, conn: Connection) -> None:
        try:
            frames = conn._poll_pump()
            if frames:
                POLLER_STATS["frames"] += len(frames)
                POLLER_STATS["bytes"] += sum(map(len, frames))
            for frame in frames:
                conn._handle_frame(frame)
        except Exception as e:
            if not Connection._log_read_error(conn.name, e):
                import traceback
                traceback.print_exc()   # handler bug: that conn dies
            self._drop(fd, conn)

    def _drop(self, fd: int, conn: Connection) -> None:
        with self._lock:
            self._conns.pop(fd, None)
        if self._ep is not None:
            try:
                self._ep.remove(fd)
            except OSError:
                pass
        # Teardown OFF the loop thread: on_close callbacks may issue
        # blocking RPCs whose replies arrive over OTHER poller-served
        # connections (node-death -> bundle re-reserve), which would
        # deadlock the loop against itself.
        threading.Thread(target=conn._finish_read,
                         name=f"ray-tpu-conn-close-{conn.name}",
                         daemon=True).start()

    def close(self) -> None:
        """Stop the loop and tear down every still-registered
        connection (their futures must error, not hang)."""
        if self._stop.is_set():
            return
        self._stop.set()
        self._wake()
        if self._thread is not threading.current_thread():
            # an agent's NODE_SHUTDOWN handler runs ON the loop thread
            # (shutdown -> poller.close); joining ourselves raises and
            # the exception used to abort the CALLER's remaining
            # teardown steps (store shutdown, shm sweep) — the loop
            # exits on the stop flag either way
            self._thread.join(timeout=5.0)
        with self._lock:
            conns, self._conns = dict(self._conns), {}
        for fd, conn in conns.items():
            if self._ep is not None:
                try:
                    self._ep.remove(fd)
                except OSError:
                    pass
            threading.Thread(target=conn._finish_read,
                             name=f"ray-tpu-conn-close-{conn.name}",
                             daemon=True).start()
        if self._ep is not None:
            self._ep.close()
        for fd in (self._wake_r, self._wake_w):
            try:
                os.close(fd)
            except OSError:
                pass

    @property
    def num_connections(self) -> int:
        with self._lock:
            return len(self._conns)


def make_poller() -> Optional[Poller]:
    """A process Poller when the epoll loop is enabled (RAY_TPU_EPOLL,
    default on), else None — callers pass the result straight to
    Connection/connect, so EPOLL=0 restores thread-per-connection
    reads everywhere."""
    from ray_tpu._private.config import CONFIG
    if not CONFIG.epoll:
        return None
    try:
        return Poller()
    except OSError:
        return None


class _Future:
    """Minimal thread-safe future for reply correlation."""

    def __init__(self):
        self._event = threading.Event()
        self._value: Any = None
        self._error: Optional[BaseException] = None
        self._callbacks: list[Callable[["_Future"], None]] = []
        self._cb_lock = threading.Lock()

    def add_done_callback(self, fn: Callable[["_Future"], None]) -> None:
        """Run `fn(self)` when the reply lands (on the reader thread) —
        relays pipe replies onward without parking a thread. Runs
        immediately if already done."""
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def _fire(self) -> None:
        with self._cb_lock:
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:
            try:
                fn(self)
            except Exception:
                pass

    def set(self, value: Any) -> None:
        self._value = value
        self._event.set()
        self._fire()

    def set_error(self, err: BaseException) -> None:
        self._error = err
        self._event.set()
        self._fire()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> Any:
        if not self._event.wait(timeout):
            raise TimeoutError("rpc timed out")
        if self._error is not None:
            raise self._error
        return self._value


def connect(addr: tuple[str, int],
            handler: Callable[[Connection, dict], None],
            on_close: Optional[Callable[[Connection], None]] = None,
            name: str = "",
            poller: Optional[Poller] = None) -> Connection:
    sock = socket.create_connection(addr)
    conn = Connection(sock, handler, on_close, name=name, poller=poller)
    conn.send_auth()             # no-op unless RAY_TPU_AUTH_TOKEN is set
    conn.start()
    return conn
