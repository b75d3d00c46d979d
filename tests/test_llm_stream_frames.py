"""The token stream's wire: everything one engine step owes one connection
leaves in one `llm_tok` frame, a record a request (PR 59). A fake
connection and a fake sink stand for the socket and the consumer; one test
sends the frames down a real loopback connection. CPU, no engine.
"""
import importlib.util
import os
import queue
import sys
import threading
import time

import pytest

from ray_tpu.serve.llm.stream import (STREAM_STATS, StreamClient,
                                      TokenStreamServer)

# what `router.py`'s `_pump` and the benchmark's `Collector.put` may read
SINK_KEYS = {"type", "req", "inc", "attempt", "base", "toks", "done",
             "reason", "err", "unknown"}


class Conn:
    """What the server needs of a `protocol.Connection`."""

    def __init__(self):
        self.frames = []

    def send(self, frame):
        self.frames.append(frame)

    def records(self):
        return [(r["req"], r["base"], list(r["toks"]), r["done"])
                for f in self.frames for r in f["recs"]]


class Sink:
    def __init__(self):
        self.got = []

    def put(self, msg):
        self.got.append(msg)


class Served:
    """A `TokenStreamServer` over buffers the test fills, as the engine's
    `_buf` / `_backlog` are."""

    def __init__(self):
        self.lock = threading.Lock()
        self.buf = {}                       # rid -> tokens emitted so far
        self.server = TokenStreamServer("inc0", self._backlog, self.lock)

    def _backlog(self, rid, cursor):
        if rid not in self.buf:
            return None
        return {"rid": rid, "attempt": 0, "base": cursor,
                "toks": list(self.buf[rid][cursor:]), "done": False,
                "reason": None, "err": None}

    def subscribe(self, conn, rid, cursor=0):
        self.buf.setdefault(rid, [])
        self.server._handle(conn, {"type": "llm_sub", "req": rid,
                                   "cursor": cursor})

    def step(self, *moves):
        """One engine step: (rid, token or None, done) a move, ingested
        and published under the engine's lock."""
        events = []
        with self.lock:
            for rid, token, done in moves:
                toks = self.buf.setdefault(rid, [])
                events.append({"rid": rid, "token": token,
                               "seq": len(toks), "first": not toks,
                               "done": done,
                               "reason": "stop" if done else None,
                               "attempt": 0})
                if token is not None:
                    toks.append(token)
            self.server.publish(events)


@pytest.fixture
def served():
    s = Served()
    yield s
    s.server.close()


@pytest.mark.parametrize("lanes", [1, 8, 32, 64])
def test_a_step_is_one_frame_with_a_record_a_request(served, lanes):
    conn = Conn()
    rids = [f"r{i}" for i in range(lanes)]
    for rid in rids:
        served.subscribe(conn, rid)
    assert conn.frames == []                # nothing to replay yet
    f0, r0 = STREAM_STATS["frames_out"], STREAM_STATS["records_out"]
    served.step(*[(rid, 100 + i, False) for i, rid in enumerate(rids)])
    assert len(conn.frames) == 1
    frame = conn.frames[0]
    assert set(frame) == {"type", "inc", "recs"}
    assert (frame["type"], frame["inc"]) == ("llm_tok", "inc0")
    assert conn.records() == [(rid, 0, [100 + i], False)
                              for i, rid in enumerate(rids)]
    assert all(set(r) == SINK_KEYS - {"type", "inc", "unknown"}
               for r in frame["recs"])
    assert STREAM_STATS["frames_out"] - f0 == 1
    assert STREAM_STATS["records_out"] - r0 == lanes


def test_two_connections_get_a_frame_each_with_their_own_records(served):
    a, b = Conn(), Conn()
    for rid in ("a0", "a1", "a2"):
        served.subscribe(a, rid)
    for rid in ("b0", "b1"):
        served.subscribe(b, rid)
    served.subscribe(a, "both")
    served.subscribe(b, "both")
    served.step(("a0", 1, False), ("b0", 2, False), ("a1", 3, False),
                ("both", 4, False), ("b1", 5, False), ("a2", 6, False),
                ("nobody", 7, False))
    assert len(a.frames) == len(b.frames) == 1
    assert sorted(r[0] for r in a.records()) == ["a0", "a1", "a2", "both"]
    assert sorted(r[0] for r in b.records()) == ["b0", "b1", "both"]


def test_every_publish_sends_what_it_owes_and_parks_nothing(served):
    """No record waits for a later step, a timer or a fuller frame."""
    conn = Conn()
    served.subscribe(conn, "x")
    served.subscribe(conn, "y")
    for n in range(5):
        served.step(("x", n, False))
        assert len(conn.frames) == n + 1
        assert conn.frames[-1]["recs"][0]["toks"] == [n]
    served.step(("y", 9, False), ("x", 5, False))
    assert [(r[0], r[1], r[2]) for r in conn.records()][-2:] == [
        ("y", 0, [9]), ("x", 5, [5])]
    before = len(conn.frames)
    served.step(("nobody", 1, False))       # owed to no one: no frame
    with served.lock:
        served.server.publish([])
    assert len(conn.frames) == before


def test_replay_overlap_is_trimmed_inside_the_frame(served):
    """A subscriber whose replay ran to position 3 gets the part of a
    record past it; a record it has whole is left out unless it ends the
    request; its neighbours in the frame are untouched."""
    conn = Conn()
    for rid in ("part", "whole", "ends"):
        served.buf[rid] = [10, 11, 12]
        served.subscribe(conn, rid)          # replayed to 3
    served.subscribe(conn, "fresh")
    assert conn.records() == [("part", 0, [10, 11, 12], False),
                              ("whole", 0, [10, 11, 12], False),
                              ("ends", 0, [10, 11, 12], False)]
    conn.frames.clear()

    def ev(rid, token, seq, done=False):
        return {"rid": rid, "token": token, "seq": seq, "first": False,
                "done": done, "reason": "stop" if done else None,
                "attempt": 0}

    with served.lock:           # records that start under the cursors
        served.server.publish([
            ev("part", 12, 2), ev("part", 13, 3), ev("whole", 12, 2),
            ev("ends", 12, 2, done=True), ev("fresh", 20, 0)])
    assert len(conn.frames) == 1
    assert conn.records() == [("part", 3, [13], False),
                              ("ends", 3, [], True),
                              ("fresh", 0, [20], False)]
    conn.frames.clear()
    with served.lock:           # the cursors moved with what was sent
        served.server.publish([ev("part", 14, 4), ev("whole", 13, 3)])
    assert conn.records() == [("part", 4, [14], False),
                              ("whole", 3, [13], False)]


def test_a_done_record_is_sent_once_and_drops_the_subscribers(served):
    a, b = Conn(), Conn()
    served.subscribe(a, "r")
    served.subscribe(b, "r")
    served.subscribe(a, "other")
    served.step(("r", 5, False), ("other", 6, False))
    served.step(("r", 7, True), ("other", 8, False))
    assert a.records()[-2:] == [("r", 1, [7], True), ("other", 1, [8], False)]
    assert b.records() == [("r", 0, [5], False), ("r", 1, [7], True)]
    assert "r" not in served.server._subs
    assert "other" in served.server._subs
    served.step(("r", 9, False), ("other", 10, False))
    assert sum(r[3] for r in a.records() + b.records()) == 2
    assert a.records()[-1] == ("other", 2, [10], False)
    assert len(b.frames) == 2


def test_subscribe_answers_in_the_same_form(served):
    conn = Conn()
    served.buf["known"] = [1, 2]
    served.subscribe(conn, "known", cursor=1)
    served.server._handle(conn, {"type": "llm_sub", "req": "lost",
                                 "cursor": 4})
    assert [set(f) for f in conn.frames] == [{"type", "inc", "recs"}] * 2
    replay, unknown = (f["recs"] for f in conn.frames)
    assert [(r["req"], r["base"], r["toks"]) for r in replay] == [
        ("known", 1, [2])]
    assert unknown == [{"req": "lost", "unknown": True, "attempt": -1,
                        "base": 4, "toks": [], "done": True,
                        "reason": None, "err": "unknown_rid"}]
    assert "lost" not in served.server._subs


def _rec(rid, attempt=0, base=0, toks=(1,), done=False):
    return {"req": rid, "attempt": attempt, "base": base,
            "toks": list(toks), "done": done, "reason": None, "err": None}


def _routed(*rids, inc="inc0", attempt=0):
    client, sinks = StreamClient(), {}
    for rid in rids:
        sinks[rid] = Sink()
        client._routes[rid] = (sinks[rid], inc, attempt, ("h", 1))
    return client, sinks


def _stats():
    return dict(STREAM_STATS)


@pytest.mark.parametrize("stale", ["first", "middle", "last"])
def test_client_drops_a_stale_record_and_delivers_its_neighbours(stale):
    client, sinks = _routed("a", "b", "c")
    recs = [_rec("a", toks=[1, 2]), _rec("b"), _rec("c", toks=[3])]
    where = {"first": 0, "middle": 1, "last": 2}[stale]
    recs[where]["attempt"] = 7               # a superseded attempt's
    s0 = _stats()
    client._on_msg(None, {"type": "llm_tok", "inc": "inc0", "recs": recs})
    good = [r["req"] for i, r in enumerate(recs) if i != where]
    assert [rid for rid in "abc" if sinks[rid].got] == good
    assert STREAM_STATS["zombie_dropped"] - s0["zombie_dropped"] == 1
    assert STREAM_STATS["frames_in"] - s0["frames_in"] == 1
    assert STREAM_STATS["tokens_in"] - s0["tokens_in"] == sum(
        len(r["toks"]) for i, r in enumerate(recs) if i != where)
    for rid in good:
        (msg,) = sinks[rid].got
        assert set(msg) == SINK_KEYS
        assert (msg["type"], msg["inc"], msg["req"], msg["unknown"]) == (
            "llm_tok", "inc0", rid, False)


def test_client_fences_a_whole_frame_of_a_stale_incarnation():
    client, sinks = _routed("a", "b")
    z0 = STREAM_STATS["zombie_dropped"]
    client._on_msg(None, {"type": "llm_tok", "inc": "zombie",
                          "recs": [_rec("a"), _rec("b")]})
    assert not sinks["a"].got and not sinks["b"].got
    assert STREAM_STATS["zombie_dropped"] - z0 == 2


def test_client_passes_unknown_and_skips_unrouted():
    client, sinks = _routed("a", attempt=3)
    client._on_msg(None, {"type": "llm_tok", "inc": "any", "recs": [
        _rec("gone"), {**_rec("a", attempt=-1, toks=(), done=True),
                       "unknown": True, "err": "unknown_rid"}]})
    (msg,) = sinks["a"].got
    assert set(msg) == SINK_KEYS
    assert msg["unknown"] is True and msg["done"] and msg["toks"] == []
    client._on_msg(None, {"type": "llm_other", "recs": [_rec("a")]})
    assert len(sinks["a"].got) == 1


def test_frames_cross_a_real_connection_in_order(served):
    """Server and client over loopback: 16 requests of one consumer share
    one connection, every step is one frame, every sink reads its own
    tokens in order and one terminal message."""
    rids = [f"q{i}" for i in range(16)]
    client, sinks = StreamClient(), {rid: queue.Queue() for rid in rids}
    served.buf.update({rid: [0] for rid in rids})   # one token before
    f0, r0 = _stats()["frames_out"], _stats()["records_out"]
    in0 = _stats()["frames_in"]
    for rid in rids:
        assert client.subscribe(served.server.addr, rid, "inc0", 0, 0,
                                sinks[rid])
    for rid in rids:                    # the replays: registered after
        assert sinks[rid].get(timeout=10)["toks"] == [0]
    steps = 5
    for n in range(1, steps + 1):
        served.step(*[(rid, n, n == steps) for rid in rids])
    for rid in rids:
        msgs = [sinks[rid].get(timeout=10) for _ in range(steps)]
        assert [m["toks"] for m in msgs] == [[n] for n in range(1, steps + 1)]
        assert [m["base"] for m in msgs] == list(range(1, steps + 1))
        assert [m["done"] for m in msgs] == [False] * (steps - 1) + [True]
        assert all(set(m) == SINK_KEYS for m in msgs)
        assert sinks[rid].empty()
    assert len(client._conns) == 1
    assert _stats()["frames_out"] - f0 == len(rids) + steps
    assert _stats()["records_out"] - r0 == len(rids) * (1 + steps)
    assert _stats()["frames_in"] - in0 == len(rids) + steps
    for conn in list(client._conns.values()):
        conn.close()


# ------------------------------ the benchmark's reader of the span (PR 59)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)                # the metric imports `benchmarks`
RECORDED = os.path.join(ROOT, "benchmarks", "tests", "data",
                        "tiny_engine_v5e.xplane.pb")


class WakingConn(Conn):
    """A connection whose write wakes a reader thread of this process, as a
    socket's does: the reader then needs the interpreter to read."""

    def __init__(self):
        super().__init__()
        self.written = threading.Semaphore(0)
        self.read = 0
        self.stop = False
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def send(self, frame):
        super().send(frame)
        self.written.release()

    def _read(self):
        while True:
            self.written.acquire()
            if self.stop:
                return
            self.read += 1


def test_a_reader_of_this_process_reads_a_step_before_publish_returns(
        served):
    """The write alone hands the interpreter to nobody: the step thread
    holds it until it next blocks, wherever that is. `publish` gives it
    up once its frames are written, so an in-process reader's turn comes
    before the engine's lock is released to whoever waits for it."""
    conn = WakingConn()
    served.subscribe(conn, "r0")
    steps, in_time = 40, 0
    try:
        for n in range(steps):
            served.step(("r0", 100 + n, False))
            in_time += conn.read == n + 1   # no release since publish
            while conn.read < n + 1:        # let a late reader catch up
                time.sleep(0.001)
    finally:
        conn.stop = True
        conn.written.release()
    assert len(conn.frames) == steps
    assert in_time > steps // 2, in_time      # 0 without the hand-off


def test_a_publish_that_writes_nothing_does_not_sleep(served, monkeypatch):
    from ray_tpu.serve.llm import stream
    slept = []
    monkeypatch.setattr(stream.time, "sleep", slept.append)
    conn = Conn()
    served.subscribe(conn, "r0")
    served.step(("nobody-listens", 5, False))
    assert conn.frames == [] and slept == []
    served.step(("r0", 6, False))
    assert len(conn.frames) == 1 and slept == [stream.HANDOFF_S]


@pytest.fixture(scope="module")
def publish_ms():
    spec = importlib.util.spec_from_file_location(
        "m_publish_ms", os.path.join(
            ROOT, "benchmarks", "metrics",
            "engine.publish_ms_per_step.batch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_publish_ms_per_step_is_span_time_over_dispatches(publish_ms):
    from benchmarks.harness.spans import Reading
    from benchmarks.harness.xplane import Event
    durs = [0.0004, 0.0003, 0.0005]
    spans = [Event("engine.decode_dispatch", t, 0.001) for t in range(4)]
    spans += [Event("engine.ingest", t + 0.4, 0.3) for t in range(3)]
    spans += [Event("stream.publish", t + 0.5, d,
                    {"frames": 1, "records": 32})
              for t, d in enumerate(durs)]
    assert publish_ms({"_spans": Reading(spans, {}, 0.0)}) == pytest.approx(
        1e3 * sum(durs) / 4)
    # an untraced run, a program without spans, a window with no step
    assert publish_ms({"_spans": None}) is None
    assert publish_ms({"result": {"traced": None}}) is None
    assert publish_ms({"_spans": Reading(spans[4:], {}, 0.0)}) is None
    # a parent whose steps publish under no such span reads 0, not an error
    assert publish_ms({"_spans": Reading(spans[:4], {}, 0.0)}) == 0.0


def test_publish_ms_per_step_on_the_recorded_engine_trace(publish_ms):
    from benchmarks.harness import spans, xplane
    r = spans.read(xplane.load(RECORDED), RECORDED)
    assert len(r.named("engine.decode_dispatch")) == 3
    want = 1e3 * sum(s.dur for s in r.named("stream.publish")) / 3
    assert want > 0
    assert publish_ms({"_spans": r}) == pytest.approx(want)
