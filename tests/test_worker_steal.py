"""UNQUEUE_TASK steal-back protocol on the worker side.

Regression for the ADVICE r5 medium finding: a steal that raced AHEAD
of (or behind) the task's completion must refuse — replying ok after
the task ran left a poisoned tombstone that silently skipped a
lineage-resubmitted task with the same id, hanging its caller's
``get()`` forever. And for PR 47's: the driver sends a task it stole
back to the same worker again and steals it again before the exec
thread has reached the first frame; every stolen frame must be skipped,
or the task runs behind the driver's back, its stray completion and its
blocked gets corrupt the driver's mirror of the FIFO, and a later task
is left queued behind the get that waits for it (the hang of
``tests/test_data_shuffle.py`` under load).
"""
import threading
import time

import cloudpickle
import pytest

from ray_tpu._private import protocol
from ray_tpu._private.specs import TaskSpec
from ray_tpu._private.worker_main import WorkerExecutor


class FakeConn:
    """Captures outbound frames; enough of Connection for the executor."""

    def __init__(self):
        self.sent = []
        self.replies = []
        self.lock = threading.Lock()

    def send(self, msg):
        with self.lock:
            self.sent.append(msg)

    send_lazy = send

    def flush(self):
        pass

    def reply(self, msg, **fields):
        with self.lock:
            self.replies.append(dict(fields))


class FakeCtx:
    worker_id = "w_test"

    def __init__(self, fns):
        self.conn = FakeConn()
        self._fns = {k: cloudpickle.dumps(v) for k, v in fns.items()}

    def get_function(self, func_id):
        return self._fns[func_id]

    def state_op(self, op, **kwargs):
        return None

    def kv_op(self, op, key, value=None, namespace="default", **kw):
        return None


def _wait_for(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def _task_dones(conn):
    with conn.lock:
        return [m for m in conn.sent
                if m.get("type") == protocol.TASK_DONE]


# module-level so cloudpickle saves them by reference (the "worker" is
# this same process); the gate lives in a global, not a closure, because
# an Event holds an unpicklable lock
_GATE = threading.Event()
_STARTED = threading.Event()
_RUNS: list = []


def _fast_fn():
    return 42


def _gate_fn():
    _STARTED.set()
    _GATE.wait(10)


def _counted_fn():
    _RUNS.append(1)


@pytest.fixture
def executor():
    _GATE.clear()
    _STARTED.clear()
    _RUNS.clear()
    ctx = FakeCtx({"f_fast": _fast_fn, "f_gate": _gate_fn,
                   "f_counted": _counted_fn})
    ex = WorkerExecutor(ctx)
    ex._gate = _GATE
    yield ex
    _GATE.set()
    ex.stop_event.set()


def _spec(tid, func="f_fast"):
    return TaskSpec(task_id=tid, func_id=func, return_ids=[tid + "r0"],
                    name=tid)


def test_unqueue_after_completion_refuses_and_leaves_no_tombstone(
        executor):
    conn = executor.ctx.conn
    executor.handle(conn, {"type": protocol.TASK, "spec": _spec("t1")})
    assert _wait_for(lambda: len(_task_dones(conn)) == 1)
    # the steal decision raced behind completion: must refuse
    executor.handle(conn, {"type": protocol.UNQUEUE_TASK,
                           "task_id": "t1", "rid": 1})
    assert conn.replies[-1] == {"ok": False}
    # lineage resubmission reuses the same task id: it must RUN, not be
    # skipped by a stale tombstone
    executor.handle(conn, {"type": protocol.TASK, "spec": _spec("t1")})
    assert _wait_for(lambda: len(_task_dones(conn)) == 2), \
        "resubmitted task was silently skipped"


def test_unqueue_of_genuinely_queued_task_succeeds(executor):
    conn = executor.ctx.conn
    # t_block occupies the single exec thread; t2 is queued-not-started
    executor.handle(conn, {"type": protocol.TASK,
                           "spec": _spec("t_block", "f_gate")})
    assert _STARTED.wait(10)
    executor.handle(conn, {"type": protocol.TASK, "spec": _spec("t2")})
    executor.handle(conn, {"type": protocol.UNQUEUE_TASK,
                           "task_id": "t2", "rid": 2})
    assert conn.replies[-1] == {"ok": True}
    executor._gate.set()                      # unblock the exec thread
    assert _wait_for(lambda: len(_task_dones(conn)) == 1)
    # only t_block completed; the stolen t2 never ran, and the steal
    # left nothing behind that would skip the task when it comes again
    assert _task_dones(conn)[0]["task_id"] == "t_block"
    executor.handle(conn, {"type": protocol.TASK, "spec": _spec("t2")})
    assert _wait_for(lambda: len(_task_dones(conn)) == 2)


def test_unqueue_of_started_task_refuses(executor):
    conn = executor.ctx.conn
    executor.handle(conn, {"type": protocol.TASK,
                           "spec": _spec("t_run", "f_gate")})
    assert _STARTED.wait(10)
    executor.handle(conn, {"type": protocol.UNQUEUE_TASK,
                           "task_id": "t_run", "rid": 3})
    assert conn.replies[-1] == {"ok": False}
    executor._gate.set()
    assert _wait_for(lambda: len(_task_dones(conn)) == 1)


@pytest.mark.parametrize("frames, steals", [
    (1, 1), (2, 1), (2, 2), (3, 2), (3, 3), (4, 3)])
def test_every_stolen_frame_of_a_task_is_skipped(executor, frames,
                                                 steals):
    """The driver's side of a steal-back, replayed in the losing order:
    behind a blocked task, one task id arrives `frames` times and is
    stolen `steals` times before the exec thread reaches any of them.
    It runs once for each frame that was not stolen: never behind the
    driver's back."""
    conn = executor.ctx.conn
    executor.handle(conn, {"type": protocol.TASK,
                           "spec": _spec("t_block", "f_gate")})
    assert _STARTED.wait(10)
    for i in range(frames):
        executor.handle(conn, {"type": protocol.TASK,
                               "spec": _spec("t2", "f_counted")})
        if i < steals:
            executor.handle(conn, {"type": protocol.UNQUEUE_TASK,
                                   "task_id": "t2", "rid": 10 + i})
            assert conn.replies[-1] == {"ok": True}
    # the sentinel is the FIFO's last entry: when it is done, every
    # frame ahead of it has run or was skipped
    executor.handle(conn, {"type": protocol.TASK, "spec": _spec("t_end")})
    executor._gate.set()
    assert _wait_for(lambda: any(m["task_id"] == "t_end"
                                 for m in _task_dones(conn)))
    ran = [m["task_id"] for m in _task_dones(conn)]
    assert ran == ["t_block"] + ["t2"] * (frames - steals) + ["t_end"]
    assert len(_RUNS) == frames - steals


@pytest.mark.parametrize("pattern", ["arguments", "nested"])
def test_tasks_stolen_back_and_forth_run_exactly_once(ray_cluster,
                                                      tmp_path, pattern):
    """The same through a live runtime, a few hundred tasks. A consumer
    blocks once for each of its producers, which end one after another,
    with other consumers pipelined behind it: the driver steals those
    back at every block and sends them again at every unblock, to the
    same worker as often as not. `arguments` is the pattern of a shuffle
    (the reducers' arguments are the mappers' outputs, all submitted in
    one burst); `nested` is that of `Dataset.zip` (a task submits its
    producers itself and gets them one by one). Every task leaves one
    line for each time it ran; at the parent most rounds had a task
    that ran twice, and one in twenty hung."""
    import os

    import ray_tpu

    def ran_once(path, name):
        with open(os.path.join(path, name), "a") as f:
            f.write("x\n")

    @ray_tpu.remote(num_cpus=1)
    def producer(path, name, i):
        time.sleep(0.003 * (i + 1))
        ran_once(path, name)
        return i

    @ray_tpu.remote(num_cpus=1)
    def consumer(path, j, *parts):
        ran_once(path, f"c{j}")
        return sum(parts)

    @ray_tpu.remote(num_cpus=1)
    def nesting_consumer(path, j, k):
        ran_once(path, f"c{j}")
        parts = [producer.remote(path, f"p{j}_{i}", i) for i in range(k)]
        return sum(ray_tpu.get(part) for part in parts)

    k, n, rounds = (6, 12, 20) if pattern == "arguments" else (4, 8, 20)
    for r in range(rounds):
        round_dir = tmp_path / f"round{r}"
        round_dir.mkdir()
        path = str(round_dir)
        if pattern == "arguments":
            parts = [producer.remote(path, f"p{i}", i) for i in range(k)]
            outs = [consumer.remote(path, j, *parts) for j in range(n)]
            names = [f"p{i}" for i in range(k)]
        else:
            outs = [nesting_consumer.remote(path, j, k) for j in range(n)]
            names = [f"p{j}_{i}" for j in range(n) for i in range(k)]
        assert ray_tpu.get(outs) == [sum(range(k))] * n
        ran = {f.name: len(f.read_text().splitlines())
               for f in round_dir.iterdir()}
        assert ran == dict.fromkeys(
            names + [f"c{j}" for j in range(n)], 1), f"round {r}"
