"""Integration shims: ActorPool, Queue, state API (reference P17/P21)."""
import numpy as np
import pytest

import ray_tpu
from ray_tpu.util.actor_pool import ActorPool
from ray_tpu.util.queue import Empty, Full, Queue


def _make_worker():
    @ray_tpu.remote
    class PoolWorker:
        def __init__(self, factor):
            self.factor = factor

        def ping(self):
            return "pong"

        def mul(self, x):
            return x * self.factor

        def slow_mul(self, x):
            import time
            time.sleep(0.05 * (x % 3))
            return x * self.factor
    return PoolWorker


def test_actor_pool_ordered_map(ray_cluster):
    W = _make_worker()
    pool = ActorPool([W.remote(10) for _ in range(3)])
    out = list(pool.map(lambda a, v: a.mul.remote(v), range(8)))
    assert out == [v * 10 for v in range(8)]       # submission order
    assert pool.num_idle == 3


def test_actor_pool_unordered_and_backpressure(ray_cluster):
    W = _make_worker()
    pool = ActorPool([W.remote(2) for _ in range(2)])
    # 6 submissions over 2 actors: 4 queue host-side
    out = sorted(pool.map_unordered(
        lambda a, v: a.slow_mul.remote(v), range(6)))
    assert out == [v * 2 for v in range(6)]
    assert pool.num_pending == 0


def test_actor_pool_submit_get_next(ray_cluster):
    W = _make_worker()
    pool = ActorPool([W.remote(1)])
    pool.submit(lambda a, v: a.mul.remote(v), 7)
    pool.submit(lambda a, v: a.mul.remote(v), 8)   # queued (1 actor)
    assert pool.has_next()
    assert pool.get_next() == 7
    assert pool.get_next() == 8
    assert not pool.has_next()
    with pytest.raises(StopIteration):
        pool.get_next()


def test_queue_roundtrip_cross_process(ray_cluster):
    q = Queue(maxsize=4)
    q.put({"a": 1})
    q.put(np.arange(3))

    @ray_tpu.remote
    def consume(q):
        item1 = q.get(timeout=10)
        item2 = q.get(timeout=10)
        q.put("reply")
        return item1["a"], int(item2.sum())

    a, s = ray_tpu.get(consume.remote(q))
    assert (a, s) == (1, 3)
    assert q.get(timeout=10) == "reply"
    q.shutdown()


def test_queue_full_empty_semantics(ray_cluster):
    q = Queue(maxsize=1)
    q.put(1)
    with pytest.raises(Full):
        q.put(2, block=False)
    assert q.full()
    assert q.get() == 1
    with pytest.raises(Empty):
        q.get_nowait()
    assert q.empty()
    q.put(1)
    assert q.get_nowait_batch(5) == [1]
    q.shutdown()


def test_state_api_lists(ray_cluster):
    from ray_tpu.util import state

    @ray_tpu.remote
    def touch():
        return 1

    ray_tpu.get(touch.remote())
    tasks = state.list_tasks()
    assert any(e["state"] == "FINISHED" for e in tasks)
    assert isinstance(state.summarize_tasks(), dict)
    nodes = state.list_nodes()
    assert nodes and nodes[0]["alive"]
    assert state.cluster_resources().get("CPU", 0) > 0
    assert "bytes" in state.object_store_stats()
    workers = state.list_workers()
    assert workers and all(w["worker_id"] for w in workers)
    busy = state.list_workers(filters=[("state", "!=", "missing")])
    assert len(busy) == len(workers)
    assert state.usage_stats()["workers"] == len(workers)


def test_worker_side_task_events_and_host_stats(ray_cluster):
    """Workers buffer EXEC_* events locally and flush them batched to
    the head (reference task_event_buffer.cc); node listings carry the
    per-node reporter sample from heartbeats."""
    import time as _t

    from ray_tpu.util import state

    @ray_tpu.remote
    def work():
        _t.sleep(0.05)
        return 1

    ray_tpu.get([work.remote() for _ in range(3)])
    # flush interval is 2s; poll until the batch lands
    deadline = _t.time() + 10
    evs = []
    while _t.time() < deadline:
        # task name is the qualname; the whole of it, since the runtime
        # is shared and another file's `work` (an instant one, in
        # tests/test_metrics_config.py) may have run on it before
        evs = [e for e in state.list_tasks()
               if e["state"].startswith("EXEC_")
               and e.get("name", "").endswith(
                   "test_worker_side_task_events_and_host_stats"
                   ".<locals>.work")]
        if sum(e["state"] == "EXEC_FINISHED" for e in evs) >= 3:
            break
        _t.sleep(0.25)
    finished = [e for e in evs if e["state"] == "EXEC_FINISHED"]
    assert len(finished) >= 3
    assert all(e["duration_s"] >= 0.05 for e in finished)
    assert all(e["worker_id"] for e in finished)

    nodes = state.list_nodes()
    hs = nodes[0]["host_stats"]
    assert hs["mem_total_mb"] > 0 and hs["num_cpus"] >= 1
    assert "workers_rss_mb" in hs
