"""Core-runtime microbenchmarks: named timed scenarios.

Parity: reference python/ray/_private/ray_perf.py:120-274 (tasks/s,
actor calls/s, put/get ops/s, put GB/s, wait on many refs) — the
scalability-envelope numbers SURVEY.md §4.5(e) requires in-repo.
Run: `python bench_core.py [--json]`; results land in ENVELOPE.md via
tools/update_envelope.py or the --json line.

Numbers are for THIS host (the CI box is 1 CPU core; worker spawns are
~2s each) — they are envelope shapes, not cluster limits.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

import numpy as np

# Repetitions per A/B pair (each rep runs BOTH arms, order
# alternating). 2 is the minimum that gives every arm one first-run
# and one second-run sample.
AB_REPS = max(1, int(os.environ.get("RAY_TPU_BENCH_AB_REPS", "2")))


def timed(fn, n: int, *, unit: str = "ops") -> dict:
    t0 = time.perf_counter()
    fn()
    dt = time.perf_counter() - t0
    return {"n": n, "seconds": round(dt, 4),
            "per_second": round(n / dt, 1), "unit": unit}


def timed_each(fn_once, n: int, *, unit: str = "ops") -> dict:
    """Per-iteration latency capture (r18 satellite): sync round-trip
    scenarios report p50/p99 ms next to the throughput median, so a
    latency regression can't hide behind an aggregate rate."""
    lats = []
    t_all = time.perf_counter()
    for i in range(n):
        t0 = time.perf_counter()
        fn_once(i)
        lats.append(time.perf_counter() - t0)
    dt = time.perf_counter() - t_all
    lats.sort()
    return {"n": n, "seconds": round(dt, 4),
            "per_second": round(n / dt, 1), "unit": unit,
            "p50_ms": round(lats[n // 2] * 1e3, 3),
            "p99_ms": round(lats[min(n - 1, int(n * 0.99))] * 1e3, 3)}


def _ab_pair(results: dict, key_a: str, run_a, key_b: str, run_b,
             reps: int = None) -> tuple[dict, dict]:
    """Order-bias-corrected A/B scenario pair.

    Back-to-back pairs systematically favor the SECOND run (warmed
    page cache, faulted pool pages, a settled box): r11 measured the
    metrics-plane overhead at "-8.0%" purely from running second, and
    a reversed-order control confirmed. So every A/B pair runs
    ``reps`` times with the arm order ALTERNATING (rep 0: A then B,
    rep 1: B then A, ...). Each arm's recorded result is its
    median-throughput run; the ``ab`` block carries every rep's
    per_second tagged by running order plus the per-order medians, so
    a reader can see the order spread instead of trusting one
    ordering. Speedup/overhead figures derive from the arm medians."""
    reps = AB_REPS if reps is None else reps
    runs: dict[str, list] = {key_a: [], key_b: []}
    for rep in range(reps):
        order = ((key_a, run_a), (key_b, run_b))
        if rep % 2:
            order = order[::-1]
        for pos, (key, run) in enumerate(order):
            rec = run()
            rec["_order"] = "first" if pos == 0 else "second"
            runs[key].append(rec)
    for key, recs in runs.items():
        med = statistics.median_low([r["per_second"] for r in recs])
        rec = dict(next(r for r in recs if r["per_second"] == med))
        rec.pop("_order")
        rec["per_second"] = round(statistics.median(
            [r["per_second"] for r in recs]), 3)
        rec["ab"] = {
            "reps": reps,
            "runs": [{"order": r["_order"],
                      "per_second": r["per_second"]} for r in recs],
            "order_medians": {
                o: round(statistics.median(
                    [r["per_second"] for r in recs
                     if r["_order"] == o]), 3)
                for o in ("first", "second")
                if any(r["_order"] == o for r in recs)}}
        results[key] = rec
    return results[key_a], results[key_b]


def _frame_stats(s0: dict, n_tasks: int) -> dict:
    """Head-process socket-frame deltas since snapshot `s0` (a copy of
    protocol.WIRE_STATS), per completed task — the per-event syscall
    cost the frame coalescing attacks."""
    from ray_tpu._private import protocol
    d = {k: protocol.WIRE_STATS[k] - s0[k] for k in s0}
    frames = d["tx_frames"] + d["rx_frames"]
    return {"head_frames": frames,
            "head_msgs": d["tx_msgs"] + d["rx_msgs"],
            "frames_per_task": round(frames / n_tasks, 2)}


def _drain_with_frames(n_tasks: int) -> dict:
    """Fresh runtime under the CURRENT env: drain n nop tasks and
    report frames per completed task plus head-process CPU µs/task
    (process_time covers every thread in the head — the Python/C split
    of the frame engine shows up here, not in wall time)."""
    import ray_tpu
    from ray_tpu._private import protocol
    from ray_tpu._private.config import CONFIG
    CONFIG.reload()
    rt = ray_tpu.init(num_cpus=4)

    @ray_tpu.remote
    def nop():
        return None

    for _ in range(3):
        ray_tpu.get([nop.remote() for _ in range(30)])       # warm pool
    s0 = dict(protocol.WIRE_STATS)
    c0 = time.process_time()
    t0 = time.perf_counter()
    refs = [nop.remote() for _ in range(n_tasks)]
    ray_tpu.get(refs, timeout=300)
    dt = time.perf_counter() - t0
    cpu = time.process_time() - c0
    stats = _frame_stats(s0, n_tasks)
    ray_tpu.shutdown()
    return {"n": n_tasks, "seconds": round(dt, 4),
            "per_second": round(n_tasks / dt, 1), "unit": "tasks",
            "head_cpu_us_per_task": round(cpu / n_tasks * 1e6, 1),
            **stats}


def _delegated_drain(n_tasks: int, delegate: bool) -> dict:
    """Remote-drain A/B (r10): a 0-CPU head routes EVERY task to one
    4-CPU agent subprocess, so the measurement isolates the head<->
    agent control protocol — central per-task dispatch
    (RAY_TPU_DELEGATE=0: NODE_ENQUEUE + dispatch event +
    NODE_TASK_DONE per task) vs delegated bulk leases (lease batches
    out, coalesced done batches back, dispatch events suppressed).
    frames/task counts the HEAD process's socket frames; head CPU is
    the head process's total thread time."""
    import ray_tpu
    from ray_tpu._private import protocol
    from ray_tpu._private.config import CONFIG
    from ray_tpu.cluster_utils import NodeAgentProcess
    os.environ["RAY_TPU_DELEGATE"] = "1" if delegate else "0"
    CONFIG.reload()
    agent = None
    try:
        rt = ray_tpu.init(num_cpus=0)
        agent = NodeAgentProcess(num_cpus=4)   # inherits DELEGATE env
        deadline = time.time() + 60
        while (time.time() < deadline
               and len(rt.cluster.alive_nodes()) < 2):
            time.sleep(0.1)

        @ray_tpu.remote
        def nop():
            return None

        for _ in range(3):
            ray_tpu.get([nop.remote() for _ in range(30)],
                        timeout=120)                     # warm pool
        s0 = dict(protocol.WIRE_STATS)
        c0 = time.process_time()
        t0 = time.perf_counter()
        refs = [nop.remote() for _ in range(n_tasks)]
        ray_tpu.get(refs, timeout=600)
        dt = time.perf_counter() - t0
        cpu = time.process_time() - c0
        stats = _frame_stats(s0, n_tasks)
        handle = next(n.scheduler for n in rt.cluster.alive_nodes()
                      if not n.is_head)
        extra = {}
        if delegate:
            extra = {"lease_batches": handle._leases_sent,
                     "tasks_leased": handle._tasks_leased}
        return {"n": n_tasks, "seconds": round(dt, 4),
                "per_second": round(n_tasks / dt, 1), "unit": "tasks",
                "head_cpu_us_per_task": round(cpu / n_tasks * 1e6, 1),
                **stats, **extra}
    finally:
        if agent is not None:
            agent.terminate()
            agent.wait(10)
        import ray_tpu as _rt
        _rt.shutdown()
        os.environ.pop("RAY_TPU_DELEGATE", None)
        CONFIG.reload()


def _direct_actor_bench(n_calls: int, direct: bool) -> dict:
    """Direct actor call plane A/B (r18): a 0-CPU head, one agent
    hosting the target actor, one agent hosting a WORKER-RESIDENT
    caller — the serving/RL shape where per-request actor-call latency
    binds. Head-routed (RAY_TPU_DIRECT_ACTOR=0) each sync call costs
    four head-relayed hops (SUBMIT_ACTOR_TASK relay in,
    NODE_SEND_ACTOR_TASK out, NODE_TASK_DONE back, GET_OBJECT resolve
    back out). Direct: the caller resolves the endpoint once, streams
    ACTOR_TASK_DIRECT peer-to-peer, and the reply lands inline —
    head_frames_per_call counts the head's actor-plane involvement
    (head-routed sends + head-processed dones + resolves + mirror
    deltas; counters, not timers) and must read ~0 on the direct
    arm."""
    import ray_tpu
    from ray_tpu._private.config import CONFIG
    from ray_tpu.cluster_utils import NodeAgentProcess
    os.environ["RAY_TPU_DIRECT_ACTOR"] = "1" if direct else "0"
    CONFIG.reload()
    agents = []
    try:
        rt = ray_tpu.init(num_cpus=0)
        # custom resources pin target and caller to DIFFERENT agents:
        # a 0-CPU actor would otherwise place on the 0-CPU head and
        # measure the in-process path instead of the wire
        agents = [NodeAgentProcess(num_cpus=4,
                                   resources={"bench_actor": 10.0}),
                  NodeAgentProcess(num_cpus=4,
                                   resources={"bench_caller": 10.0})]
        deadline = time.time() + 60
        while (time.time() < deadline
               and len(rt.cluster.alive_nodes()) < 3):
            time.sleep(0.1)

        @ray_tpu.remote(resources={"bench_actor": 1.0})
        class Ping:
            def ping(self):
                return None

        @ray_tpu.remote(resources={"bench_caller": 1.0})
        class Caller:
            def drive(self, h, n):
                import time as _t
                lats = []
                t_all = _t.perf_counter()
                for _ in range(n):
                    t0 = _t.perf_counter()
                    ray_tpu.get(h.ping.remote())
                    lats.append(_t.perf_counter() - t0)
                dt = _t.perf_counter() - t_all
                lats.sort()
                return dt, lats[n // 2], lats[min(n - 1,
                                                  int(n * 0.99))]

        a = Ping.remote()
        c = Caller.remote()
        ray_tpu.get(a.ping.remote(), timeout=120)        # ALIVE
        ray_tpu.get(c.drive.remote(a, 20), timeout=120)  # warm path
        # steady state: heartbeats have carried the target worker's
        # direct port and the caller's provisional (agent-hosted)
        # endpoint is eligible for its worker-socket upgrade
        time.sleep(1.5)
        ray_tpu.get(c.drive.remote(a, 5), timeout=120)
        keys = ("head_routed_sends", "head_actor_dones", "resolves",
                "delta_frames", "inline_bytes")
        s0 = {k: rt._direct_stats[k] for k in keys}
        direct0 = sum(
            (getattr(n.scheduler, "direct_stats", None)
             or {}).get("served", 0)
            for n in rt.cluster.alive_nodes())
        dt, p50, p99 = ray_tpu.get(c.drive.remote(a, n_calls),
                                   timeout=600)
        d = {k: rt._direct_stats[k] - s0[k] for k in keys}
        time.sleep(1.2)          # host serve counters ride heartbeats
        served = sum(
            (getattr(n.scheduler, "direct_stats", None)
             or {}).get("served", 0)
            for n in rt.cluster.alive_nodes()) - direct0
        return {
            "n": n_calls, "seconds": round(dt, 4),
            "per_second": round(n_calls / dt, 1), "unit": "calls",
            "p50_ms": round(p50 * 1e3, 3),
            "p99_ms": round(p99 * 1e3, 3),
            "head_frames_per_call": round(
                (d["head_routed_sends"] + d["head_actor_dones"]
                 + d["resolves"] + d["delta_frames"]) / n_calls, 3),
            "direct_served": served,
            "inline_reply_bytes": d["inline_bytes"],
        }
    finally:
        for ag in agents:
            ag.terminate()
        for ag in agents:
            ag.wait(10)
        import ray_tpu as _rt
        _rt.shutdown()
        os.environ.pop("RAY_TPU_DIRECT_ACTOR", None)
        CONFIG.reload()


def _rl_bench(direct: bool, n_updates: int = 12) -> dict:
    """Sebulba RL throughput (r20): 4 env-runner actors on one agent
    act against 2 batched inference actors on another while the
    driver learner consumes trajectory rings and publishes versioned
    weights. per_second is aggregate environment steps/s consumed by
    the learner; staleness p50/p95 is the policy-version lag of each
    consumed shard (bounded by the ring depth by construction).

    The A/B arm is the act() path: direct plane (env-runner workers
    submit straight to the inference worker's socket) vs head-routed
    (RAY_TPU_DIRECT_ACTOR=0: every act rides the head tables).
    head_frames_per_call is the r18 actor-plane accounting —
    head-routed sends + head-processed dones + endpoint resolves +
    mirror delta frames, counters not timers — so the object-plane
    weight-publish traffic (put + broadcast fanout) never bills the
    act path; the direct arm must read ~0."""
    import ray_tpu
    from ray_tpu._private.config import CONFIG
    from ray_tpu.cluster_utils import NodeAgentProcess
    os.environ["RAY_TPU_DIRECT_ACTOR"] = "1" if direct else "0"
    CONFIG.reload()
    agents = []
    tr = None
    try:
        rt = ray_tpu.init(num_cpus=0, resources={"head": 4.0})
        from ray_tpu.rllib.sebulba import SebulbaConfig
        agents = [NodeAgentProcess(num_cpus=4,
                                   resources={"rl_infer": 10.0}),
                  NodeAgentProcess(num_cpus=4,
                                   resources={"rl_env": 10.0})]
        deadline = time.time() + 60
        while (time.time() < deadline
               and len(rt.cluster.alive_nodes()) < 3):
            time.sleep(0.1)
        cfg = SebulbaConfig(
            num_env_runners=4, num_inference_actors=2,
            num_envs_per_runner=8, rollout_length=16,
            inference_options={"num_cpus": 0,
                               "resources": {"rl_infer": 1.0},
                               "max_concurrency": 16},
            runner_options={"num_cpus": 0,
                            "resources": {"rl_env": 1.0}},
            seed=0)
        tr = cfg.build()
        # warm: first shards pay env resets, actor spin-up, the
        # env-runner workers' one-time endpoint resolves, and the
        # adaptive mirror window's ramp to its steady-state width
        for _ in range(8):
            tr.learner.update_shard(tr._next_shard())
            tr._publish()
        keys = ("head_routed_sends", "head_actor_dones", "resolves",
                "delta_frames")
        i0 = sum(s["requests"] for s in ray_tpu.get(
            [h.stats.remote() for h in tr._infer]))
        s0 = {k: rt._direct_stats[k] for k in keys}
        staleness = []
        steps = 0
        t0 = time.perf_counter()
        for _ in range(n_updates):
            shard = tr._next_shard()
            m = tr.learner.update_shard(shard)
            staleness.append(m["staleness"])
            steps += int(shard["steps"])
            tr._publish()
        wall = time.perf_counter() - t0
        d = {k: rt._direct_stats[k] - s0[k] for k in keys}
        i1 = sum(s["requests"] for s in ray_tpu.get(
            [h.stats.remote() for h in tr._infer]))
        calls = max(1, i1 - i0)
        head_frames = (d["head_routed_sends"] + d["head_actor_dones"]
                       + d["resolves"] + d["delta_frames"])
        staleness.sort()

        def _pct(q):
            return staleness[min(len(staleness) - 1,
                                 int(len(staleness) * q))]

        return {
            "n": steps, "seconds": round(wall, 4),
            "per_second": round(steps / wall, 1), "unit": "env-steps",
            "updates": n_updates,
            "infer_calls": calls,
            "staleness_p50": _pct(0.50),
            "staleness_p95": _pct(0.95),
            "staleness_max": staleness[-1],
            "seq_gaps": tr.learner.seq_gaps,
            "head_frames_per_call": round(head_frames / calls, 3),
            "head_frame_mix": d,
        }
    finally:
        if tr is not None:
            try:
                tr.stop()
            except BaseException:
                pass
        for ag in agents:
            ag.terminate()
        for ag in agents:
            ag.wait(10)
        import ray_tpu as _rt
        _rt.shutdown()
        os.environ.pop("RAY_TPU_DIRECT_ACTOR", None)
        CONFIG.reload()


def _rl_section(results: dict) -> None:
    """Sebulba act-path A/B (r20). Acceptance: the direct arm's
    head_frames_per_call reads ~0 (<= 0.1) while the head-routed arm
    pays full actor-call frame costs, at no env-steps/s loss."""
    _hd, _dr = _ab_pair(
        results, "rl_sebulba_head",
        lambda: _rl_bench(direct=False),
        "rl_sebulba_direct",
        lambda: _rl_bench(direct=True))
    if _hd["per_second"]:
        _dr["direct_speedup"] = round(
            _dr["per_second"] / _hd["per_second"], 2)


def _codec_bench() -> dict:
    """Codec-only cost: encode+decode µs for the hot frame shapes,
    native engine vs pure-Python protobuf (RAY_TPU_WIRE_NATIVE=0 —
    in-process equivalent of RAY_TPU_DISABLE_NATIVE for the wire
    paths). No runtime, no sockets: isolates the envelope tax the r7
    C codec attacks."""
    import os as _os
    from ray_tpu._private import wire
    from ray_tpu._private.config import CONFIG
    from ray_tpu._private.specs import TaskSpec

    spec = TaskSpec(task_id="t" * 16, func_id="f" * 16,
                    args=(1, 2.5, "x", b"b" * 64), kwargs={"k": [1, 2]},
                    return_ids=["t" * 16 + "r0"],
                    resources={"CPU": 1.0})
    task = {"type": "task", "rid": 123, "spec": spec}
    done = {"type": "task_done", "rid": 124, "task_id": "t" * 16,
            "results": ["r" * 18], "error": None}
    # all Python-plane subs: a structural sub anywhere makes
    # dumps_batch take the one-shot protobuf path (by design), which
    # would turn this row into a protobuf-vs-protobuf comparison
    batch64 = [dict(done, rid=1000 + i) for i in range(64)]
    from google.protobuf.internal import api_implementation
    backend = api_implementation.Type()
    N = 3000
    out: dict = {}
    for mode in ("native", "python"):
        if mode == "python":
            _os.environ["RAY_TPU_WIRE_NATIVE"] = "0"
        else:
            # force the C codec: 'auto' would defer to a C-backed
            # protobuf, and this scenario measures the codec itself
            _os.environ["RAY_TPU_WIRE_NATIVE_CODEC"] = "1"
        CONFIG.reload()
        try:
            rec = {}
            for name, fn in (
                    ("task_us", lambda: wire.loads(wire.dumps(task))),
                    ("task_done_us",
                     lambda: wire.loads(wire.dumps(done))),
                    ("batch64_us",
                     lambda: wire.loads(wire.dumps_batch(batch64)))):
                fn()                                     # warm
                n = N // 10 if name == "batch64_us" else N
                t0 = time.perf_counter()
                for _ in range(n):
                    fn()
                rec[name] = round(
                    (time.perf_counter() - t0) / n * 1e6, 2)
            out[f"wire_codec_{mode}"] = {
                "n": N, "unit": "roundtrips",
                "pb_backend": backend,
                # False here means the forced C codec could NOT engage
                # (no compiler / RAY_TPU_DISABLE_NATIVE) and this row
                # degenerated to a protobuf-vs-protobuf comparison
                "c_codec_active": wire._native_codec() is not None,
                **rec}
        finally:
            _os.environ.pop("RAY_TPU_WIRE_NATIVE", None)
            _os.environ.pop("RAY_TPU_WIRE_NATIVE_CODEC", None)
            CONFIG.reload()
    return out


def _broadcast_bench(n_nodes: int = 8, mb: int = 64) -> dict:
    """Tree vs all-pull-from-source A/B (r8 object plane, r12
    cut-through): one `mb`-MB object distributed to `n_nodes` real
    agent subprocesses. `flat` fans every node directly off the source
    (the pre-tree topology); `tree` runs the fanout cascade — the
    source serves <= fanout transfers and relay nodes serve their
    subtrees from the in-flight landing (cut-through) the moment their
    first chunk lands. Aggregate GB/s counts every delivered copy.
    Arm order alternates across AB_REPS (see _ab_pair); one cluster
    hosts all reps, each rep broadcasting a FRESH object."""
    import ray_tpu
    from ray_tpu.cluster_utils import NodeAgentProcess
    from ray_tpu._private.config import CONFIG
    CONFIG.reload()
    rt = ray_tpu.init(num_cpus=2)
    agents = [NodeAgentProcess(num_cpus=1) for _ in range(n_nodes)]
    out: dict = {}
    try:
        deadline = time.time() + 180
        while (time.time() < deadline
               and len(rt.cluster.alive_nodes()) < n_nodes + 1):
            time.sleep(0.2)
        joined = len(rt.cluster.alive_nodes()) - 1
        payload = np.arange(mb * 1024 * 1024 // 8, dtype=np.float64)
        seq = {"n": 0}

        def run(fanout: int) -> dict:
            seq["n"] += 1
            ref = ray_tpu.put(payload * float(seq["n"]))  # fresh object
            t0 = time.perf_counter()
            st = rt.broadcast_object(ref.object_id, fanout=fanout,
                                     timeout=600)
            dt = time.perf_counter() - t0
            src_serves = rt._pull_server.serves_per_object().get(
                ref.object_id, 0)
            gb = st["nbytes"] * st["completed"] / 2 ** 30
            rec = {"n": st["completed"], "unit": "GB",
                   "seconds": round(dt, 4),
                   "per_second": round(gb / dt, 3),
                   "fanout": fanout, "depth": st["depth"],
                   "source_serves": src_serves,
                   "failed": len(st["failed"])}
            del ref                  # free agent copies before the next
            time.sleep(1.0)
            return rec

        flat, tree = _ab_pair(
            out, f"bcast_{mb}mb_flat", lambda: run(max(64, joined)),
            f"bcast_{mb}mb_tree", lambda: run(2))
        if flat["per_second"]:
            tree["tree_speedup"] = round(
                tree["per_second"] / flat["per_second"], 2)
    finally:
        for a in agents:
            a.terminate()
        for a in agents:
            a.wait(10)
        ray_tpu.shutdown()
    return out


def _pull_bench(mb: int = 64) -> dict:
    """Manifest-vs-blob pull A/B (r12 zero-copy serve/land): one
    holder store serving a `mb`-MB object over a real same-box TCP
    pair. The blob arm is exactly what a MINOR<5 peer runs
    (materialize + pickle blob + chunk slices, reassembly + re-decode
    on the puller); the manifest arm scatter-gathers chunk frames
    straight from the holder's shm mapping and lands bodies into the
    puller's pooled segments with ONE memcpy. Copy counters come from
    OBJECT_PLANE_STATS deltas, so the copies-per-byte columns are the
    code's own accounting, not an estimate. One untimed manifest
    warm-up faults the segment pool first: timed manifest runs
    measure steady-state (pooled-page) serving, the weight-delivery
    case — same-box numbers are wire-floor-bound, see ENVELOPE."""
    from ray_tpu._private import object_store as osm
    from ray_tpu._private import object_transfer as ot
    from ray_tpu._private import protocol
    from ray_tpu._private.config import CONFIG
    CONFIG.reload()
    src = osm.LocalStore()
    obj = osm.serialize(np.arange(mb * 1024 * 1024 // 8,
                                  dtype=np.float64))
    src.put_stored(obj)
    oid, nbytes = obj.object_id, obj.nbytes
    server = ot.PullServer(src)

    def handle(conn, msg):
        if msg["type"] == protocol.PULL_OBJECT:
            server.handle_pull(conn, msg)
        elif msg["type"] == protocol.PULL_CHUNK:
            server.handle_chunk(conn, msg)

    import socket as _socket
    lst = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
    lst.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
    lst.bind(("127.0.0.1", 0))
    lst.listen(4)
    cli = protocol.connect(lst.getsockname(), lambda c, m: None,
                           name="bench-puller")
    srv_sock, _ = lst.accept()
    srv = protocol.Connection(srv_sock, handle,
                              on_close=server.on_conn_closed,
                              name="bench-holder", server=True)
    srv.start()
    dst = osm.LocalStore()
    out: dict = {}
    try:
        def run(manifest: bool) -> dict:
            s0 = dict(ot.OBJECT_PLANE_STATS)
            t0 = time.perf_counter()
            stored = ot.pull_object(cli, oid, timeout=300,
                                    store=dst if manifest else None)
            dt = time.perf_counter() - t0
            assert stored is not None and stored.nbytes == nbytes
            d = {k: ot.OBJECT_PLANE_STATS[k] - s0[k] for k in s0}
            rec = {"n": 1, "unit": "GB", "seconds": round(dt, 4),
                   "per_second": round(nbytes / dt / 2 ** 30, 3),
                   "serve_copies_per_byte": round(
                       d["serve_bytes_copied"] / nbytes, 2),
                   "land_copies_per_byte": round(
                       d["land_bytes_copied"] / nbytes, 2)}
            if manifest:
                dst.delete(oid)      # segments back to the pool
            return rec

        run(True)                    # untimed pool warm-up
        blob, man = _ab_pair(out, f"pull_{mb}mb_blob",
                             lambda: run(False),
                             f"pull_{mb}mb_manifest",
                             lambda: run(True))
        if blob["per_second"]:
            man["manifest_speedup"] = round(
                man["per_second"] / blob["per_second"], 2)
    finally:
        cli.close()
        srv.close()
        lst.close()
        dst.shutdown()
        src.shutdown()
    return out


def _head_restart_bench(n_tasks: int = 3000) -> dict:
    """Head-HA chaos scenario (r15): a 0-CPU head leases `n_tasks` to
    one 4-CPU agent, is SIGKILLed mid-drain, and a fresh head process
    rehydrates from snapshot+WAL on the same port. Measures the
    recovery envelope: SIGKILL -> first post-restart TASK_DONE
    processed (rejoin + completion-replay latency) and SIGKILL ->
    every task accounted exactly once. Exactly-once is asserted from
    the agent-side execution log, not inferred."""
    import signal
    import socket as _socket
    import subprocess
    import tempfile
    import textwrap

    import ray_tpu
    from ray_tpu.cluster_utils import NodeAgentProcess

    d = tempfile.mkdtemp(prefix="rtpu_ha_bench_")
    snap = os.path.join(d, "head.snap")
    execlog = os.path.join(d, "exec.log")
    ready = os.path.join(d, "ready")
    outp = os.path.join(d, "out.json")
    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RAY_TPU_HEAD_SNAPSHOT_PATH=snap)
    env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    head_a = textwrap.dedent(f"""
        import time, ray_tpu
        rt = ray_tpu.init(num_cpus=0, port={port})
        deadline = time.monotonic() + 60
        while (len(rt.cluster.alive_nodes()) < 2
               and time.monotonic() < deadline):
            time.sleep(0.05)

        @ray_tpu.remote(resources={{"agent": 0.01}})
        def work(i):
            import os
            fd = os.open({execlog!r},
                         os.O_WRONLY | os.O_CREAT | os.O_APPEND)
            os.write(fd, (str(i) + "\\n").encode())
            os.close(fd)
            return i

        refs = [work.remote(i) for i in range({n_tasks})]
        open({ready!r}, "w").write("ok")
        time.sleep(600)
    """)
    head_b = textwrap.dedent(f"""
        import collections, json, time, ray_tpu
        t_start = time.time()
        rt = ray_tpu.init(num_cpus=0, port={port})
        t_init = time.time()
        n0 = len(rt.controller.live_task_ids())
        t_first = None
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            n = len(rt.controller.live_task_ids())
            if t_first is None and n < n0:
                t_first = time.time()
            if n == 0 and not rt._ha.pending_nodes:
                break
            time.sleep(0.002)
        t_drained = time.time()
        st = rt.state_op("head_ha_stats")
        c = collections.Counter(
            int(x) for x in open({execlog!r}).read().split())
        json.dump({{
            "t_start": t_start, "t_init": t_init, "t_first": t_first,
            "t_drained": t_drained, "live_at_init": n0,
            "dups": sum(1 for v in c.values() if v > 1),
            "executed": len(c), "recovered": st["recovered"],
        }}, open({outp!r}, "w"))
        ray_tpu.shutdown()
    """)
    pa = pb = agent = None
    try:
        pa = subprocess.Popen([sys.executable, "-c", head_a], env=env)
        deadline = time.time() + 30
        while agent is None and time.time() < deadline:
            try:
                agent = NodeAgentProcess(
                    head_address=("127.0.0.1", port), num_cpus=4,
                    resources={"agent": 100.0})
            except Exception:
                time.sleep(0.3)
        while not os.path.exists(ready) and time.time() < deadline + 60:
            time.sleep(0.05)
        # kill mid-drain: roughly half the batch executed
        while time.time() < deadline + 120:
            done = (len(open(execlog).read().split())
                    if os.path.exists(execlog) else 0)
            if done >= n_tasks // 2:
                break
            time.sleep(0.02)
        t_kill = time.time()
        os.kill(pa.pid, signal.SIGKILL)
        pa.wait(timeout=10)
        pb = subprocess.Popen([sys.executable, "-c", head_b], env=env)
        rc = pb.wait(timeout=240)
        rep = json.load(open(outp)) if os.path.exists(outp) else {}
        rec = {
            "n": n_tasks, "unit": "tasks",
            "killed_after": n_tasks - rep.get("live_at_init", 0),
            "live_at_restart": rep.get("live_at_init"),
            "sigkill_to_first_done_s": (
                round(rep["t_first"] - t_kill, 3)
                if rep.get("t_first") else None),
            "sigkill_to_drained_s": round(
                rep.get("t_drained", t_kill) - t_kill, 3),
            "head_b_init_s": round(
                rep.get("t_init", 0) - rep.get("t_start", 0), 3),
            "executed_exactly_once": (rep.get("dups") == 0
                                      and rep.get("executed") == n_tasks
                                      and rc == 0),
            "replayed_completions": rep.get("recovered", {}).get(
                "replayed_completions"),
            "deduped_completions": rep.get("recovered", {}).get(
                "deduped_completions"),
        }
        return {"head_restart_recovery": rec}
    finally:
        for p in (pa, pb):
            if p is not None and p.poll() is None:
                p.kill()
        if agent is not None:
            agent.terminate()
            agent.wait(10)


def _pipeline_stage_fn(p, h):
    import jax

    def layer(h, wb):
        w, b = wb
        import jax.numpy as jnp
        return jnp.tanh(h @ w + b), None
    h, _ = jax.lax.scan(layer, h, (p["w"], p["b"]))
    return h


def _pipeline_loss_fn(y, t):
    import jax.numpy as jnp
    return jnp.sum((y - t) ** 2)


def _pipeline_bench() -> dict:
    """MPMD pipeline A/Bs (r13): transfer/compute overlap (ring depth
    2) vs single-slot channels (depth 1), and the 1F1B schedule vs the
    GPipe fallback — 4 stage-actor processes over shm channels, one
    shared runtime, stage actors (and their jit caches) reused across
    arms so each timed run measures the schedule, not process spawns.
    Bubble fraction comes from the r9 tracing plane, windowed to the
    timed steps."""
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu._private import context as _pctx
    from ray_tpu._private import tracing_plane as _tp
    from ray_tpu._private.config import CONFIG
    from ray_tpu.parallel.pipeline import partition_layers, slice_stage
    from ray_tpu.train.pipeline import MPMDPipeline, bubble_fraction
    CONFIG.reload()
    ray_tpu.init(num_cpus=6)
    S, L, D, B, M, STEPS = 4, 8, 256, 32, 8, 4
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(L, D, D)) * 0.2,
                               jnp.float32),
              "b": jnp.zeros((L, D), jnp.float32)}
    X = rng.normal(size=(B, D)).astype(np.float32)
    T = rng.normal(size=(B, D)).astype(np.float32)

    @ray_tpu.remote
    class StageWorker:
        pass

    actors = [StageWorker.remote() for _ in range(S)]
    parts = partition_layers(L, S)
    sparams = [slice_stage(params, s, c) for s, c in parts]

    def run(schedule: str, depth: int):
        def _run() -> dict:
            pipe = MPMDPipeline(
                actors, sparams, stage_fn=_pipeline_stage_fn,
                loss_fn=_pipeline_loss_fn, num_microbatches=M,
                schedule=schedule, steps=STEPS + 1, transport="shm",
                ring_depth=depth, capacity=16 << 20)
            pipe.start()
            try:
                pipe.run_step(0, X, T)          # warm the stage jits
                w0 = _tp.now()
                t0 = time.perf_counter()
                for s_ in range(STEPS):
                    pipe.run_step(1 + s_, X, T)
                dt = time.perf_counter() - t0
                w1 = _tp.now()
                bf = None
                try:
                    dump = _pctx.get_ctx().state_op("trace_dump")
                    bf = bubble_fraction(dump.get("processes", []),
                                         window=(w0, w1))
                except Exception:
                    bf = None
                pipe.finish(timeout=120)
            finally:
                pipe.teardown()
            n_mb = STEPS * M
            rec = {"n": n_mb, "seconds": round(dt, 4),
                   "per_second": round(n_mb / dt, 1),
                   "unit": "microbatches"}
            if bf is not None and bf == bf:
                rec["bubble_fraction"] = bf
            return rec
        return _run

    results: dict = {}
    run("1f1b", 2)()                 # global warmup: actor jax imports
    off, on = _ab_pair(results, "pipeline_1f1b_depth1", run("1f1b", 1),
                       "pipeline_1f1b_overlap", run("1f1b", 2))
    if off["per_second"]:
        on["overlap_speedup"] = round(
            on["per_second"] / off["per_second"], 2)
    gp, fb = _ab_pair(results, "pipeline_gpipe", run("gpipe", 2),
                      "pipeline_1f1b", run("1f1b", 2))
    if gp["per_second"]:
        fb["schedule_speedup"] = round(
            fb["per_second"] / gp["per_second"], 2)
    for a in actors:
        try:
            ray_tpu.kill(a)
        except Exception:
            pass
    ray_tpu.shutdown()
    return results


def main(as_json: bool = False) -> dict:
    results: dict = {}

    # ------- MPMD pipeline: overlap + schedule A/Bs (r13). First so
    # its 4 stage actors' flight recorders aren't polluted by other
    # scenarios' spans (bubble fraction is window-filtered anyway).
    results.update(_pipeline_bench())

    # ----------------------- wire codec: native vs pure Python (r7)
    results.update(_codec_bench())

    # ----- object plane: manifest vs blob 64 MB pull (r12 zero-copy)
    results.update(_pull_bench())

    # ------- object plane: broadcast tree vs all-pull-from-source (r8)
    results.update(_broadcast_bench())

    # ------------- native frame engine: 5k drain A/B (r7)
    # Fresh runtime per run (each arm sets its env before its workers
    # spawn); order alternates across reps — see _ab_pair.
    def _drain_env(n: int, var: str = None, val: str = "1"):
        def run() -> dict:
            if var is not None:
                os.environ[var] = val
            try:
                return _drain_with_frames(n)
            finally:
                if var is not None:
                    os.environ.pop(var, None)
        return run

    _off, _on = _ab_pair(
        results, "drain_5k_nonative",
        _drain_env(5000, "RAY_TPU_DISABLE_NATIVE"),
        "drain_5k_native", _drain_env(5000))
    if _off["per_second"]:
        _on["native_speedup"] = round(
            _on["per_second"] / _off["per_second"], 2)

    # ---------- delegated vs central dispatch: 5k remote drain (r10)
    # Fresh head+agent pair per run (each arm's env is set before its
    # agent spawns, inside _delegated_drain); order alternates.
    _c, _d = _ab_pair(
        results, "drain_5k_central",
        lambda: _delegated_drain(5000, delegate=False),
        "drain_5k_delegated",
        lambda: _delegated_drain(5000, delegate=True))
    if _c["per_second"]:
        _d["delegate_speedup"] = round(
            _d["per_second"] / _c["per_second"], 2)

    # ------ direct vs head-routed actor calls: agent-hosted (r18)
    # Fresh head+agent pair per run; order alternates. Acceptance:
    # direct >= 2x head-routed sync throughput AND
    # head_frames_per_call <= 0.1 on the direct arm.
    _h, _dd = _ab_pair(
        results, "actor_sync_head",
        lambda: _direct_actor_bench(400, direct=False),
        "actor_sync_direct",
        lambda: _direct_actor_bench(400, direct=True))
    if _h["per_second"]:
        _dd["direct_speedup"] = round(
            _dd["per_second"] / _h["per_second"], 2)

    # --------------------- 100k-task drain: sustained head envelope
    # (r10 acceptance scenario; r16 acceptance metric — the scale at
    # which per-task head cost used to GROW with the in-flight
    # population; local workers, so the number tracks the full
    # submit->dispatch->done pipeline, not one box's agent protocol).
    # The r16 criterion rides the record: 100k per-task head CPU as a
    # multiple of the same-session 5k-delegated floor measured above.
    results["drain_100k"] = _drain_with_frames(100_000)
    floor = results.get("drain_5k_delegated",
                        {}).get("head_cpu_us_per_task")
    if floor:
        results["drain_100k"]["vs_delegated_floor"] = round(
            results["drain_100k"]["head_cpu_us_per_task"] / floor, 2)

    # ------------- tracing plane: trace-off vs trace-on 3k drain (r9)
    # Machine-checks the cost of FULL tracing (sampling stride forced
    # to 1 — the pre-r16 default): every task records its submit/
    # queue/lease/recv/exec/put/done spans and task-plane frames carry
    # 18 bytes of trace context. r14 measured this at +17%, which is
    # why r16 samples by default (the pair below).
    _b, _t = _ab_pair(
        results, "drain_3k_notrace",
        _drain_env(3000, "RAY_TPU_TRACE", "0"),
        "drain_3k_trace", _drain_env(3000, "RAY_TPU_TRACE_SAMPLE", "1"))
    if _b["per_second"]:
        _t["trace_overhead_pct"] = round(
            (_b["per_second"] / _t["per_second"] - 1) * 100, 1)

    # ------- sampled tracing: trace-off vs DEFAULT sampling (r16)
    # The r16 acceptance pair: at the default RAY_TPU_TRACE_SAMPLE
    # stride, 1-in-64 tasks carry a whole-or-nothing trace and the
    # rest pay zero ring writes / zero wire bytes — the overhead
    # column must sit within box noise (<2%), which is what makes
    # tracing cheap enough to leave on.
    _b, _s = _ab_pair(
        results, "drain_3k_trace_off",
        _drain_env(3000, "RAY_TPU_TRACE", "0"),
        "drain_3k_trace_sampled", _drain_env(3000))
    if _b["per_second"]:
        _s["trace_overhead_pct"] = round(
            (_b["per_second"] / _s["per_second"] - 1) * 100, 1)

    # ------------- head HA: WAL-off vs WAL-on 3k drain (r15)
    # Machine-checks the r15 claim: with the write-ahead log on
    # (RAY_TPU_HEAD_SNAPSHOT_PATH set, group-commit fsync batching at
    # the default 5 ms window) every submit/terminal/lease/refs event
    # is durably logged — throughput must stay within box noise of the
    # persistence-off run.
    import tempfile as _tempfile

    def _wal_drain():
        # fresh snapshot/WAL path per rep: reusing one would make rep
        # N+1 pay rep N's rehydration and measure the wrong thing
        d = _tempfile.mkdtemp(prefix="rtpu_wal_bench_")
        return _drain_env(3000, "RAY_TPU_HEAD_SNAPSHOT_PATH",
                          os.path.join(d, "head.snap"))()

    _b, _w = _ab_pair(
        results, "drain_3k_nowal", _drain_env(3000),
        "drain_3k_wal", _wal_drain)
    if _b["per_second"]:
        _w["wal_overhead_pct"] = round(
            (_b["per_second"] / _w["per_second"] - 1) * 100, 1)

    # ---------- head HA: SIGKILL mid-delegated-drain recovery (r15)
    results.update(_head_restart_bench())

    # --------- metrics plane: metrics-off vs metrics-on 3k drain (r11)
    # Machine-checks the r11 zero-cost claim: with metrics ON (the
    # default) every dispatch observes a queue-wait bucket, every task
    # a worker exec + head e2e bucket (one bisect + list increment
    # each), and every spec carries a submit stamp — throughput must
    # stay within noise of the RAY_TPU_METRICS=0 run.
    _b, _m = _ab_pair(
        results, "drain_3k_nometrics",
        _drain_env(3000, "RAY_TPU_METRICS", "0"),
        "drain_3k_metrics", _drain_env(3000))
    if _b["per_second"]:
        _m["metrics_overhead_pct"] = round(
            (_b["per_second"] / _m["per_second"] - 1) * 100, 1)

    # ------------------- control-frame coalescing: off vs on (r6)
    # The OFF run goes first in its own runtime (workers inherit the
    # env at spawn); the ON run is the normal 5k-drain below, which
    # records the same frames-per-task counters for comparison.
    os.environ["RAY_TPU_WIRE_BATCH"] = "0"
    try:
        results["drain_2k_unbatched"] = _drain_with_frames(2000)
    finally:
        os.environ.pop("RAY_TPU_WIRE_BATCH", None)

    import ray_tpu
    from ray_tpu._private.config import CONFIG as _CFG
    _CFG.reload()
    ray_tpu.init(num_cpus=4)

    # -------------------------------------------------- tasks / second
    @ray_tpu.remote
    def nop():
        return None

    ray_tpu.get([nop.remote() for _ in range(10)])        # warm pool
    N = 200
    # sync scenarios carry p50/p99 latency readouts (r18 satellite):
    # the r17 machine block read 209/s here with no way to tell a
    # uniform slowdown from a p99 tail — now both are visible
    results["tasks_sync_per_s"] = timed_each(
        lambda i: ray_tpu.get(nop.remote()), N)
    results["tasks_batch_per_s"] = timed(
        lambda: ray_tpu.get([nop.remote() for _ in range(N)]), N)

    # -------------------------------------------- actor calls / second
    @ray_tpu.remote
    class A:
        def ping(self):
            return None

    a = A.remote()
    ray_tpu.get(a.ping.remote())
    results["actor_calls_sync_per_s"] = timed_each(
        lambda i: ray_tpu.get(a.ping.remote()), N)
    results["actor_calls_async_per_s"] = timed(
        lambda: ray_tpu.get([a.ping.remote() for _ in range(N)]), N)
    ray_tpu.kill(a)          # scenario actors must not skew later ones

    # --------------------------------------------------- object plane
    small = np.arange(16)
    results["put_small_per_s"] = timed(
        lambda: [ray_tpu.put(small) for _ in range(N)], N)
    big = np.zeros(8 * 1024 * 1024 // 8)                  # 8 MB
    M = 40
    t0 = time.perf_counter()
    refs = [ray_tpu.put(big) for _ in range(M)]
    dt = time.perf_counter() - t0
    results["put_gbps"] = {"n": M, "seconds": round(dt, 4),
                           "per_second": round(M * 8 / 1024 / dt, 3),
                           "unit": "GB"}
    t0 = time.perf_counter()
    for r in refs:
        ray_tpu.get(r)
    dt = time.perf_counter() - t0
    results["get_gbps"] = {"n": M, "seconds": round(dt, 4),
                           "per_second": round(M * 8 / 1024 / dt, 3),
                           "unit": "GB"}

    # ---------------- shm segment churn: pooled vs unpooled (r6)
    # The large-object producer/consumer hot cycle in isolation:
    # serialize (segment create + 8 MB copy) then free. Pooled, the
    # freed segment is renamed into the size-class pool and the next
    # cycle reuses its already-faulted pages; unpooled, every cycle
    # pays shm_open/ftruncate plus kernel page zeroing + soft faults.
    from ray_tpu._private import object_store as _osm
    CY = 30

    def _cycle(release_fn) -> float:
        t0 = time.perf_counter()
        for _ in range(CY):
            obj = _osm.serialize(big)
            for name in obj.shm_names:
                release_fn(name)
        return time.perf_counter() - t0

    _cycle(_osm.free_segment)                       # warm the pool
    dt_pooled = _cycle(_osm.free_segment)
    reused = _osm.SEGMENT_POOL.reused
    os.environ["RAY_TPU_SHM_POOL"] = "0"
    from ray_tpu._private.config import CONFIG as _CFG2
    _CFG2.reload()
    try:
        dt_unpooled = _cycle(_osm.unlink_segment)
    finally:
        os.environ.pop("RAY_TPU_SHM_POOL", None)
        _CFG2.reload()
    _osm.SEGMENT_POOL.clear()
    results["shm_cycle_pooled_gbps"] = {
        "n": CY, "seconds": round(dt_pooled, 4),
        "per_second": round(CY * 8 / 1024 / dt_pooled, 3),
        "unit": "GB", "segments_reused": reused}
    results["shm_cycle_unpooled_gbps"] = {
        "n": CY, "seconds": round(dt_unpooled, 4),
        "per_second": round(CY * 8 / 1024 / dt_unpooled, 3),
        "unit": "GB",
        "pool_speedup": round(dt_unpooled / dt_pooled, 2)}

    # -------------------------------------------------- wait semantics
    K = 1000
    refs = [nop.remote() for _ in range(K)]
    t0 = time.perf_counter()
    remaining = refs
    while remaining:
        done, remaining = ray_tpu.wait(
            remaining, num_returns=min(100, len(remaining)), timeout=30)
    dt = time.perf_counter() - t0
    results["wait_1k_refs"] = {"n": K, "seconds": round(dt, 4),
                               "per_second": round(K / dt, 1),
                               "unit": "refs"}

    # --------------------------- parked waiters (event-driven core)
    # 200 concurrent gets on one unsealed object from a threaded actor:
    # the driver must hold 200 blocked requests. With the event-driven
    # waiter registry this costs ZERO driver threads (thread-per-blocked
    # -get would add 200); resolve latency is one seal -> 200 replies.
    import threading as _th

    @ray_tpu.remote(max_concurrency=200)
    class Getter:
        def fetch(self, ref):
            return ray_tpu.get(ref[0])

    g = Getter.remote()
    ray_tpu.get(g.fetch.remote([ray_tpu.put(1)]))
    from ray_tpu._private.refs import ObjectRef
    pending = ObjectRef("pending_" + "0" * 12)   # not sealed yet
    ray_tpu._private.context.get_ctx().addref(pending.object_id)
    W = 200
    threads_before = _th.active_count()
    futs = [g.fetch.remote([pending]) for _ in range(W)]
    time.sleep(1.0)                     # let all 200 gets park
    threads_parked = _th.active_count()
    t0 = time.perf_counter()
    ray_tpu._private.context.get_ctx().store.put(42, object_id=pending.object_id)
    ray_tpu.get(futs, timeout=60)
    dt = time.perf_counter() - t0
    results["parked_gets_200"] = {
        "n": W, "seconds": round(dt, 4),
        "per_second": round(W / dt, 1), "unit": "resolved",
        "driver_threads_added": threads_parked - threads_before}
    ray_tpu.kill(g)          # its 200-thread pool would drag later runs

    # --------------------------- compiled DAG: channels vs ref-wired
    # (VERDICT r3 item 8: the shm-channel fast path must beat the
    # ref-wired path on per-execute latency)
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class Hop:
        def work(self, x):
            return x

    h1, h2 = Hop.remote(), Hop.remote()
    with InputNode() as inp:
        chain = h2.work.bind(h1.work.bind(inp))
    ref_dag = chain.experimental_compile()
    for i in range(5):
        ray_tpu.get(ref_dag.execute(i))           # warm
    N_DAG = 200
    t0 = time.perf_counter()
    for i in range(N_DAG):
        ray_tpu.get(ref_dag.execute(i))
    ref_lat = (time.perf_counter() - t0) / N_DAG

    h3, h4 = Hop.remote(), Hop.remote()
    with InputNode() as inp:
        chain2 = h4.work.bind(h3.work.bind(inp))
    ch_dag = chain2.experimental_compile(enable_shm_channels=True)
    for i in range(5):
        ch_dag.execute(i).get()                   # warm
    t0 = time.perf_counter()
    for i in range(N_DAG):
        ch_dag.execute(i).get()
    ch_lat = (time.perf_counter() - t0) / N_DAG
    ch_dag.teardown()
    results["dag_2hop_execute"] = {
        "n": N_DAG, "unit": "executes",
        "refwired_ms": round(ref_lat * 1e3, 3),
        "shm_channel_ms": round(ch_lat * 1e3, 3),
        "channel_speedup": round(ref_lat / ch_lat, 2)}
    # ---------------------- device channels: raw-array hot edge
    # (VERDICT r4 item 6: jax.Array hand-off between actors without a
    # host serialize on the hot edge — raw shm frame + device_put)
    h5, h6 = Hop.remote(), Hop.remote()
    with InputNode() as inp:
        chain3 = h6.work.bind(h5.work.bind(inp))
    dev_dag = chain3.experimental_compile(enable_shm_channels=True,
                                          buffer_size_bytes=16 << 20)
    arr = np.zeros((1024, 1024), dtype=np.float32)      # 4 MB
    for _ in range(3):
        dev_dag.execute(arr).get()                      # warm
    N_DEV = 50
    t0 = time.perf_counter()
    for _ in range(N_DEV):
        out = dev_dag.execute(arr).get()
    dev_lat = (time.perf_counter() - t0) / N_DEV
    assert out.shape == arr.shape
    dev_dag.teardown()
    results["dag_device_hop"] = {
        "n": N_DEV, "unit": "executes",
        "payload_mb": round(arr.nbytes / 2 ** 20, 1),
        "per_execute_ms": round(dev_lat * 1e3, 3),
        "per_second": round(1.0 / dev_lat, 1),
        "seconds": round(dev_lat * N_DEV, 4),
        # 3 channel crossings per execute: driver->h5, h5->h6, h6->driver
        "channel_gbps_total": round(
            3 * arr.nbytes / dev_lat / 2 ** 30, 2)}

    for hop in (h1, h2, h3, h4, h5, h6):
        ray_tpu.kill(hop)
    time.sleep(0.5)          # let kills land before the queue scenarios

    # ------------------------------------------- many queued tasks
    # re-warm the worker pool first: the scenario measures queue drain
    # throughput, not worker-spawn latency after the actor kills above
    for _ in range(3):
        ray_tpu.get([nop.remote() for _ in range(30)])
    from ray_tpu._private import protocol as _protocol
    K = 5000
    s0 = dict(_protocol.WIRE_STATS)
    c0 = time.process_time()
    t0 = time.perf_counter()
    refs = [nop.remote() for _ in range(K)]
    dt_submit = time.perf_counter() - t0
    ray_tpu.get(refs, timeout=300)
    dt_total = time.perf_counter() - t0
    cpu = time.process_time() - c0
    results["queue_5k_tasks"] = {
        "n": K, "seconds": round(dt_total, 4),
        "submit_per_second": round(K / dt_submit, 1),
        "per_second": round(K / dt_total, 1), "unit": "tasks",
        "head_cpu_us_per_task": round(cpu / K * 1e6, 1),
        **_frame_stats(s0, K)}

    # ----------------------------- 100k queued: O(1) submit check
    # Submission cost must not grow with backlog depth (reference
    # envelope: 1M queued tasks per node). Chunk rates across a 100k
    # backlog expose any O(n) in enqueue/demand bookkeeping. The
    # backlog is deliberately NOT drained (that measures throughput,
    # covered above; this scenario measures submit scaling) — the
    # runtime is shut down with the queue loaded.
    CH, NCH = 10_000, 10
    chunk_rates = []
    for _ in range(NCH):
        t0 = time.perf_counter()
        for _ in range(CH):
            nop.remote()
        chunk_rates.append(round(CH / (time.perf_counter() - t0), 1))
    results["queue_100k_submit"] = {
        "n": CH * NCH, "seconds": round(
            sum(CH / r for r in chunk_rates), 4),
        "per_second": round(
            CH * NCH / sum(CH / r for r in chunk_rates), 1),
        "unit": "tasks",
        "first_chunk_per_s": chunk_rates[0],
        "last_chunk_per_s": chunk_rates[-1],
        "o1_submit": chunk_rates[-1] > 0.5 * chunk_rates[0]}

    ray_tpu.shutdown()
    if as_json:
        print(json.dumps(results))
    else:
        for name, r in results.items():
            if "per_second" in r:
                print(f"{name:28s} {r['per_second']:>12} {r['unit']}/s "
                      f"(n={r['n']}, {r.get('seconds', '?')}s)")
            else:
                extra = {k: v for k, v in r.items()
                         if k not in ("n", "unit")}
                print(f"{name:28s} {extra}")
    return results


def rl_main(as_json: bool = False) -> dict:
    """Just the r20 Sebulba A/B — re-measures the RL act path in
    isolation (the full suite takes tens of minutes)."""
    results: dict = {}
    _rl_section(results)
    if as_json:
        print(json.dumps(results))
    else:
        for name, r in results.items():
            print(f"{name:24s} {r['per_second']:>10} {r['unit']}/s "
                  f"(staleness p50/p95 {r['staleness_p50']}/"
                  f"{r['staleness_p95']}, head frames/call "
                  f"{r['head_frames_per_call']})")
    return results


if __name__ == "__main__":
    if "--rl" in sys.argv:
        rl_main(as_json="--json" in sys.argv)
    else:
        main(as_json="--json" in sys.argv)
