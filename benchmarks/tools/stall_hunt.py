"""Hunt the engine step that takes seconds (PERF.md, Findings 4), on the chip:

    python3 benchmarks/tools/stall_hunt.py --workload <serving cell> --windows 20

One engine, one warm-up, then the cell's own window again and again in one
process. Every engine step is timed from the benchmark's side, phase by
phase: the decode dispatch, the wait for the argmax to come back from the
device, `_ingest` and the stream's `publish`, with the step thread's own
run and run-queue time from /proc/thread-self/schedstat beside them, so a
slow step says whether its thread was running, waiting for a core, or asleep
on the device or a socket. A watchdog thread records when the whole process
(or the machine) stood still. Not a measurement: the wrappers are on the
timed path. Writes chiprun_out/stall_hunt.<cell>.jsonl, a line a window.
"""
import argparse
import json
import os
import threading
import time

from _common import ROOT

from benchmarks.harness.cells import load_cell, prepare_device

SLOW_S = 0.25


def schedstat():
    try:
        with open("/proc/thread-self/schedstat") as f:
            run_ns, wait_ns, _ = f.read().split()
        return int(run_ns) * 1e-9, int(wait_ns) * 1e-9
    except OSError:
        return 0.0, 0.0


def steal_s():
    try:
        with open("/proc/stat") as f:
            return int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


class Watchdog(threading.Thread):
    """Sleeps 10 ms at a time; an overshoot says nothing in this process
    got a core (or the GIL) for that long."""

    def __init__(self):
        super().__init__(name="bench-watchdog", daemon=True)
        self.late, self.stop = [], False

    def run(self):
        last = time.monotonic()
        while not self.stop:
            time.sleep(0.01)
            now = time.monotonic()
            if now - last > 0.1:
                self.late.append((now, now - last))
            last = now


class TimedNumpy:
    """Stands in for the engine core's numpy: times `asarray` on a device
    array, which is where the step waits for the device."""

    def __init__(self, np, phase):
        self._np, self._phase = np, phase

    def __getattr__(self, name):
        return getattr(self._np, name)

    def asarray(self, x, *a, **k):
        t = time.monotonic()
        out = self._np.asarray(x, *a, **k)
        if not isinstance(x, self._np.ndarray):
            self._phase["fetch"] += time.monotonic() - t
        return out


def instrument(engine, steps):
    core = engine.core
    phase = {}
    core._np = TimedNumpy(core._np, phase)

    def timed(obj, name, key):
        orig = getattr(obj, name)

        def wrapped(*a, **k):
            t = time.monotonic()
            try:
                return orig(*a, **k)
            finally:
                phase[key] = phase.get(key, 0.0) + time.monotonic() - t
        setattr(obj, name, wrapped)

    timed(core, "_decode_fn", "decode_dispatch")
    timed(engine._stream, "publish", "publish")
    orig_step, orig_ingest = core.step, engine._ingest
    state = {"last_end": None}

    def step():
        phase.clear()
        phase.update(fetch=0.0, decode_dispatch=0.0, publish=0.0)
        t0 = time.monotonic()
        run0, wait0 = schedstat()
        state.update(t0=t0, run0=run0, wait0=wait0,
                     running=len(core._running), waiting=len(core._waiting))
        events = orig_step()
        state["step_s"] = time.monotonic() - t0
        return events

    def ingest(events):
        t = time.monotonic()
        orig_ingest(events)
        end = time.monotonic()
        run1, wait1 = schedstat()
        steps.append({
            "t": state["t0"], "since_last_s": (
                None if state["last_end"] is None
                else state["t0"] - state["last_end"]),
            "step_s": state["step_s"], "ingest_s": end - t,
            "fetch_s": phase["fetch"],
            "decode_dispatch_s": phase["decode_dispatch"],
            "publish_s": phase["publish"],
            "thread_run_s": run1 - state["run0"],
            "thread_runqueue_s": wait1 - state["wait0"],
            "running": state["running"], "waiting": state["waiting"],
            "events": len(events)})
        state["last_end"] = end

    core.step, engine._ingest = step, ingest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--windows", type=int, default=20)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    _, cell, cfg, mix = load_cell(a.workload)
    prepare_device(cell, bool(a.rehearse))
    if a.rehearse:
        from benchmarks.harness.modelcfg import load_model
        cfg = load_model(cfg).tiny(cfg)
    from benchmarks.harness.serve_cell import Served
    served = Served(cfg, mix, a.seed, a.seconds)
    steps = []
    instrument(served.engine, steps)
    dog = Watchdog()
    dog.start()
    out = os.path.join(ROOT, "chiprun_out", f"stall_hunt.{a.workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        for i in range(a.windows):
            del steps[:]
            del dog.late[:]
            steal0 = steal_s()
            w = served.window(mix, a.seconds)
            t0 = w["t0"]
            slow = [dict(s, t=s["t"] - t0) for s in steps
                    if s["step_s"] + s["ingest_s"] > SLOW_S
                    or (s["since_last_s"] or 0.0) > SLOW_S and s["running"]]
            row = {"window": i, "sent": w["sent"], "failed": w["failed"],
                   "ttft_mean_ms": 1e3 * sum(w["ttft_s"]) / len(w["ttft_s"]),
                   "gap_max_ms": 1e3 * max(w["gap_s"]),
                   "submit_max_ms": 1e3 * max(w["submit_s"]),
                   "tokens": w["tokens_in_window"], "steps": len(steps),
                   "step_max_s": max(s["step_s"] for s in steps),
                   "steal_s": (None if steal0 is None
                               else steal_s() - steal0),
                   "watchdog_late": [(t - t0, d) for t, d in dog.late],
                   "slow_steps": slow}
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
    dog.stop = True
    served.close()
    os._exit(0)


if __name__ == "__main__":
    main()
