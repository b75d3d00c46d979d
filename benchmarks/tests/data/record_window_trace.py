"""Records the small trace of a traced window that overhangs
(`benchmarks/tests/test_window.py` reads it).

Run on the chip
(`chiprun -- python3 benchmarks/tests/data/record_window_trace.py`): a thread
keeps two executions of one program (six 4096 x 4096 matmuls, some 4 ms) in
flight, so the device never pauses; beside it this thread traces a quarter
of a second the way `harness/serve_cell.trace_part_of_window` does
(`start_trace`, the span `xplane.WINDOW` around a sleep, `stop_trace`), but
for 50 ms that it lets pass before it opens the marker. So this small trace
holds device time outside the marker, as a serving run's holds the 20-24 ms
of decode steps that the profiler takes to stop (PERF.md, PR 32): a busy time
taken over the whole trace reads above the window (what refused PR 31), the
one `xplane.traced_window` takes inside the marker cannot. What it wrote was
copied to
`benchmarks/tests/data/busy_window_v5e.xplane.pb` (the plane
`/host:metadata` dropped); the numbers the tests expect are printed here.
"""
import glob
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))
sys.path.insert(0, HERE)


def main():
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import xplane
    from record_engine_trace import drop_hlo_protos

    out = os.path.join("chiprun_out", "record_window_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))

    def busy_step(x):
        for _ in range(6):
            x = (x @ x) * jnp.bfloat16(1.0 / 64)
        return x

    step = jax.jit(busy_step)
    x = jnp.ones((4096, 4096), jnp.bfloat16) / 64
    jax.block_until_ready(step(x))
    stop = threading.Event()

    def feed():
        y, ahead = x, []
        while not stop.is_set():
            y = step(y)
            ahead.append(y)
            if len(ahead) > 2:
                jax.block_until_ready(ahead.pop(0))

    feeder = threading.Thread(target=feed, name="bench-feeder")
    feeder.start()
    time.sleep(0.3)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    time.sleep(0.05)
    a = time.perf_counter()
    with jax.profiler.TraceAnnotation(xplane.WINDOW):
        time.sleep(0.25)
    b = time.perf_counter()
    jax.profiler.stop_trace()
    stop.set()
    feeder.join()
    print("host stopwatch around the sleep", b - a)
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    whole = os.path.getsize(path)
    drop_hlo_protos(path)
    print("trace", path, whole, "->", os.path.getsize(path))
    trace = xplane.load(path)
    w = xplane.traced_window(trace)
    lo, hi = xplane.device_span(trace)
    print("window_s", w.window_s, "busy_s inside", w.busy_s,
          "busy_s of the whole trace", xplane.busy_seconds(trace),
          "device span", hi - lo, "clock_shift_s", xplane.clock_shift_s(trace))
    print("first op before the marker", w.lo - lo, "last op after", hi - w.hi)
    print("programs", {k: (len(v), sum(v)) for k, v in
                       xplane.program_times(trace).items()})
    gaps = xplane.idle_gaps(trace, lo=w.lo, hi=w.hi)
    print("idle gaps inside", len(gaps), sum(q - p for p, q in gaps))
    print("device_ops inside", xplane.top(
        xplane.op_times(trace, w.lo, w.hi), 4),
        "whole", xplane.top(xplane.op_times(trace), 4))
    print("host lines", {k: len(v) for k, v in trace.host.items()})


if __name__ == "__main__":
    main()
