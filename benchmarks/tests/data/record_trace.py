"""Records the small device trace the trace-reduction tests read.

Run on the chip (`chiprun -- python3 benchmarks/tests/data/record_trace.py`):
three calls of a tiny jitted program (two matmuls and the program's flash
kernel) with a host sleep between them, under `jax.profiler`. What it wrote
was copied to `benchmarks/tests/data/tiny_v5e.xplane.pb`; the numbers the
tests expect from it are printed by this script.
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))


def main():
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention

    out = os.path.join("chiprun_out", "record_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))
    print("memory_stats", dev.memory_stats())

    def tiny_step(x, w, q):
        y = jnp.tanh(x @ w) @ w
        a = flash_attention(q, q, q, causal=True, block_q=128, block_k=128)
        return y.sum() + a.astype(jnp.float32).sum()

    step = jax.jit(tiny_step)
    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16) * 0.01
    q = jnp.ones((1, 4, 256, 128), jnp.bfloat16) * 0.1
    jax.block_until_ready(step(x, w, q))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = int(os.environ.get("PY_TRACER", "1"))
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    t0 = time.perf_counter()
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.call", i=i):
            jax.block_until_ready(step(x, w, q))
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
    print("window_s", time.perf_counter() - t0)
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    print("trace", paths, [os.path.getsize(p) for p in paths])
    data = jax.profiler.ProfileData.from_file(paths[0])
    for plane in data.planes:
        print("PLANE", repr(plane.name), len(list(plane.lines)))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                print("    EV", repr(ev.name), ev.start_ns, ev.duration_ns,
                      dict(list(ev.stats)[:8]) if len(evs) < 400 else "")


if __name__ == "__main__":
    main()
