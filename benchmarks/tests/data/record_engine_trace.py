"""Records the small engine trace `benchmarks/tests/test_spans.py` reads.

Run on the chip
(`chiprun -- python3 benchmarks/tests/data/record_engine_trace.py`): the
program's `LLMEngine` (step thread, token stream, one subscriber) at a
small size the chip's compiler takes, every program compiled before the
trace opens; then, under `jax.profiler`, a request of 5 tokens, an idle
stretch, a request of 20 tokens, and one gradient of a rematted
flash-attention layer so that the backward kernels are in the trace too.
What it wrote was copied to
`benchmarks/tests/data/tiny_engine_v5e.xplane.pb`; the numbers the tests
expect from it are printed by this script. The plane `/host:metadata` (the
programs' HLO protos, two thirds of the file, read by nothing here) is
dropped from the copy.
"""
import glob
import os
import queue
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

MODEL = dict(vocab_size=512, d_model=256, n_layers=1, n_heads=2,
             n_kv_heads=1, d_ff=512, max_seq_len=256, remat=False,
             dtype="bfloat16", param_dtype="bfloat16")
ENGINE = dict(num_pages=64, page_size=16, max_batch=2)
REQUESTS = [("a", 5, 3), ("b", 20, 2)]      # rid, prompt tokens, tokens out


def generate(engine, client, rid, n_prompt, n_out):
    acc = engine.generate(list(range(1, n_prompt + 1)), max_tokens=n_out,
                          rid=rid)
    sink = queue.Queue()
    assert client.subscribe(tuple(acc["stream"]), rid, acc["incarnation"],
                            acc["attempt"], 0, sink)
    got = 0
    while True:
        msg = sink.get(timeout=120)
        got = max(got, msg["base"] + len(msg["toks"]))
        if msg["done"]:
            return got


def drop_hlo_protos(path: str) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    keep = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(keep)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def kernel_step():
    """Forward and backward of one rematted attention layer and an
    rms_norm: every Pallas kernel the program has, the forward twice."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention_saveable
    from ray_tpu.ops.norms import rms_norm

    def loss(q, kv, x, w):
        layer = jax.checkpoint(
            lambda q, kv: flash_attention_saveable(q, kv, kv, causal=True))
        return (layer(q, kv).astype(jnp.float32).sum()
                + rms_norm(x, w).astype(jnp.float32).sum())

    args = (jnp.ones((1, 2, 256, 128), jnp.bfloat16) * 0.1,
            jnp.ones((1, 1, 256, 128), jnp.bfloat16) * 0.1,
            jnp.ones((256, 256), jnp.bfloat16),
            jnp.zeros((256,), jnp.bfloat16))
    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    return lambda: jax.block_until_ready(step(*args))


def main():
    import jax
    from benchmarks.harness import spans, xplane
    from ray_tpu.serve.llm.engine import LLMEngine
    from ray_tpu.serve.llm.stream import stream_client

    out = os.path.join("chiprun_out", "record_engine_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))
    engine = LLMEngine(model=MODEL, seed=0, **ENGINE)
    client = stream_client()
    for rid, n_prompt, n_out in REQUESTS:            # compiles
        generate(engine, client, "warm-" + rid, n_prompt, n_out)
    kernels = kernel_step()
    kernels()                                        # compiles
    time.sleep(0.2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    t0 = time.perf_counter()
    for rid, n_prompt, n_out in REQUESTS:
        print(rid, "tokens", generate(engine, client, rid, n_prompt, n_out))
        time.sleep(0.12)
    kernels()
    print("window_s", time.perf_counter() - t0)
    jax.profiler.stop_trace()
    stats = engine.engine_stats()
    engine.close()
    print("counters", {k: v for k, v in stats.items()
                       if isinstance(v, int) and not isinstance(v, bool)})
    print("slow_steps", stats["slow_steps"])
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    whole = os.path.getsize(path)
    drop_hlo_protos(path)
    print("trace", path, whole, "->", os.path.getsize(path))
    trace = xplane.load(path)
    print("programs", {k: len(v) for k, v in
                       xplane.program_times(trace).items()})
    print("device_ops", xplane.top(xplane.op_times(trace), 6))
    print("clock_shift_s", xplane.clock_shift_s(trace))
    gaps = xplane.idle_gaps(trace)
    print("idle_gaps", len(gaps), sum(b - a for a, b in gaps))
    by_thread = spans.load(path)
    for thread, ss in by_thread.items():
        print("THREAD", thread)
        for s, parent in zip(ss, spans.parents(ss)):
            print("  ", s.name, round(s.start, 6), round(s.dur * 1e6, 1),
                  s.stats, "<-", parent.name if parent else None)
    r = spans.read(trace, path)
    print("gaps by span", r.gaps, "sum", sum(r.gaps.values()),
          "idle", r.idle_s)


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)     # daemon threads of the engine's stream must not linger
