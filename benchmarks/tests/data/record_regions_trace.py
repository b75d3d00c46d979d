"""Records the small trace `benchmarks/tests/test_op_scopes.py` reads for
the regions (`ray_tpu/models/regions.py`).

Run on the chip
(`chiprun -- python3 benchmarks/tests/data/record_regions_trace.py`): the
program's `LLMEngine` at `record_engine_trace.py`'s size, every program
compiled before the trace opens, on a compile cache of its own (metadata
is no part of the cache's key: an executable from before the regions would
carry none); then, under `jax.profiler`, two requests, and five steps of a
tiny rematted `Transformer.loss` + AdamW jitted as `train_cell.py` jits
them, under the name `_train_step` so that its executions stand apart
from the engine's `jit__step`. What it wrote was copied to
`benchmarks/tests/data/regions_v5e.xplane.pb`, cut to what
`harness/op_scopes.py` reads (`keep_what_is_read`); the numbers the tests
expect from it are printed by this script.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="regions_cache_")

from record_engine_trace import ENGINE, MODEL, REQUESTS, generate  # noqa

TRAIN = dict(vocab_size=512, d_model=256, n_layers=2, n_heads=2,
             n_kv_heads=1, d_ff=512, max_seq_len=256, remat=True,
             remat_policy="save_attn_qkv", dtype="bfloat16",
             param_dtype="bfloat16")
TRAIN_STEPS = 5


def _key(number: int, kind: int) -> bytes:
    return _varint(number << 3 | kind)


def _varint(value: int) -> bytes:
    out = bytearray()
    while True:
        value, low = value >> 7, value & 0x7F
        out.append(low | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _field(number: int, body: bytes) -> bytes:
    return _key(number, 2) + _varint(len(body)) + body


KEPT_STATS = ("tf_op", "source", "hlo_category", "program_id", "flops",
              "bytes_accessed")


def keep_what_is_read(path: str) -> None:
    """Rewrites the file with what `op_scopes` reads alone: the plane
    `/device:TPU:0`, its lines `XLA Ops` and `XLA Modules` with each
    event's metadata id, offset and duration, the event metadata those
    events name with their name and the `KEPT_STATS`, and the stat
    metadata (two thirds of the plane are source stacks, layouts and lines
    nothing here reads)."""
    from benchmarks.harness import op_scopes as ops
    with open(path, "rb") as f:
        buf = f.read()

    def raw(span):
        return buf[span[0]:span[1]]

    out = b""
    for number, plane in ops._fields(buf, 0, len(buf)):
        parts = list(ops._fields(buf, *plane)) if number == 1 else []
        if not any(n == 2 and ops._text(buf, v) == ops.DEVICE_PLANE
                   for n, v in parts):
            continue
        stat_names = {}
        for n, v in parts:
            if n == 5:
                key, value = ops._map_entry(buf, v)
                stat_names[key] = next(
                    (ops._text(buf, s) for m, s in ops._fields(buf, *value)
                     if m == 2), "")
        body, used = _field(2, ops.DEVICE_PLANE.encode()), set()
        for n, v in parts:
            if n != 3:
                continue
            fields = list(ops._fields(buf, *v))
            name = next(ops._text(buf, x) for m, x in fields if m == 2)
            if name not in (ops.OPS_LINE, ops.MODULES_LINE):
                continue
            line = b""
            for m, x in fields:
                if m == 4:
                    event = b""
                    for q, y in ops._fields(buf, *x):
                        if q in (1, 2, 3):
                            event += _key(q, 0) + _varint(y)
                        if q == 1:
                            used.add(y)
                    line += _field(4, event)
                elif m in (2, 11):
                    line += _field(m, raw(x))
                elif m in (1, 3, 9, 10):
                    line += _key(m, 0) + _varint(x)
            body += _field(3, line)
        for n, v in parts:
            if n == 5:
                body += _field(5, raw(v))
            elif n == 4:
                key, value = ops._map_entry(buf, v)
                if key not in used:
                    continue
                meta = b""
                for m, x in ops._fields(buf, *value):
                    if m == 1:
                        meta += _key(1, 0) + _varint(x)
                    elif m == 2:
                        meta += _field(2, raw(x))
                    elif m == 5 and stat_names.get(next(
                            (y for q, y in ops._fields(buf, *x) if q == 1),
                            None)) in KEPT_STATS:
                        meta += _field(5, raw(x))
                body += _field(4, _key(1, 0) + _varint(key)
                               + _field(2, meta))
        out += _field(1, body)
    with open(path, "wb") as f:
        f.write(out)


def train_step():
    import jax
    import optax
    from ray_tpu.models import Transformer, TransformerConfig
    model = Transformer(TransformerConfig(**TRAIN))
    params = model.init(jax.random.key(0))
    opt = optax.adamw(1e-4)
    state = jax.jit(opt.init)(params)
    tokens = jax.random.randint(jax.random.key(1), (2, 256), 0, 512)

    def _train_step(p, s, batch):
        loss, g = jax.value_and_grad(model.loss)(p, batch)
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s, loss

    step = jax.jit(_train_step, donate_argnums=(0, 1))
    box = [params, state]

    def run(n: int):
        for _ in range(n):
            box[0], box[1], loss = step(box[0], box[1], {"tokens": tokens})
        jax.block_until_ready(loss)
    return run


def main():
    import jax
    from benchmarks.harness import op_scopes
    from ray_tpu.serve.llm.engine import LLMEngine
    from ray_tpu.serve.llm.stream import stream_client

    out = os.path.join("chiprun_out", "record_regions_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))
    engine = LLMEngine(model=MODEL, seed=0, **ENGINE)
    client = stream_client()
    for rid, n_prompt, n_out in REQUESTS:            # compiles
        generate(engine, client, "warm-" + rid, n_prompt, n_out)
    train = train_step()
    train(1)                                         # compiles
    time.sleep(0.2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    for rid, n_prompt, n_out in REQUESTS:
        print(rid, "tokens", generate(engine, client, rid, n_prompt, n_out))
    train(TRAIN_STEPS)
    jax.profiler.stop_trace()
    engine.close()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    whole = os.path.getsize(path)
    keep_what_is_read(path)
    print("trace", path, whole, "->", os.path.getsize(path))
    dev_ops = op_scopes.load(path)
    for program in sorted({ex.program for ex in dev_ops.executions}):
        runs = op_scopes.executions(dev_ops, program)
        table = op_scopes.table(dev_ops, program)
        print("PROGRAM", program, len(runs), "executions",
              [ex.dur_ps for ex in runs][:6])
        for key, ms in sorted((table or {}).items()):
            print("   ", key, round(ms, 6))
    shutil.copy(path, os.path.join(out, "regions_v5e.xplane.pb"))


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)     # daemon threads of the engine's stream must not linger
