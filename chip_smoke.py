"""Chip smoke: the train and serve paths, once, on the TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # one host with four (the builder's run)

Drives the system through the entry points a user calls, at the full
width of the ~1B dense model (`models.config.bench_1b`, random weights
from seed 0):

  train  ray_tpu.init() -> JaxTrainer(loop, ScalingConfig(use_tpu=True))
         .fit(): adamw steps on one fixed batch; loss finite and falling,
         flash attention and rms_norm against their references on the
         same device, the Pallas custom calls counted in the step's HLO.
  serve  serve_llm(model=..., ray_actor_options={"num_tpus": 1}): prompts
         of different lengths submitted together and streamed.
  check  a num_tpus=1 task rebuilds the weights from the seed and asserts
         every served token is the teacher-forced argmax of
         Transformer.apply (or within LOGIT_MARGIN of it); the paged
         decode-attention kernel against the einsum at the serving
         cells' shapes; which attention the engine's decode step holds.

With --chips 4 the train phase runs again with one worker holding all
four chips on MeshSpec(fsdp=2, tp=2), serving is four one-chip replicas,
and one more replica holds four chips with mesh tp=4.

This process never initialises a JAX backend. The chip is held by one
worker process at a time; the scheduler hands a chip on only when the
process that held it has exited, and each phase checks that.

There is no CPU mode: without a TPU the script says so and exits
non-zero. The last line of output is one JSON object with the device as
JAX reported it in the worker. Results go under chiprun_out/chip_smoke/.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# bf16 kernels against float32-accumulating references on the same
# device: largest error over largest reference value.
KERNEL_TOL = {"attn_out": 3e-2, "attn_grad": 5e-2, "rms_norm": 2e-2,
              "paged_attn": 3e-2}
# a served token may lose to the teacher-forced argmax by this much
# (logits have a standard deviation near 1): decode reads the paged
# cache one position at a time, the full forward runs the flash kernel.
LOGIT_MARGIN = 0.1
# one chip and fsdp=2.tp=2 reduce in different orders, in bf16: the first
# loss is one forward apart; later ones also carry five adamw steps whose
# smallest gradients may have changed sign
MESH_LOSS_RTOL_FIRST = 1e-2
MESH_LOSS_RTOL = 1e-1
# a killed worker has this long to be gone before the next phase starts
EXIT_WAIT_S = 60.0
# no single wait (a report, a token, the check task) may outlast this
PHASE_TIMEOUT_S = 900.0


# ------------------------------------------------------------- train
def _kernel_checks(cfg, batch: int, seq: int) -> dict:
    """flash attention (values and gradients) and rms_norm against the
    references, at the model's shapes, on this process's first device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_attention_kernel, mha_reference
    from ray_tpu.ops.norms import rms_norm, rms_norm_reference

    dt = cfg.activation_dtype

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 5)
        heads = (batch, cfg.n_heads, seq, cfg.head_dim)
        kv_heads = (batch, cfg.kv_heads, seq, cfg.head_dim)
        return (jax.random.normal(ks[0], heads, dt),
                jax.random.normal(ks[1], kv_heads, dt),
                jax.random.normal(ks[2], kv_heads, dt),
                jax.random.normal(ks[3], (batch, seq, cfg.d_model), dt),
                (0.1 * jax.random.normal(ks[4], (cfg.d_model,))).astype(dt))

    q, k, v, x, w = inputs(jax.random.PRNGKey(7))

    @jax.jit
    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))

    def kern(q_, k_, v_):
        return flash_attention_kernel(q_, k_, v_, causal=True,
                                      block_q=cfg.attn_block_q,
                                      block_k=cfg.attn_block_k)

    def out_and_grads(attn):
        def loss(*a):
            out = attn(*a)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.jit(lambda *a: jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(*a))

    (_, out_k), g_k = out_and_grads(kern)(q, k, v)
    (_, out_r), g_r = out_and_grads(mha_reference)(q, k, v)
    errs = {"attn_out": float(rel(out_k, out_r)),
            "attn_grad": max(float(rel(a, b)) for a, b in zip(g_k, g_r))}
    errs["rms_norm"] = float(rel(jax.jit(rms_norm)(x, w),
                                 jax.jit(rms_norm_reference)(x, w)))
    for name, err in errs.items():
        if not err <= KERNEL_TOL[name]:
            raise AssertionError(
                f"kernel check {name}: error {err:.3g} over tolerance "
                f"{KERNEL_TOL[name]} ({errs})")
    return errs


def train_loop(config: dict) -> None:
    """examples/train_sft.py's loop at the width asked for: adamw,
    donated state, one fixed batch, every step synced."""
    import jax
    import optax

    from ray_tpu import train as rt_train
    from ray_tpu.models import Transformer, TransformerConfig
    from ray_tpu.parallel import MeshSpec, param_shardings
    from ray_tpu.parallel.sharding import batch_sharding

    _assert_exited(config["prev_pids"])
    cfg = TransformerConfig(**config["model"])
    batch, seq = config["batch"], config["seq"]
    devices = jax.devices()
    platform = devices[0].platform
    errs = _kernel_checks(cfg, batch, seq)

    mesh = shardings = None
    if config["mesh"]:
        mesh = MeshSpec(**config["mesh"]).build(devices)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    if mesh is not None:
        tokens = jax.device_put(tokens, batch_sharding(mesh))
    opt = optax.adamw(config["lr"])

    def build(cfg_):
        model = Transformer(cfg_, mesh=mesh)

        def step(p, s, b):
            loss, g = jax.value_and_grad(model.loss)(p, b)
            updates, s = opt.update(g, s, p)
            return optax.apply_updates(p, updates), s, loss
        return model, jax.jit(step, donate_argnums=(0, 1))

    model, step = build(cfg)
    if mesh is not None:
        shardings = param_shardings(mesh, model.param_logical_axes())
    # sharded from the first byte: no device ever holds the whole model
    params = jax.jit(model.init, out_shardings=shardings)(
        jax.random.PRNGKey(0))
    opt_state = opt.init(params)      # zeros_like keeps each sharding

    def compile_step(step_):
        t0 = time.perf_counter()
        compiled = step_.lower(params, opt_state,
                               {"tokens": tokens}).compile()
        return compiled, time.perf_counter() - t0

    compiled, compile_s = compile_step(step)
    stats = devices[0].memory_stats() or {}
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    if stats.get("bytes_limit") and need > stats["bytes_limit"]:
        # what the runtime leaves is too little without remat: keep the
        # width, recompute the layers, save the attention residuals
        cfg = dataclasses.replace(cfg, remat=True, remat_policy="save_attn")
        model, step = build(cfg)
        compiled, compile_s = compile_step(step)

    custom_calls = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    if platform == "tpu":
        # per layer: two rms_norm, the flash forward and its one backward
        # kernel; the final norm; with remat the two norms again
        want = 7 if cfg.remat else 5
        if custom_calls != want:
            raise AssertionError(
                f"train step HLO holds {custom_calls} tpu_custom_call, "
                f"expected {want}: a kernel was replaced or interpreted")

    losses, step_s = [], []
    for _ in range(config["steps"]):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state,
                                           {"tokens": tokens})
        jax.block_until_ready(loss)
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    if not all(map(math.isfinite, losses)) or not losses[-1] < losses[0]:
        raise AssertionError(f"loss not finite and falling: {losses}")

    per_device = [(d.memory_stats() or {}) for d in devices]
    rt_train.report({
        "pid": os.getpid(), "platform": platform,
        "device_kind": devices[0].device_kind, "device_count": len(devices),
        "chips": os.environ.get("TPU_VISIBLE_CHIPS", ""),
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "remat": cfg.remat, "kernel_errors": errs,
        "custom_calls": custom_calls, "losses": losses,
        "compile_s": compile_s, "step_bytes_needed": need,
        "step_s": step_s[1:],
        "bytes_limit": stats.get("bytes_limit"),
        "peak_bytes_in_use": [m.get("peak_bytes_in_use")
                              for m in per_device],
        "bytes_in_use": [m.get("bytes_in_use") for m in per_device],
    })


def train_phase(cfg, *, chips: int, out_dir: str, mesh: dict = None,
                batch: int = 2, seq: int = 2048, steps: int = 6,
                lr: float = 1e-4, prev_pids=()) -> dict:
    """One worker holding `chips` chips (0: a CPU worker, for the tests)
    takes `steps` adamw steps; returns what it reported."""
    from ray_tpu.train import (JaxConfig, JaxTrainer, RunConfig,
                               ScalingConfig)
    _assert_exited(prev_pids, EXIT_WAIT_S)
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "model": dataclasses.asdict(cfg), "mesh": mesh, "batch": batch,
            "seq": seq, "steps": steps, "lr": lr,
            "prev_pids": list(prev_pids)},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=chips > 0,
                                     chips_per_worker=chips),
        run_config=RunConfig(name=f"smoke_train_{chips}",
                             storage_path=out_dir,
                             worker_poll_timeout=PHASE_TIMEOUT_S),
        backend_config=JaxConfig(distributed=False),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"train phase failed: {result.error}")
    report = result.metrics
    _expect_device(report, chips)
    if mesh:
        _assert_balanced(report["bytes_in_use"], f"training on {mesh}")
    return report


# ------------------------------------------------------------- serve
def serve_phase(cfg, prompts, *, chips: int, replicas: int = 1,
                mesh: dict = None, max_tokens: int = 16,
                name: str = "smoke_llm", prev_pids=()) -> dict:
    """`replicas` engine replicas of `chips` chips each answer all the
    prompts at once, streamed; returns tokens and where each replica
    ran. The deployment is deleted before returning."""
    from ray_tpu import serve
    from ray_tpu.serve import llm

    _assert_exited(prev_pids, EXIT_WAIT_S)
    t0 = time.monotonic()
    handle = llm.serve_llm(
        name=name, model=dataclasses.asdict(cfg), num_replicas=replicas,
        mesh=mesh, seed=0,
        ray_actor_options={"num_tpus": chips} if chips else None)
    try:
        streams = [handle.generate(p, max_tokens=max_tokens,
                                   timeout_s=PHASE_TIMEOUT_S)
                   for p in prompts]
        served = [s.tokens() for s in streams]
        for i, s in enumerate(streams):
            if s.finish_reason not in ("length", "stop"):
                raise AssertionError(
                    f"stream {i} ended with {s.finish_reason!r}")
            if s.failovers:
                raise AssertionError(
                    f"stream {i} failed over {s.failovers} time(s)")
            if len(served[i]) != max_tokens:
                raise AssertionError(
                    f"stream {i} gave {len(served[i])} tokens, "
                    f"asked {max_tokens}")
        stats = handle.stats()
    finally:
        serve.delete(name)
    if len(stats) != replicas:
        raise AssertionError(
            f"{len(stats)} of {replicas} replicas answered engine_stats")
    if sum(st["admitted"] for st in stats) != len(prompts):
        raise AssertionError(
            f"{len(prompts)} prompts were sent and the replicas alive at "
            f"the end admitted {[st['admitted'] for st in stats]}: a "
            f"replica was replaced on the way")
    for st in stats:
        if st["failed"]:
            raise AssertionError(f"engine step failed:\n{st['failed']}")
        _expect_device(st, chips)
        if not st["admitted"]:
            raise AssertionError(f"a replica received no request: {st}")
    held = [tuple(st["chips"]) for st in stats]
    if chips and len(set(held)) != replicas:
        raise AssertionError(f"replicas share chips: {held}")
    if mesh:
        _assert_balanced(stats[0]["bytes_in_use"], f"engine on {mesh}")
    return {"served": served,
            # replica start-up and every first compile are in here
            "seconds": time.monotonic() - t0,
            "replicas": [
        {k: st[k] for k in ("pid", "platform", "device_kind",
                            "device_ids", "chips", "bytes_in_use",
                            "decode_attention", "decode_kernel_steps",
                            "admitted", "tokens")} for st in stats]}


# ------------------------------------------------------------- check
def _paged_attention_check() -> dict:
    """The paged decode kernel against the gather + einsum on this
    process's first device, at the serving cells' shapes: 8 lanes, 16
    query heads over 8 kv heads of 128, 16-token pages of bf16, tables of
    256; ragged lanes, one empty, pages in no order. The CPU suite only
    ever sees the interpreter."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention as pa

    lanes, heads, kvh, hd, page, max_pages, layers = 8, 16, 8, 128, 16, 256, 2
    lengths = [1, 16, 17, 577, 1300, 0, 4096, 640]
    pages = lanes * max_pages + 1
    rng = np.random.default_rng(7)
    pt = (1 + rng.permutation(pages - 1)).reshape(lanes, max_pages)
    for lane, n in enumerate(lengths):
        pt[lane, -(-n // page):] = -1

    @jax.jit
    def inputs(key):
        kq, kk, kv = jax.random.split(key, 3)
        pool = (layers, pages, page, kvh * hd)
        return (jax.random.normal(kq, (lanes, heads, hd), jnp.bfloat16),
                jax.random.normal(kk, pool, jnp.bfloat16),
                jax.random.normal(kv, pool, jnp.bfloat16))

    q, k, v = inputs(jax.random.PRNGKey(11))
    args = (q, k, v, jnp.int32(1), jnp.asarray(pt, jnp.int32),
            jnp.asarray(lengths, jnp.int32))
    if not pa.uses_kernel(hd, page, k.dtype):
        raise AssertionError(
            "the paged decode kernel is not what runs at the serving "
            "cells' shapes on this device")
    got = jax.jit(pa.paged_decode_attention)(*args).astype(jnp.float32)
    want = jax.jit(pa.paged_attention_reference)(*args).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    if not bool(jnp.isfinite(got).all()) or err > KERNEL_TOL["paged_attn"]:
        raise AssertionError(
            f"paged decode attention leaves the einsum by {err:.3g} "
            f"(limit {KERNEL_TOL['paged_attn']})")
    if bool(got[lengths.index(0)].any()):
        raise AssertionError("a lane that holds nothing got an output")
    return {"paged_attn": err}


def _check_served(model_kwargs: dict, prompts, served_runs, prev_pids):
    """Runs on the chip after every replica is gone: the weights again
    from seed 0, Transformer.apply over prompt + served tokens, every
    served token against the argmax at its position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import Transformer, TransformerConfig
    from ray_tpu.serve.llm.engine import EngineCore
    from ray_tpu.util.compile_cache import use_compile_cache

    _assert_exited(prev_pids)
    use_compile_cache()
    cfg = TransformerConfig(**model_kwargs)
    model = Transformer(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    device = jax.devices()[0]

    n_new = len(served_runs[0][0])
    pad = 64 * -(-(max(map(len, prompts)) + n_new) // 64)
    apply = jax.jit(model.apply)
    worst, exact, total = 0.0, 0, 0
    for served in served_runs:
        for prompt, new in zip(prompts, served):
            toks = np.zeros((1, pad), np.int32)
            toks[0, :len(prompt) + len(new)] = list(prompt) + list(new)
            logits = np.asarray(apply(params, jnp.asarray(toks))[0])
            for i, tok in enumerate(new):
                row = logits[len(prompt) + i - 1]
                worst = max(worst, float(row.max() - row[tok]))
                exact += int(row.argmax() == tok)
                total += 1
    if worst > LOGIT_MARGIN:
        raise AssertionError(
            f"a served token trails the teacher-forced argmax by "
            f"{worst:.4f} logits (margin {LOGIT_MARGIN}); {exact}/{total} "
            f"tokens are the argmax exactly")

    # prefill exactly as the engine jits it, smallest bucket
    core = EngineCore(cfg, params, max_batch=1)
    prefill_calls = core._prefill_fn(16).lower(
        params, jnp.zeros((16,), jnp.int32), jnp.int32(5),
        jnp.full((core.max_pages_per_seq,), -1, jnp.int32),
        core._cache).compile().as_text().count(
            'custom_call_target="tpu_custom_call"')
    if device.platform == "tpu" and prefill_calls != 4:
        # two rms_norm and the flash forward per layer, the final norm
        raise AssertionError(
            f"prefill HLO holds {prefill_calls} tpu_custom_call, "
            f"expected 4")
    report = {"pid": os.getpid(), "platform": device.platform,
              "device_kind": device.device_kind,
              "device_count": len(jax.devices()),
              "tokens_checked": total, "exact_argmax": exact,
              "worst_margin": worst, "prefill_custom_calls": prefill_calls,
              # which attention this engine's decode step holds
              "decode_attention": core.device_stats()["decode_attention"]}
    if device.platform == "tpu":
        report["kernel_errors"] = _paged_attention_check()
    return report


def check_phase(cfg, prompts, served_runs, *, chips: int,
                prev_pids=()) -> dict:
    import ray_tpu
    _assert_exited(prev_pids, EXIT_WAIT_S)
    task = ray_tpu.remote(num_tpus=chips, max_retries=0)(_check_served)
    report = ray_tpu.get(task.remote(
        dataclasses.asdict(cfg), [list(p) for p in prompts],
        served_runs, list(prev_pids)), timeout=PHASE_TIMEOUT_S)
    _expect_device(report, chips)
    return report


# ----------------------------------------------------------- helpers
def _assert_balanced(used, what: str) -> None:
    """Per-device bytes of something sharded: similar numbers, not one
    large and the rest small."""
    if max(used) > 1.5 * min(used):
        raise AssertionError(f"{what}: per-device bytes {used} are not "
                             f"balanced, so it is not sharded")


def _assert_exited(pids, timeout: float = 0.0) -> None:
    """Every process that held a chip in an earlier phase is gone, not
    merely told to go. Workers call this before they touch JAX. The
    driver calls it with a timeout before a phase, to verify and not to
    synchronise: it is the scheduler that waits on the process before
    it hands a chip on."""
    def gone(pid):
        try:
            with open(f"/proc/{pid}/stat") as f:    # "pid (comm) state .."
                return f.read().rsplit(")", 1)[1].split()[0] == "Z"
        except FileNotFoundError:
            return True

    deadline = time.monotonic() + timeout
    while not all(map(gone, pids)):
        if time.monotonic() > deadline:
            raise AssertionError(
                f"of pids {list(pids)}, which held the chip in an earlier "
                f"phase, some are alive {timeout:g}s on, as pid "
                f"{os.getpid()} is about to need it")
        time.sleep(0.05)


def _expect_device(report: dict, chips: int) -> None:
    want = "tpu" if chips else "cpu"
    if report["platform"] != want:
        raise AssertionError(
            f"expected platform {want!r}, the worker ran on "
            f"{report['platform']!r} ({report.get('device_kind')})")


def smoke_prompts(vocab_size: int):
    """Fixed prompts of four lengths: two in the smallest prefill
    bucket (16), one each in 64 and 128."""
    import random
    rng = random.Random(0)
    return [[rng.randrange(vocab_size) for _ in range(n)]
            for n in (5, 14, 40, 100)]


def _say(phase: str, report: dict) -> None:
    print(f"[{phase}] platform={report.get('platform')} "
          f"device_kind={report.get('device_kind')} OK "
          f"{json.dumps(report, default=str)}", flush=True)


def _driver_off_jax() -> bool:
    """True while this process has initialised no JAX backend."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return bridge is None or not bridge.backends_are_initialized()


# -------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    try:
        import ray_tpu
        from ray_tpu import native
        from ray_tpu._private.accelerators import detect_num_tpu_chips
        from ray_tpu.models.config import bench_1b
        from ray_tpu.util.compile_cache import compile_cache_dir
    except ImportError as e:
        print(f"chip_smoke.py runs from a ray_tpu checkout: {e}",
              file=sys.stderr)
        return 2
    found = detect_num_tpu_chips()
    if found < args.chips:
        print(f"chip_smoke.py needs {args.chips} TPU chip(s) and this "
              f"machine has {found}; there is no CPU mode. Run it on the "
              f"chip: chiprun -- python chip_smoke.py", file=sys.stderr)
        return 2

    out_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    cache_dir = compile_cache_dir()
    cache_before = (len(os.listdir(cache_dir))
                    if os.path.isdir(cache_dir) else 0)
    # every compile_s below is cold if the cache was empty and warm if
    # an earlier run on this machine filled it
    print(f"chip_smoke: chips={args.chips} detected={found} "
          f"native_wire={native.available()} compile_cache={cache_dir} "
          f"({cache_before} entries before this run: "
          f"{'warm' if cache_before else 'cold'})", flush=True)

    cfg = bench_1b()
    prompts = smoke_prompts(cfg.vocab_size)
    results: dict = {"chips": args.chips, "cache_entries_before":
                     cache_before}
    t_start = time.time()
    ray_tpu.init()
    try:
        held: list = []         # pids that have held a chip so far
        one = train_phase(cfg, chips=1, out_dir=out_dir, prev_pids=held)
        held.append(one["pid"])
        results["train"] = one
        _say("train", one)
        device = {"platform": one["platform"], "kind": one["device_kind"],
                  "count": one["device_count"]}

        if args.chips == 4:
            four = train_phase(cfg, chips=4, out_dir=out_dir,
                               mesh={"dp": 1, "fsdp": 2, "tp": 2},
                               prev_pids=held)
            held.append(four["pid"])
            gaps = [abs(a - b) / abs(a) for a, b in
                    zip(one["losses"], four["losses"])]
            if gaps[0] > MESH_LOSS_RTOL_FIRST or max(gaps) > MESH_LOSS_RTOL:
                raise AssertionError(
                    f"fsdp=2.tp=2 losses {four['losses']} leave the "
                    f"one-chip losses {one['losses']} by {max(gaps):.3g}")
            four["loss_gaps_vs_one_chip"] = gaps
            results["train_mesh"] = four
            _say("train fsdp=2.tp=2", four)
            device["count"] = four["device_count"]

        if args.chips == 4:     # two rounds, so all four get work
            prompts = prompts + [p[::-1] for p in prompts]
        served = serve_phase(cfg, prompts, chips=1, replicas=args.chips,
                             prev_pids=held)
        served_runs = [served["served"]]
        held += [r["pid"] for r in served["replicas"]]
        results["serve"] = served
        _say("serve", dict(served["replicas"][0], replicas=len(
            served["replicas"]), chips_held=[r["chips"] for r in
                                             served["replicas"]],
            seconds=served["seconds"]))

        if args.chips == 4:
            meshed = serve_phase(cfg, prompts, chips=4,
                                 mesh={"dp": 1, "tp": 4},
                                 name="smoke_llm_tp4", prev_pids=held)
            held += [r["pid"] for r in meshed["replicas"]]
            served_runs.append(meshed["served"])
            results["serve_mesh"] = meshed
            _say("serve tp=4", dict(meshed["replicas"][0],
                                    seconds=meshed["seconds"]))

        check = check_phase(cfg, prompts, served_runs, chips=1,
                            prev_pids=held)
        results["check"] = check
        _say("check", check)

        if not _driver_off_jax():
            raise AssertionError(
                "the driver process initialised a JAX backend")
    except BaseException:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr, flush=True)
        return 1
    finally:
        ray_tpu.shutdown()      # waits for every worker process
    results["seconds"] = time.time() - t_start
    with open(os.path.join(out_dir, f"result_{args.chips}chip.json"),
              "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
