"""A training cell: steps of the program's loss (`train_model` of the
configuration's model module) + AdamW jitted as `bench.py` jits them
(donated state, bf16 weights and optimizer state), on the benchmark's own
weights and tokens, with the first sequence's loss and gradient compared to
the module's plain reference before the optimizer exists."""
from __future__ import annotations

import math
import time

from benchmarks.harness.modelcfg import load_model

NOW = time.perf_counter


def make_tokens(mix: dict, vocab: int, seed: int):
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             (int(seed) >> 31) + 7)
    shape = (int(mix["distinct_batches"]), int(mix["batch"]),
             int(mix["seq_len"]))
    return jax.jit(lambda k: jax.random.randint(k, shape, 0, vocab))(key)


def compare_fn(program, model, sz, control: bool = False):
    """One program: the system's loss and gradient on one sequence
    (`program.loss`), the reference's on the same (`model.loss_fn`), and
    what separates them. With `control` the reference in fp8 stands in the
    system's place."""
    import jax
    import jax.numpy as jnp
    from benchmarks.harness.reference import fp8_round

    def compare(params, seq):
        if control:
            loss_p, g_p = jax.value_and_grad(
                lambda p: model.loss_fn(sz, p, seq, fp8_round,
                                        remat=True))(params)
        else:
            loss_p, g_p = jax.value_and_grad(program.loss)(
                params, {"tokens": seq[None]})
        loss_r, g_r = jax.value_and_grad(
            lambda p: model.loss_fn(sz, p, seq, remat=True))(params)
        f32 = jnp.float32
        num = sum(jnp.sum(jnp.square(a.astype(f32) - b.astype(f32)))
                  for a, b in zip(jax.tree_util.tree_leaves(g_p),
                                  jax.tree_util.tree_leaves(g_r)))
        den = sum(jnp.sum(jnp.square(b.astype(f32)))
                  for b in jax.tree_util.tree_leaves(g_r))
        return (loss_p.astype(f32), loss_r, jnp.sqrt(num / den),
                jnp.sqrt(den))
    return jax.jit(compare)


def check_against_reference(program, model, sz, params, seq, cfg, log,
                            control: bool = False) -> dict:
    compare = compare_fn(program, model, sz, control)
    loss_p, loss_r, grad_err, grad_norm = (
        float(x) for x in compare(params, seq))
    ref = cfg["reference"]
    out = {"loss": loss_p, "reference_loss": loss_r,
           "loss_error": abs(loss_p - loss_r), "grad_error": grad_err,
           "reference_grad_norm": grad_norm,
           "grad_limit": ref["grad_limit"]}
    log(f"reference check{' (fp8 control)' if control else ''}: loss "
        f"{loss_p:.6f} against {loss_r:.6f}, |difference| "
        f"{out['loss_error']:.3e} (not judged); relative error of the "
        f"gradient {grad_err:.5f} (limit {ref['grad_limit']}); "
        f"reference gradient norm {grad_norm:.5f}")
    out["ok"] = (ref["grad_limit"] is not None
                 and grad_err <= ref["grad_limit"]
                 and math.isfinite(loss_p) and math.isfinite(grad_err))
    return out


def compile_step(program, mix: dict, params, tokens):
    """The train step ahead of time, so its memory_analysis() is to hand."""
    import jax
    import optax
    opt = optax.adamw(float(mix["optimizer"]["learning_rate"]))
    opt_state = jax.jit(opt.init)(params)

    def _step(p, s, batch_):
        loss, g = jax.value_and_grad(program.loss)(p, batch_)
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s, loss

    step = jax.jit(_step, donate_argnums=(0, 1))
    compiled = step.lower(params, opt_state, {"tokens": tokens[0]}).compile()
    return compiled, opt_state


def run(cell: dict, cfg: dict, mix: dict, args, t_start: float,
        log) -> dict:
    import jax
    from benchmarks.harness.cells import CompileCounter
    from benchmarks.harness.weights import make_weights
    from benchmarks.harness.xplane import WINDOW

    model = load_model(cfg)
    sz = model.sizes(cfg)
    seconds = float(args.seconds)
    b, s = int(mix["batch"]), int(mix["seq_len"])
    program = model.train_model(cfg, s)
    params = make_weights(model.weight_shapes(sz), args.seed)
    tokens = make_tokens(mix, sz.vocab, args.seed)
    check = check_against_reference(program, model, sz, params, tokens[0, 0],
                                    cfg, log)
    step, opt_state = compile_step(program, mix, params, tokens)
    mem_an = step.memory_analysis()
    n_batches = tokens.shape[0]
    batches = [{"tokens": tokens[i]} for i in range(n_batches)]
    losses = []
    for i in range(int(mix["warmup_steps"])):
        params, opt_state, loss = step(params, opt_state, batches[i % n_batches])
        jax.block_until_ready(loss)
    compiles = CompileCounter()
    setup_s = NOW() - t_start
    compiles.start()
    traced, trace_steps = None, int(mix.get("trace", {}).get("steps", 5))
    t0 = NOW()
    done = 0
    while True:
        params, opt_state, loss = step(params, opt_state,
                                       batches[len(losses) % n_batches])
        losses.append(loss)
        if len(losses) > 1:
            jax.block_until_ready(losses[-2])   # one step stays in flight
            done = len(losses) - 1
        now = NOW()
        if now - t0 >= seconds:
            break
        if args.trace and traced is None and now - t0 >= 0.3 * seconds:
            jax.block_until_ready(loss)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(args.trace_dir, profiler_options=opts)
            stalled_from = NOW()
            # the traced window: from the sync above to the last step's
            with jax.profiler.TraceAnnotation(WINDOW):
                for _ in range(trace_steps):
                    with jax.profiler.TraceAnnotation("bench.train_step"):
                        params, opt_state, loss = step(
                            params, opt_state,
                            batches[len(losses) % n_batches])
                        losses.append(loss)
                        jax.block_until_ready(loss)
            jax.profiler.stop_trace()
            traced = {"steps": trace_steps, "dir": args.trace_dir,
                      "stall_s": NOW() - stalled_from}
    jax.block_until_ready(losses[-1])
    elapsed = NOW() - t0
    in_window_compiles = compiles.stop()
    values = [float(x) for x in losses]
    bad = sum(1 for v in values if not math.isfinite(v))
    mem = jax.devices()[0].memory_stats() or {}
    steps = len(values)
    log(f"steps in window {steps} ({steps * b * s} tokens in {elapsed:.3f} "
        f"s), loss {values[0]:.4f} -> {values[-1]:.4f}, non-finite {bad}, "
        f"compilations in window {in_window_compiles}")
    untraced_s = elapsed - (traced["stall_s"] if traced else 0.0)
    untraced_steps = steps - (traced["steps"] if traced else 0)
    return {
        "correct": bool(check["ok"] and bad == 0),
        "attempted": steps + 1, "failed": bad + (0 if check["ok"] else 1),
        "setup_s": setup_s, "window_s": elapsed,
        "compiles_in_window": in_window_compiles,
        "reference_check": check,
        "memory_peak_bytes": mem.get("peak_bytes_in_use"),
        "samples": {"tokens_in_window": steps * b * s,
                    "tokens_per_s_untraced": untraced_steps * b * s
                    / untraced_s,
                    "batch": b, "seq_len": s,
                    "step_program_bytes": (
                        mem_an.argument_size_in_bytes
                        + mem_an.output_size_in_bytes
                        + mem_an.temp_size_in_bytes
                        - mem_an.alias_size_in_bytes),
                    "losses": values},
        "traced": traced,
    }
