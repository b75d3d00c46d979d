"""Sweep bench.py-shaped configs on the real chip (one per process).

Usage: python tools/bench_sweep.py <block_q> <block_k> <remat_policy> \
           [batch] [loss_chunk]     (remat_policy "none" = remat off)
Prints one result line; run via the loop in the repo makefile or by hand.
"""
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from bench import device_peak_flops  # noqa: E402


def main():
    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.config import bench_1b
    from ray_tpu.util.compile_cache import use_compile_cache

    use_compile_cache()
    peak = device_peak_flops(jax.devices()[0])
    bq, bk = int(sys.argv[1]), int(sys.argv[2])
    policy = sys.argv[3]
    batch = int(sys.argv[4]) if len(sys.argv) > 4 else 8
    loss_chunk = int(sys.argv[5]) if len(sys.argv) > 5 else 512
    seq, steps = 2048, 10

    cfg = dataclasses.replace(
        bench_1b(), remat=policy != "none",
        remat_policy=policy if policy != "none" else "full",
        loss_chunk=loss_chunk, attn_block_q=bq, attn_block_k=bk)

    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adamw(1e-4)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)

    def _step(p, s, batch_):
        loss, g = jax.value_and_grad(model.loss)(p, batch_)
        updates, s = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s, loss

    train_step = jax.jit(_step, donate_argnums=(0, 1))
    for _ in range(2):      # compile, then one warm step
        params, opt_state, loss = train_step(params, opt_state,
                                             {"tokens": tokens})
        jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state,
                                             {"tokens": tokens})
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    tok_per_s = batch * seq * steps / dt
    mfu = tok_per_s * cfg.flops_per_token() / peak
    print(json.dumps({
        "bq": bq, "bk": bk, "policy": policy, "batch": batch,
        "loss_chunk": loss_chunk,
        "tok_s": round(tok_per_s, 1), "mfu": round(mfu, 4),
        "step_ms": round(dt / steps * 1e3, 1)}))


if __name__ == "__main__":
    main()
