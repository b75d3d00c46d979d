"""Headline benchmark: LLM training throughput on one TPU chip.

Prints ONE JSON line: tokens/sec/chip on the ~1B dense decoder of
`models.config.bench_1b` (bf16, flash-attention Pallas kernels, adamw),
plus achieved MFU and the device it ran on. `vs_baseline` is MFU / 0.35
— the reference publishes no tokens/sec number (BASELINE.md: the 35% MFU
target is the driver-supplied north star), so >=1.0 means the target is
met. There is no CPU mode: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import json
import sys
import time

# Peak dense bf16 FLOP/s per chip, keyed by `jax.Device.device_kind`.
# Source: Google Cloud TPU documentation, system architecture pages.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,      # v5e
    "TPU v5": 459e12,           # v5p
    "TPU v6 lite": 918e12,      # v6e
}


def device_peak_flops(device) -> float:
    """Peak of the chip the benchmark runs on; a device that is not in
    the table is an error, not a default."""
    if device.platform != "tpu":
        sys.exit(f"bench.py needs a TPU; JAX found {device.platform!r} "
                 f"({device.device_kind}). Run it through the chip tool.")
    if device.device_kind not in PEAK_FLOPS:
        sys.exit(f"no peak FLOP/s listed for device_kind "
                 f"{device.device_kind!r}; add it to bench.PEAK_FLOPS "
                 f"with its source")
    return PEAK_FLOPS[device.device_kind]


def main():
    import jax
    import optax

    from ray_tpu.models import Transformer
    from ray_tpu.models.config import bench_1b
    from ray_tpu.util.compile_cache import use_compile_cache

    use_compile_cache()
    device = jax.devices()[0]
    peak = device_peak_flops(device)
    cfg = bench_1b()
    batch, seq, steps = 2, 2048, 20

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

    # donate params+opt state: avoids double-buffering ~6 GB on-chip
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
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tok_per_s, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(mfu / 0.35, 4),
        "mfu": round(mfu, 4),
        "params": cfg.num_params(),
        "device": {"platform": device.platform,
                   "kind": device.device_kind,
                   "count": len(jax.devices())},
    }))


if __name__ == "__main__":
    main()
