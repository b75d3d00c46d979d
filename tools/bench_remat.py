"""What keeping one more value of a rematted layer buys on the chip: the
training cell's step (`benchmarks/harness/train_cell.compile_step` on
`mistral-7b-v0.1-1chip`'s widths, 2 x 4096 tokens, bf16 AdamW) compiled
under a `remat_policy`, then timed. The argument is a policy as users write
it (a rung of `models/transformer.py`'s REMAT_RUNGS, or "auto", the default:
the rung the model takes from the step's shapes), or a list of checkpoint
names to keep that is no rung. Prints the rung that ran and the bytes it
keeps (`Transformer.remat_plan`, no compile needed), what every rung would
keep at these shapes, the sum the benchmark reads from `memory_analysis()`
(arguments + outputs + temporaries - aliased), its `peak_memory_in_bytes`,
the chip's `bytes_limit` and milliseconds a step. Chip only, one policy a
process (a step that does not fit takes its process with it):

    chiprun -- python tools/bench_remat.py auto
    chiprun -- python tools/bench_remat.py save_attn_qkv
    chiprun -- python tools/bench_remat.py save_attn_stream_up
    chiprun -- python tools/bench_remat.py attn_q,attn_k,attn_v,mlp_gate

The names are the ladder's (REMAT_LADDER: ATTN_RESIDUAL_NAMES of
`ops/attention.py`, then ATTN_INPUT_NAMES, ATTN_STREAM_NAME and MLP_NAMES).
What PRs 40 and 45 read with it is in PERF.md section 6.
"""
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from benchmarks.harness import train_cell
from benchmarks.harness.modelcfg import load_config, load_model
from benchmarks.harness.weights import make_weights
from ray_tpu.models import Transformer, transformer
from ray_tpu.util.compile_cache import use_compile_cache

SEED = 3000040900
STEPS = 10


def main() -> int:
    policy = sys.argv[1]
    if jax.default_backend() != "tpu":
        print("no TPU: nothing here is a measurement off the chip")
        return 3
    use_compile_cache()
    cfg = load_config("mistral-7b-v0.1-1chip")
    with open(os.path.join(os.path.dirname(train_cell.__file__), os.pardir,
                           "traffic", "train.packed4k.json")) as f:
        mix = json.load(f)
    model = load_model(cfg)
    sz = model.sizes(cfg)
    program = model.train_model(cfg, int(mix["seq_len"]))
    if policy != transformer.REMAT_AUTO and \
            policy not in transformer.REMAT_SAVED_NAMES:
        transformer.REMAT_SAVED_NAMES[policy] = tuple(policy.split(","))
    program = Transformer(dataclasses.replace(program.config,
                                              remat_policy=policy))
    batch_tokens = int(mix["batch"]) * int(mix["seq_len"])
    rung, kept = program.remat_plan(batch_tokens)
    params = make_weights(model.weight_shapes(sz), SEED)
    tokens = train_cell.make_tokens(mix, sz.vocab, SEED)
    step, opt_state = train_cell.compile_step(program, mix, params, tokens)
    mem = step.memory_analysis()
    out = {"policy": policy, "rung": rung, "kept_gb": kept / 1e9,
           "rungs_kept_gb": {
               r: transformer.remat_kept_bytes(program.config, r,
                                               batch_tokens) / 1e9
               for r in transformer.REMAT_RUNGS},
           "sum_gb": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                      + mem.temp_size_in_bytes
                      - mem.alias_size_in_bytes) / 1e9,
           "peak_gb": mem.peak_memory_in_bytes / 1e9}
    batches = [{"tokens": tokens[i]} for i in range(tokens.shape[0])]
    for i in range(3 + STEPS):
        if i == 3:
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state,
                                       batches[i % len(batches)])
    jax.block_until_ready(loss)
    out["step_ms"] = (time.perf_counter() - t0) / STEPS * 1e3
    out["loss"] = float(loss)
    out["bytes_limit"] = (jax.devices()[0].memory_stats()
                          or {}).get("bytes_limit")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
