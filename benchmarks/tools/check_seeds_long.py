"""`check_seeds.py` for a serving cell whose prompts pass 2,144 tokens:

    python3 benchmarks/tools/check_seeds_long.py --workload <cell> \
        --seeds 1,2,... --controls 4

The same two readings. `check_seeds.py` holds its control's sequence in
2,176 positions, which a prompt of this cell's does not fit; here the
control runs first and without an engine, on sequences as long as the
schedule's shortest, median and longest prompt plus the check's decode
steps, padded as the check pads them (to the longest, a multiple of 128):
the reference in fp8 against the reference, the same rows the check
compares. Then the sound runs, which are `check_seeds.serving`'s.
"""
import argparse
import json
import os

from _common import ROOT

import check_seeds
from benchmarks.harness.cells import load_cell, prepare_device


def controls(cfg, mix, seeds):
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.harness import traffic
    from benchmarks.harness.modelcfg import load_model
    from benchmarks.harness.reference import rel_rms
    from benchmarks.harness.weights import make_weights
    model = load_model(cfg)
    sz = model.sizes(cfg)
    steps = int(mix["check_decode_steps"])
    lens = sorted(r.prompt_len for r in traffic.schedule(mix))
    ref_len = -(-(lens[-1] + steps) // 128) * 128
    rows = []
    for seed in seeds:
        params = make_weights(model.weight_shapes(sz), seed)
        for p in (lens[0], lens[len(lens) // 2], lens[-1]):
            toks = np.zeros((ref_len,), np.int32)
            toks[:p + steps] = np.random.default_rng(seed).integers(
                0, sz.vocab, p + steps)
            args = (sz, params, jnp.asarray(toks), jnp.int32(p - 1),
                    steps + 1)
            row = {"seed": seed, "prompt": p, "control": rel_rms(
                model.reference_rows(*args, True),
                model.reference_rows(*args, False))}
            rows.append(row)
            print(json.dumps(row), flush=True)
        del params
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=4,
                    help="how many of the seeds also run the control")
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    _, cell, cfg, mix = load_cell(a.workload)
    prepare_device(cell, bool(a.rehearse))
    if a.rehearse:
        from benchmarks.harness.modelcfg import load_model
        cfg = load_model(cfg).tiny(cfg)
    seeds = [int(x) for x in a.seeds.split(",")]
    control = controls(cfg, mix, seeds[:a.controls])
    sound = check_seeds.serving(cfg, mix, seeds, 0)
    every = [e for r in sound for e in r["sound_all"]]
    print(f"{a.workload}: largest sound over {len(every)} requests of "
          f"{len(sound)} seeds {max(every):.6g} (smallest {min(every):.6g});"
          f" smallest control over {len(control)} sequences of "
          f"{a.controls} seeds "
          f"{min(r['control'] for r in control) if control else 'not run'}")
    out = os.path.join(ROOT, "chiprun_out",
                       f"check_seeds_long.{a.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"sound": sound, "control": control}, f, indent=1)
    os._exit(0)


if __name__ == "__main__":
    main()
