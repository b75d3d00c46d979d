"""The two readings every `correct` limit is set from, in one process:

    python3 benchmarks/tools/check_seeds.py --workload <cell> --seeds 1,2,...

For each seed: the error of the system under test against the plain float32
reference (sound runs), and the error of the control, the reference computed
in fp8 put in the system's place. Prints one line per seed and the largest
sound error beside the smallest control error. No timed window.
"""
import argparse
import json
import os

from _common import ROOT

from benchmarks.harness.cells import load_cell, prepare_device


def serving(cfg, mix, seeds, controls):
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.harness import traffic
    from benchmarks.harness.reference import rel_rms
    from benchmarks.harness.serve_cell import Served
    served, rows = None, []
    for n, seed in enumerate(seeds):
        if served is None:
            served = Served(cfg, mix, seed, 51.0)
        else:
            served.seed = seed
            served.engine.core.params = None
            served.engine.core.params = served.weights(seed)
            served.prompts = traffic.prompt_tokens(
                served.requests, served.sz.vocab, seed)
        errors, lens = served.check(mix)
        row = {"seed": seed, "sound": max(errors), "sound_all": errors,
               "prompts": lens}
        if n < controls:
            sz, params = served.sz, served.engine.core.params
            reference_rows = served.model.reference_rows
            steps = int(mix["check_decode_steps"])
            toks = np.zeros((2176,), np.int32)
            p = lens[0]
            toks[:p + steps] = np.random.default_rng(seed).integers(
                0, sz.vocab, p + steps)
            args = (sz, params, jnp.asarray(toks), jnp.int32(p - 1),
                    steps + 1)
            row["control"] = rel_rms(reference_rows(*args, True),
                                     reference_rows(*args, False))
        rows.append(row)
        print(json.dumps(row), flush=True)
    served.close()
    return rows


def training(cfg, mix, seeds, controls):
    from benchmarks.harness import train_cell
    from benchmarks.harness.modelcfg import load_model
    from benchmarks.harness.weights import make_weights
    model = load_model(cfg)
    sz = model.sizes(cfg)
    program = model.train_model(cfg, int(mix["seq_len"]))
    rows = []
    for n, seed in enumerate(seeds):
        params = make_weights(model.weight_shapes(sz), seed)
        seq = train_cell.make_tokens(mix, sz.vocab, seed)[0, 0]
        out = train_cell.check_against_reference(program, model, sz, params,
                                                 seq, cfg, print)
        row = {"seed": seed, "sound_loss": out["loss_error"],
               "sound_grad": out["grad_error"]}
        if n < controls:
            c = train_cell.check_against_reference(
                program, model, sz, params, seq, cfg, print, control=True)
            row.update(control_loss=c["loss_error"],
                       control_grad=c["grad_error"])
        rows.append(row)
        print(json.dumps(row), flush=True)
        del params
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="how many of the seeds also run the control")
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    _, cell, cfg, mix = load_cell(a.workload)
    prepare_device(cell, bool(a.rehearse))
    if a.rehearse:
        from benchmarks.harness.modelcfg import load_model
        cfg = load_model(cfg).tiny(cfg)
    seeds = [int(x) for x in a.seeds.split(",")]
    if mix["kind"] == "train_steps":
        rows = training(cfg, mix, seeds, a.controls)
    else:
        rows = serving(cfg, mix, seeds, a.controls)
    keys = [k for k in rows[0] if k.startswith("sound") and k != "sound_all"]
    for k in keys:
        c = k.replace("sound", "control")
        sound = max(r[k] for r in rows)
        control = [r[c] for r in rows if c in r]
        print(f"{a.workload}: largest {k} over {len(rows)} seeds {sound:.6g}"
              f"; smallest {c} over {len(control)} seeds "
              f"{min(control) if control else 'not run'}")
    out = os.path.join(ROOT, "chiprun_out", f"check_seeds.{a.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    os._exit(0)


if __name__ == "__main__":
    main()
