"""The second control of a cell whose attention reads what an indexer
chooses: the same program with the selection ignored, against the plain
reference that honours it.

    python3 benchmarks/tools/check_dense.py --workload <cell> --seeds 1,2

The engine is built from the cell's own file with `index_topk` raised to the
deployment's context limit, so every bucket and every table is at or under
it and the class traces its dense programs (causal flash prefill, the latent
decode kernel over every live position) over the same weights and the same
two pools; the check is the cell's own (`serve_cell.check_against_reference`:
3 requests, prefill then 32 decode steps) against `reference_rows` at the
published `index_topk`. One line a seed; the cell's limit has to lie below
the smallest of these, or the check cannot tell selected from dense
attention. No timed window.
"""
import argparse
import json
import os

from _common import ROOT

from benchmarks.harness.cells import load_cell, prepare_device


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    _, cell, cfg, mix = load_cell(a.workload)
    prepare_device(cell, False)
    from benchmarks.harness import traffic
    from benchmarks.harness.serve_cell import Served
    dense = {**cfg, "index_topk": cfg["deployment"]["context_limit"]}
    served, rows = None, []
    for seed in [int(x) for x in a.seeds.split(",")]:
        if served is None:
            served = Served(dense, mix, seed, 51.0)
            served.sz = served.model.sizes(cfg)   # the reference's choice
        else:
            served.seed = seed
            served.engine.core.params = None
            served.engine.core.params = served.weights(seed)
            served.prompts = traffic.prompt_tokens(
                served.requests, served.sz.vocab, seed)
        errors, lens = served.check(mix)
        row = {"seed": seed, "dense_program": min(errors),
               "dense_program_all": errors, "prompts": lens}
        rows.append(row)
        print(json.dumps(row), flush=True)
    served.close()
    print(f"{a.workload}: smallest reading of the dense program over "
          f"{len(rows)} seeds {min(r['dense_program'] for r in rows):.6g}")
    out = os.path.join(ROOT, "chiprun_out", f"check_dense.{a.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    os._exit(0)


if __name__ == "__main__":
    main()
