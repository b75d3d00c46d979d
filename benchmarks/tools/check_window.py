"""The third control of a cell whose sliding layers keep a ring: the same
program with the window ignored, against the plain reference that honours
it.

    python3 benchmarks/tools/check_window.py --workload <cell> --seeds 1,2

The engine is built from the cell's own file with `sliding_window_size`
raised to the deployment's context limit less a page (the largest window
whose ring a page table holds) and `--lanes` lanes (4: the ring of a lane
is then its whole table, 1,024 pages, and 32 lanes of them would leave the
pool no page), so every sliding layer attends to every causal position over
the same weights; the check is the cell's own
(`serve_cell.check_against_reference`: 3 requests, prefill then 32 decode
steps) against `reference_rows` at the published window. One line a seed;
the cell's limit has to lie below the smallest of these over prompts past
1,024 tokens, or the check cannot tell a window from none. No timed window.
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
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    _, cell, cfg, mix = load_cell(a.workload)
    prepare_device(cell, bool(a.rehearse))
    if a.rehearse:
        from benchmarks.harness.modelcfg import load_model
        cfg = load_model(cfg).tiny(cfg)
    from benchmarks.harness import traffic
    from benchmarks.harness.serve_cell import Served
    dep = cfg["deployment"]
    wide = {**cfg,
            "sliding_window_size": dep["context_limit"] - dep["page_size"],
            "deployment": {**dep, "max_batch": a.lanes}}
    served, rows = None, []
    for seed in [int(x) for x in a.seeds.split(",")]:
        if served is None:
            served = Served(wide, mix, seed, 51.0)
            served.sz = served.model.sizes(cfg)   # the reference's window
        else:
            served.seed = seed
            served.engine.core.params = None
            served.engine.core.params = served.weights(seed)
            served.prompts = traffic.prompt_tokens(
                served.requests, served.sz.vocab, seed)
        errors, lens = served.check(mix)
        row = {"seed": seed, "windowless_program": errors, "prompts": lens}
        rows.append(row)
        print(json.dumps(row), flush=True)
    served.close()
    past = [e for r in rows for e, n in zip(r["windowless_program"],
                                            r["prompts"]) if n > 1024]
    print(f"{a.workload}: smallest reading of the windowless program over "
          f"{len(past)} prompts past 1,024 tokens of {len(rows)} seeds "
          f"{min(past, default=float('nan')):.6g}")
    out = os.path.join(ROOT, "chiprun_out",
                       f"check_window.{a.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    os._exit(0)


if __name__ == "__main__":
    main()
