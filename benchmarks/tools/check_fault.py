"""Does a serving cell's `correct` see a fault? The cell's own check
(`Served.check`: the served path's logits against `reference_rows`) on a
PROGRAM built with fields of its config replaced, the reference sound:

    python3 benchmarks/tools/check_fault.py --workload <cell> --seed 1 \
        --set routed_scaling_factor=0

`--set name=value` (JSON values; several allowed) replaces fields of what
the model module's `program_config` returns; none given reads the sound
program. Prints the requests' errors beside the cell's limit: a limit is
worth what the faults it fails are. One seed a process, no timed window.
"""
import argparse
import dataclasses
import json

import _common  # noqa: F401

from benchmarks.harness.cells import load_cell, prepare_device
from benchmarks.harness.modelcfg import load_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="FIELD=VALUE")
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    fault = {k: json.loads(v) for k, v in
             (item.split("=", 1) for item in a.set)}
    _, cell, cfg, mix = load_cell(a.workload)
    prepare_device(cell, bool(a.rehearse))
    model = load_model(cfg)
    if a.rehearse:
        cfg = model.tiny(cfg)
    sound = model.program_config

    def faulted(cfg, max_seq_len, **extra):
        return dataclasses.replace(sound(cfg, max_seq_len, **extra), **fault)

    model.program_config = faulted
    from benchmarks.harness.serve_cell import Served
    served = Served(cfg, mix, a.seed, 51.0)
    built = served.engine.core.model.config
    assert all(getattr(built, k) == v for k, v in fault.items()), built
    errors, lens = served.check(mix)
    limit = cfg["reference"]["limit"]
    print(json.dumps({"workload": a.workload, "seed": a.seed, "fault": fault,
                      "errors": errors, "prompts": lens, "limit": limit,
                      "seen": min(errors) > limit}), flush=True)
    served.close()


if __name__ == "__main__":
    main()
