"""How often a traced run's busy time reads above its window:

    python3 benchmarks/tools/window_overhang.py --tree . --workload <cell> --runs 10

Runs `benchmarks/run.py --trace 1` of the checkout at `--tree` again and
again (each run a process of its own, as the driver's are; this one stays
off JAX) and lists, run by run, `device.busy_s`, `device.window_s`, their
difference (above 0: the driver refuses the line as malformed), the device
seconds of the programs in the trace and, where the tree's harness reports
it, the busy time it found outside the window. `--seconds 16` still traces
the cell's 6 s: the overhang is the profiler's start and stop, not the
window's length. Appends to chiprun_out/window_overhang.jsonl.
"""
import argparse
import json
import os
import subprocess
import sys

from _common import ROOT


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=ROOT)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--seconds", type=float, default=16.0)
    ap.add_argument("--seed", type=int, default=3000003401)
    ap.add_argument("--label", default="")
    a = ap.parse_args()
    tree = os.path.abspath(a.tree)
    dest = os.path.join(ROOT, "chiprun_out", "window_overhang.jsonl")
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    for i in range(a.runs):
        done = subprocess.run(
            [sys.executable, os.path.join("benchmarks", "run.py"),
             "--workload", a.workload, "--seed", str(a.seed + i),
             "--seconds", str(a.seconds), "--trace", "1"],
            cwd=tree, capture_output=True, text=True)
        row = {"label": a.label, "workload": a.workload, "seed": a.seed + i,
               "rc": done.returncode}
        try:
            line = json.loads(done.stdout.strip().splitlines()[-1])
            dev = line["device"]
            row.update(
                busy_s=dev["busy_s"], window_s=dev["window_s"],
                busy_less_window_s=dev["busy_s"] - dev["window_s"],
                programs_s=sum(t for _, t in line["programs"].values()),
                busy_outside_window_s=line.get("busy_outside_window_s"),
                correct=line["correct"], failed=line["failed"],
                compiles_in_window=line["compiles_in_window"],
                metrics={k: v["value"] for k, v in line["metrics"].items()})
        except (IndexError, KeyError, ValueError):
            row["stderr"] = done.stderr[-1500:]
        print(json.dumps(row), flush=True)
        with open(dest, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
