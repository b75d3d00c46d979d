"""One `benchmarks/run.py` run whose trace is kept, then the trace's device
time by region with the largest operations of each (`benchmarks/tools/
regions.py --ops`), written under `chiprun_out/`:

    chiprun -- python tools/keep_trace.py --workload <cell> --seed <n> \
        [--seconds 30] [--program jit__step ...]

`run.py` deletes `.bench_out/` when it ends and leaves by `os._exit`; here
both are put out of action for the run. Chip only; not a measurement of
record.
"""
import argparse
import os
import runpy
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", default="3000000001")
    ap.add_argument("--seconds", default="30")
    ap.add_argument("--program", nargs="*", default=["jit__step"])
    ap.add_argument("--top", default="14")
    a = ap.parse_args()
    shutil.rmtree(os.path.join(ROOT, ".bench_out"), ignore_errors=True)
    shutil.rmtree = lambda *args, **kw: None
    leave = os._exit

    def stay(code):
        raise SystemExit(code)
    os._exit = stay
    sys.argv = ["run.py", "--workload", a.workload, "--seed", a.seed,
                "--seconds", a.seconds, "--trace", "1"]
    try:
        runpy.run_path(os.path.join(ROOT, "benchmarks", "run.py"),
                       run_name="__main__")
    except SystemExit:
        pass
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    # the engine's process still holds the chip: the reading needs none
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    with open(os.path.join(out, f"regions.{a.workload}.txt"), "w") as f:
        subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks/tools/regions.py"),
             os.path.join(ROOT, ".bench_out", "trace", a.workload),
             *[x for p in a.program for x in ("--program", p)], "--ops",
             "--top", a.top],
            stdout=f, stderr=subprocess.STDOUT, env=env, check=False)
    leave(0)


if __name__ == "__main__":
    main()
