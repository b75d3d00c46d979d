"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, the model module it names, its traffic mix and its
metric readers are files found by the names BENCHMARK.json gives (see
benchmarks/README.md); nothing of a cell or an architecture is in this file. The last line of standard output is the result. Without a
TPU holding the chips the cell asks for, the run fails and prints no result;
`--rehearse 1` walks the same control flow at a tiny size on the CPU and
marks its output as not a measurement.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up counts from the first line

import argparse                     # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_metric(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, group: str, workload: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--samples-out", default=None,
                    help="write the run's raw samples here (diagnosis)")
    args = ap.parse_args()

    from benchmarks.harness import modelcfg
    from benchmarks.harness.cells import load_cell, prepare_device, timed
    from benchmarks.harness.peaks import NoAccelerator
    try:
        bench, cell, cfg, mix = load_cell(args.workload)
    except KeyError as e:
        log(str(e))
        return 2
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    args.trace_dir = os.path.join(ROOT, ".bench_out", "trace", cell["name"])
    shutil.rmtree(args.trace_dir, ignore_errors=True)
    try:
        # importing JAX and its first sight of the chips: 8-14 s of the
        # runtime's own, moving by seconds between runs of one machine and
        # nothing a program can shorten. Logged, and not counted as set-up.
        peaks, acquire_s = timed(prepare_device, cell, bool(args.rehearse))
    except NoAccelerator as e:
        log(f"no measurement: {e}")
        return 3
    model = modelcfg.load_model(cfg)
    if args.rehearse:
        cfg = model.tiny(cfg)
    import jax
    device = jax.devices()[0]
    log(f"set-up: acquiring the device {acquire_s:.2f} s (not counted), "
        f"{time.perf_counter() - T_START - acquire_s:.2f} s of imports; "
        f"model module {cfg['model']}")

    if mix["kind"] == "train_steps":
        from benchmarks.harness import train_cell as driver
    else:
        from benchmarks.harness import serve_cell as driver
    result = driver.run(cell, cfg, mix, args, T_START + acquire_s, log)

    run = {"result": result, "samples": result["samples"], "cell": cell,
           "cfg": cfg, "mix": mix, "peaks": peaks, "trace": None,
           "model": model, "sizes": model.sizes(cfg),
           "seconds": result["window_s"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": int(cell["chips"]),
           "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": dev,
            "compiles_in_window": result["compiles_in_window"],
            "device_acquire_s": acquire_s,
            "workload": cell["name"], "seed": args.seed,
            "window_s": result["window_s"]}
    if args.trace and result.get("traced"):
        from benchmarks.harness import xplane
        trace = xplane.load(xplane.find_xplane(result["traced"]["dir"]))
        run["trace"] = trace
        # the window is the span the harness marked inside the trace, and
        # what is summed over it (busy, the breakdown) is cut to it
        w = xplane.traced_window(trace)
        result["traced"].update(window_s=w.window_s, lo=w.lo, hi=w.hi)
        dev["busy_s"], dev["window_s"] = w.busy_s, w.window_s
        # what the profiler recorded before and after the window: what a
        # busy time taken over the whole trace would add to `busy_s`
        line["busy_outside_window_s"] = xplane.busy_seconds(trace) - w.busy_s
        steppers = [name for name, evs in trace.host.items()
                    if any(e.name in ("bench.engine_step", "bench.train_step")
                           for e in evs)]
        line["breakdown"] = {
            "device_ops": xplane.top(xplane.op_times(trace, w.lo, w.hi)),
            "idle_gaps": xplane.top(xplane.attribute_gaps(
                trace, threads=steppers or None, lo=w.lo, hi=w.hi))}
        line["programs"] = {k: [len(v), sum(v)] for k, v in
                            xplane.program_times(trace).items()}
    if args.samples_out:
        with open(args.samples_out, "w") as f:
            json.dump(result["samples"], f)
    group = "per_layer" if args.trace else "end_to_end"
    if not args.rehearse:
        for m in metrics_of(bench, group, cell["name"]):
            value = load_metric(m["name"]).read(run)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
    else:
        line["rehearsal"] = True
        line["not_a_measurement"] = (
            "tiny sizes on the CPU: control flow only, no metric is reported")
        line["correct"] = None
    shutil.rmtree(os.path.join(ROOT, ".bench_out"), ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)      # daemon threads of the engine's stream must not linger
