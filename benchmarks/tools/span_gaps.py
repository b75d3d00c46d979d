"""A serving cell's device idle time by the program's own spans, whole:

    python3 benchmarks/tools/span_gaps.py --workload <cell> --seconds 51

One engine, one warm-up, nothing patched (`serve_cell.instrument()` is not
used): an untraced window, a traced one, an untraced one. Prints every piece
of `harness/spans.py`'s split of the trace's idle time beside
`xplane.idle_gaps`' total (they must add up), the share that fell outside
every span, the traced window and the device's busy time in it as `run.py`
reads them (`xplane.traced_window`: the marker span, and what the device did
inside it), the engine's counters and `slow_steps`, and each window's tokens
per second (what the profiler costs while it is on). Writes
chiprun_out/span_gaps.<cell>.json.
"""
import argparse
import json
import os

from _common import ROOT

from benchmarks.harness.cells import load_cell, prepare_device


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    _, cell, cfg, mix = load_cell(a.workload)
    prepare_device(cell, bool(a.rehearse))
    if a.rehearse:
        from benchmarks.harness.modelcfg import load_model
        cfg = load_model(cfg).tiny(cfg)
    from benchmarks.harness import spans, xplane
    from benchmarks.harness.serve_cell import Served
    served = Served(cfg, mix, a.seed, a.seconds)
    trace_dir = os.path.join(ROOT, ".bench_out", "span_gaps", cell["name"])
    out = {"workload": cell["name"], "tokens_per_s": []}
    for traced in (False, True, False):
        w = served.window(mix, a.seconds, trace_dir if traced else None)
        out["tokens_per_s"].append(
            [traced, w["tokens_in_window"] / a.seconds, w["failed"]])
        if traced:
            path = xplane.find_xplane(w["traced"]["dir"])
            trace = xplane.load(path)
            r = spans.read(trace, path)
            steps = len(r.named(spans.DISPATCH))
            win = xplane.traced_window(trace)
            out.update(
                traced_s=win.window_s, busy_s=win.busy_s, idle_s=r.idle_s,
                pieces_s=sum(r.gaps.values()),
                outside_share=(r.gaps.get(spans.OUTSIDE, 0.0)
                               / (r.idle_s or 1.0)),   # no device on a CPU
                decode_steps=steps,
                tokens_per_s_traced_span=(
                    sum(int(s.stats["lanes"])
                        for s in r.named(spans.DISPATCH)
                        if win.lo <= s.start < win.hi) / win.window_s),
                gaps_ms_per_step={k: 1e3 * v / steps for k, v in sorted(
                    r.gaps.items(), key=lambda kv: -kv[1])})
    stats = served.engine.engine_stats()
    served.close()
    out["counters"] = {k: v for k, v in stats.items()
                       if type(v) is int and k != "pid"}
    out["slow_steps"] = stats["slow_steps"]
    print(json.dumps(out), flush=True)
    dest = os.path.join(ROOT, "chiprun_out", f"span_gaps.{cell['name']}.json")
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    os._exit(0)


if __name__ == "__main__":
    main()
