"""The host's cycle a decode dispatch in a serving cell, from one traced
window:

    python3 benchmarks/tools/host_cycle.py --workload <cell> --seed <n>

One engine, one warm-up, one window with the cell's traced part, nothing
patched. Prints the step thread's spans by name as milliseconds a decode
dispatch (their summed durations over the `engine.decode_dispatch` spans),
the cycle they make up (`engine.step` less the wait in
`engine.fetch_tokens`, plus `engine.ingest` and `engine.yield`: what the
thread does between reading one step's tokens and handing over the next
step but one), what the `stream.publish` spans carry (`frames`, `records`,
and how many spans read each ratio of the two; a program from before PR 59
writes no `records`), the device's idle time by span and by length of gap,
the part of it within 30 ms after an admission, and the engine's pipeline
counters. Appends the line to chiprun_out/host_cycle.jsonl. ROADMAP S4's
numbers are this tool's.
"""
import argparse
import json
import os

from _common import ROOT

from benchmarks.harness.cells import load_cell, prepare_device

GAP_EDGES_MS = (0.2, 1.0, 5.0)


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
    w = served.window(mix, a.seconds, os.path.join(
        ROOT, ".bench_out", "host_cycle", cell["name"]))
    path = xplane.find_xplane(w["traced"]["dir"])
    trace = xplane.load(path)
    r = spans.read(trace, path)
    win = xplane.traced_window(trace)
    steps = len(r.named(spans.DISPATCH))
    per_step = {}
    for s in r.spans:
        n, t = per_step.get(s.name, (0, 0.0))
        per_step[s.name] = (n + 1, t + s.dur)
    ms = {k: 1e3 * t / steps for k, (n, t) in per_step.items()}
    publish = r.named(spans.PUBLISH)
    ratios = {}
    for s in publish:
        key = f"{s.stats.get('records', '-')}/{s.stats.get('frames', '-')}"
        ratios[key] = ratios.get(key, 0) + 1
    gaps = xplane.idle_gaps(trace)
    bins = [[0, 0.0] for _ in range(len(GAP_EDGES_MS) + 1)]
    admitted = [p.start for p in r.named(spans.PREFILL)]
    after_admission = [0, 0.0]
    for lo, hi in gaps:
        d = 1e3 * (hi - lo)
        b = bins[sum(d >= e for e in GAP_EDGES_MS)]
        b[0], b[1] = b[0] + 1, b[1] + d
        if any(lo - 0.030 <= t <= hi for t in admitted):
            after_admission[0] += 1
            after_admission[1] += d
    stats = served.engine.engine_stats()
    served.close()
    out = {
        "workload": cell["name"], "seed": a.seed,
        "tokens_per_s": w["tokens_in_window"] / a.seconds,
        "failed": w["failed"], "traced_s": win.window_s,
        "busy_s": win.busy_s,
        # no device on a CPU: a rehearsal's window holds no busy time
        "idle_share": 1.0 - win.busy_s / win.window_s,
        "decode_steps": steps, "prefills": len(admitted),
        "cycle_ms_per_dispatch": (
            ms.get(spans.STEP, 0.0) - ms.get(spans.FETCH, 0.0)
            + ms.get(spans.INGEST, 0.0) + ms.get("engine.yield", 0.0)),
        "span_ms_per_dispatch": {
            k: [per_step[k][0], round(v, 4)]
            for k, v in sorted(ms.items(), key=lambda kv: -kv[1])},
        "publish": {
            "spans": len(publish),
            "frames": sum(int(s.stats.get("frames", 0)) for s in publish),
            "records": sum(int(s.stats.get("records", 0)) for s in publish),
            "spans_by_records_over_frames": dict(sorted(
                ratios.items(), key=lambda kv: -kv[1])[:6])},
        "gaps_ms_per_step": {
            k: round(1e3 * v / steps, 4) for k, v in sorted(
                r.gaps.items(), key=lambda kv: -kv[1])},
        "gaps_by_length_ms": {
            name: [n, round(t, 3)] for name, (n, t) in zip(
                ["under 0.2", "0.2-1", "1-5", "5 and over"], bins)},
        "gaps_within_30ms_after_an_admission": [
            after_admission[0], round(after_admission[1], 3)],
        "longest_gaps_ms": [round(1e3 * (hi - lo), 3) for lo, hi in sorted(
            gaps, key=lambda g: g[0] - g[1])[:12]],
        "counters": {k: stats.get(k) for k in (
            "decode_steps", "decode_steps_ahead", "pipeline_flushes",
            "discarded_lane_steps", "lock_waits")}}
    print(json.dumps(out), flush=True)
    dest = os.path.join(ROOT, "chiprun_out", "host_cycle.jsonl")
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    with open(dest, "a") as f:
        f.write(json.dumps(out) + "\n")
    os._exit(0)


if __name__ == "__main__":
    main()
