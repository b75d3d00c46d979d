"""Find the knee of an open-loop serving cell once, on the chip:

    python3 benchmarks/tools/sweep.py --workload <cell> --rates 1.5,2,2.5,3 --seconds 40

One engine, one warm-up; for each rate the cell's own schedule is offered
stretched to that rate for a window, and the waiting queue is read at the
window's middle and end. The knee is the highest rate at which the queue at
the end is no longer than at the middle. Writes chiprun_out/sweep.<cell>.json.
"""
import argparse
import json
import os

from _common import ROOT

from benchmarks.harness.cells import load_cell, prepare_device


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    _, cell, cfg, mix = load_cell(a.workload)
    prepare_device(cell, bool(a.rehearse))
    if a.rehearse:
        from benchmarks.harness.modelcfg import load_model
        cfg = load_model(cfg).tiny(cfg)
    from benchmarks.harness.serve_cell import Served
    from benchmarks.harness.stats import percentile
    rates = [float(r) for r in a.rates.split(",")]
    fastest = dict(mix, rate_rps=max(rates))
    served = Served(cfg, fastest, a.seed, a.seconds)
    rows = []
    for rate in rates:
        queue = {}
        w = served.window(
            dict(mix, rate_rps=rate), a.seconds,
            probe=lambda e, share: queue.__setitem__(
                share, (len(e.core._waiting), len(e.core._running))))
        row = {"rate_rps": rate, "sent": w["sent"], "failed": w["failed"],
               "waiting_mid": queue[0.5][0], "waiting_end": queue[1.0][0],
               "running_mid": queue[0.5][1], "running_end": queue[1.0][1],
               "ttft_p50_ms": 1e3 * percentile(w["ttft_s"], 50),
               "ttft_p90_ms": 1e3 * percentile(w["ttft_s"], 90),
               "itl_p98_ms": 1e3 * percentile(w["gap_s"], 98),
               "queue_wait_p95_ms": 1e3 * (percentile(w["queue_wait_s"], 95)
                                           or 0.0),
               "tokens_per_s": w["tokens_in_window"] / a.seconds}
        rows.append(row)
        print(json.dumps(row), flush=True)
    served.close()
    out = os.path.join(ROOT, "chiprun_out", f"sweep.{a.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    os._exit(0)


if __name__ == "__main__":
    main()
