"""Where a serving cell's set-up goes, as the engine itself accounts for it.
Chip side, one process:

    chiprun -- python tools/setup_table.py --workload <cell>

Builds the cell's engine and warms it up as `benchmarks/run.py` does (the
harness's `Served`: the engine, the benchmark's weights, one request a
prefill bucket), then prints `engine_stats()`'s `setup` (seconds by
construction phase) and `programs` (a row a program built: wall seconds,
JAX's trace, its lowering, the backend's compile or the cache's retrieval,
whether the persistent cache held it), and their sums beside the harness's
own two readings. Writes chiprun_out/setup_table.<cell>.json. What a
replica's cold start costs is the same table on an empty cache:
`JAX_COMPILATION_CACHE_DIR=<an empty directory>`.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    from benchmarks.harness.cells import load_cell, prepare_device
    _, cell, cfg, mix = load_cell(a.workload)
    prepare_device(cell, bool(a.rehearse))
    if a.rehearse:
        from benchmarks.harness.modelcfg import load_model
        cfg = load_model(cfg).tiny(cfg)
    from benchmarks.harness.serve_cell import Served
    t0 = time.perf_counter()
    served = Served(cfg, mix, a.seed, a.seconds,
                    lambda m: print(f"[harness] {m}", file=sys.stderr))
    whole = time.perf_counter() - t0
    stats = served.engine.engine_stats()
    served.close()
    rows = stats["programs"]
    print(f"{cell['name']}: engine, weights and warm-up {whole:.2f} s")
    for phase, s in stats["setup"].items():
        print(f"  {phase:<24}{s:8.3f} s")
    print(f"  {'program':<8}{'bucket':>7}{'step':>6}{'wall':>9}{'trace':>9}"
          f"{'lower':>9}{'compile':>9}  cache  rebuild")
    for r in rows:
        print(f"  {r['program']:<8}{r['bucket']:>7}{r['step']:>6}"
              f"{r['wall_s']:>9.3f}{r['trace_s']:>9.3f}{r['lower_s']:>9.3f}"
              f"{r['compile_s']:>9.3f}  "
              f"{'hit ' if r['cache_hit'] else 'miss'}   "
              f"{int(r['rebuild'])}")
    sums = {k: sum(r[k] for r in rows)
            for k in ("wall_s", "trace_s", "lower_s", "compile_s")}
    print(f"  {'all':<8}{'':>13}{sums['wall_s']:>9.3f}{sums['trace_s']:>9.3f}"
          f"{sums['lower_s']:>9.3f}{sums['compile_s']:>9.3f}  "
          f"{sum(not r['cache_hit'] for r in rows)} missed")
    out = {"workload": cell["name"], "seed": a.seed, "whole_s": whole,
           "setup": stats["setup"], "programs": rows, "sums": sums,
           "lock_waits": stats["lock_waits"],
           "lock_wait_s": stats["lock_wait_s"]}
    dest = os.path.join(ROOT, "chiprun_out",
                        f"setup_table.{cell['name']}.json")
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    sys.stdout.flush()
    os._exit(0)     # daemon threads of the engine's stream must not linger


if __name__ == "__main__":
    main()
