"""A trace's device time by the program's own regions, one table a program:

    python3 benchmarks/tools/regions.py <trace directory or .xplane.pb>
        [--program jit__step ...] [--top 10] [--min-share 0.5]

For each program the trace holds executions of (all, or those named):
region x pass (`harness/op_scopes.py`: the `r.*` scopes of
`ray_tpu/models/regions.py` read from the operations' metadata), ms an
execution (median over the executions), the share of the program's
operation time, and from the operations' own `flops` and `bytes_accessed`
(XLA's cost analysis; a Pallas kernel reports neither and shows `-`) the
share of `harness/peaks.py`'s peaks the region reaches (XLA counts every
operand whole, so a region whose operands stay in fast memory reads above
100 % of HBM's). Under it the time under no region by XLA's category, the
largest operations there with their `source` line, and the largest under
each region (`--ops`). A program whose operations carry no region prints
that, and nothing else.

The trace of a benchmark run is deleted when the run ends; trace with
`ray_tpu.util.tracing.profile(dir)` or keep a run's `.bench_out/trace/`.
"""
import argparse
import os
import statistics
import sys

from _common import ROOT  # noqa: F401

from benchmarks.harness import op_scopes, peaks, xplane


def program_report(dev, program: str, peak: dict, top: int,
                   min_share: float, show_ops: bool) -> str:
    runs = op_scopes.executions(dev, program)
    table = op_scopes.table(dev, program)
    whole = statistics.median(ex.dur_ps for ex in runs) * 1e-9
    head = (f"== {program}: {len(runs)} executions, {whole:.4f} ms an "
            f"execution (median)")
    if table is None:
        return head + "; no operation carries a region"
    ops_ms = sum(table.values())
    lines = [head + f", {ops_ms:.4f} ms of operations",
             f"{'region':<16}{'pass':<11}{'ms':>10}{'share %':>9}"
             f"{'TFLOP/s':>10}{'% peak':>8}{'GB/s':>9}{'% peak':>8}"]
    # flops and bytes by (region, pass), a mean over the executions
    work = {}
    for ex in runs:
        for meta, _ in ex.ops:
            key = (op_scopes.region_of(meta.tf_op),
                   op_scopes.pass_of(meta.tf_op))
            f, b = work.get(key, (0.0, 0.0))
            work[key] = (f + meta.flops / len(runs),
                         b + meta.bytes_accessed / len(runs))
    for key, ms in sorted(table.items(), key=lambda kv: -kv[1]):
        if 100 * ms / ops_ms < min_share:
            continue
        flops, nbytes = work.get(key, (0.0, 0.0))
        rate = [f"{'-':>10}{'-':>8}", f"{'-':>9}{'-':>8}"]
        if ms and flops:
            r = flops / (ms * 1e-3)
            rate[0] = f"{r * 1e-12:>10.1f}{100 * r / peak['bf16_flops']:>8.1f}"
        if ms and nbytes:
            r = nbytes / (ms * 1e-3)
            rate[1] = (f"{r * 1e-9:>9.0f}"
                       f"{100 * r / peak['hbm_bytes_per_s']:>8.1f}")
        lines.append(f"{key[0]:<16}{key[1]:<11}{ms:>10.4f}"
                     f"{100 * ms / ops_ms:>9.2f}{rate[0]}{rate[1]}")
    by_op = {}
    for ex in runs:
        for meta, own in ex.ops:
            key = (op_scopes.region_of(meta.tf_op), meta)
            by_op[key] = by_op.get(key, 0.0) + own * 1e-9 / len(runs)

    def listing(region):
        found = sorted(((ms, meta) for (r, meta), ms in by_op.items()
                        if r == region), key=lambda e: -e[0])[:top]
        return [f"  {ms:>9.4f} ms  {xplane.op_label(meta.name):<44}"
                f"{meta.hlo_category:<20}{meta.source or '-'}  "
                f"{meta.tf_op[-70:]}" for ms, meta in found]
    by_category = {}
    for (region, meta), ms in by_op.items():
        if region == op_scopes.UNSCOPED:
            label = meta.hlo_category or xplane.op_label(meta.name)
            by_category[label] = by_category.get(label, 0.0) + ms
    lines.append("under no region, by XLA's category (ms an execution): "
                 + ", ".join(f"{label} {ms:.4f}" for label, ms in sorted(
                     by_category.items(), key=lambda kv: -kv[1])[:top]))
    lines.append("largest operations under no region (ms an execution):")
    lines.extend(listing(op_scopes.UNSCOPED))
    if show_ops:
        for region in sorted({r for r, _ in by_op} - {op_scopes.UNSCOPED}):
            lines.append(f"largest under {region}:")
            lines.extend(listing(region))
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--program", action="append", default=None)
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--min-share", type=float, default=0.0,
                    help="leave out rows under this percent of a program")
    ap.add_argument("--ops", action="store_true",
                    help="list the largest operations of every region")
    ap.add_argument("--device-kind", default="TPU v5 lite")
    a = ap.parse_args()
    path = (a.trace if os.path.isfile(a.trace)
            else xplane.find_xplane(a.trace))
    dev = op_scopes.load(path)
    if dev is None:
        print(f"{path}: no {op_scopes.DEVICE_PLANE} with operations",
              file=sys.stderr)
        return 1
    peak = peaks.peaks_for(a.device_kind)
    programs = a.program or sorted(
        {ex.program for ex in dev.executions if ex.ops})
    for program in programs:
        if op_scopes.executions(dev, program):
            print(program_report(dev, program, peak, a.top, a.min_share,
                                 a.ops))
            print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
