"""Refresh ENVELOPE.md's machine-generated benchmark block.

Usage:
    python bench_core.py --json > /tmp/bench.json
    python tools/update_envelope.py --json /tmp/bench.json
    # or run the bench in-process:
    python tools/update_envelope.py --run

Rewrites the block between the ``<!-- bench:latest:begin -->`` /
``<!-- bench:latest:end -->`` markers in ENVELOPE.md (appending the
block on first use) with one row per scenario key, including the r6
frames-per-task column, so every bench refresh lands in the envelope
doc the same way and future rounds can track the trajectory. The
hand-curated narrative above the block is never touched.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BEGIN = "<!-- bench:latest:begin -->"
END = "<!-- bench:latest:end -->"

# scenario key -> human row label (table order follows this list; keys
# absent from the JSON are skipped, unknown keys are appended as-is)
LABELS = [
    ("pipeline_1f1b_depth1",
     "MPMD pipeline 4-stage, 1F1B, single-slot channels (depth 1)"),
    ("pipeline_1f1b_overlap",
     "MPMD pipeline 4-stage, 1F1B, ring depth 2 (overlap)"),
    ("pipeline_gpipe", "MPMD pipeline 4-stage, GPipe fill-drain"),
    ("pipeline_1f1b", "MPMD pipeline 4-stage, 1F1B (vs GPipe pair)"),
    ("wire_codec_native", "wire codec, C forced (encode+decode µs)"),
    ("wire_codec_python",
     "wire codec, protobuf backend (encode+decode µs)"),
    ("drain_5k_nonative", "5k drain, RAY_TPU_DISABLE_NATIVE=1"),
    ("drain_5k_native", "5k drain, native frame engine"),
    ("drain_5k_central",
     "5k remote drain, central dispatch (RAY_TPU_DELEGATE=0)"),
    ("drain_5k_delegated", "5k remote drain, delegated bulk leases"),
    ("drain_100k", "100k drain, local workers"),
    ("drain_3k_notrace", "3k drain, RAY_TPU_TRACE=0"),
    ("drain_3k_trace", "3k drain, FULL tracing (RAY_TPU_TRACE_SAMPLE=1)"),
    ("drain_3k_trace_off", "3k drain, RAY_TPU_TRACE=0 (sampled-pair twin)"),
    ("drain_3k_trace_sampled",
     "3k drain, sampled tracing (default RAY_TPU_TRACE_SAMPLE)"),
    ("drain_3k_nometrics", "3k drain, RAY_TPU_METRICS=0"),
    ("drain_3k_metrics", "3k drain, metrics on (default)"),
    ("drain_3k_nowal", "3k drain, head persistence off"),
    ("drain_3k_wal", "3k drain, head WAL + group-commit fsync (r15)"),
    ("head_restart_recovery",
     "head SIGKILL mid-3k-delegated-drain: WAL recovery (r15)"),
    ("actor_sync_head",
     "sync actor calls, worker caller, head-routed "
     "(RAY_TPU_DIRECT_ACTOR=0)"),
    ("actor_sync_direct",
     "sync actor calls, worker caller, direct plane (r18)"),
    ("rl_sebulba_head",
     "Sebulba RL, 4 env-runners x 2 inference actors, head-routed "
     "act() (RAY_TPU_DIRECT_ACTOR=0)"),
    ("rl_sebulba_direct",
     "Sebulba RL, 4 env-runners x 2 inference actors, direct-plane "
     "act() (r20)"),
    ("tasks_sync_per_s", "tasks, sync round-trip"),
    ("tasks_batch_per_s", "tasks, batched"),
    ("actor_calls_sync_per_s", "actor calls, sync"),
    ("actor_calls_async_per_s", "actor calls, pipelined"),
    ("put_small_per_s", "put (small objects)"),
    ("put_gbps", "put throughput (8 MB)"),
    ("get_gbps", "get throughput (8 MB)"),
    ("pull_64mb_blob", "64 MB pull, blob protocol (MINOR<5 peer)"),
    ("pull_64mb_manifest", "64 MB pull, manifest zero-copy"),
    ("bcast_64mb_flat",
     "broadcast 64 MB x 8 nodes, all-pull-from-source"),
    ("bcast_64mb_tree", "broadcast 64 MB x 8 nodes, fanout tree"),
    ("shm_cycle_pooled_gbps", "shm put+free cycle, pooled (8 MB)"),
    ("shm_cycle_unpooled_gbps", "shm put+free cycle, unpooled (8 MB)"),
    ("wait_1k_refs", "wait on 1k refs"),
    ("parked_gets_200", "200 parked gets"),
    ("drain_2k_unbatched", "2k drain, RAY_TPU_WIRE_BATCH=0"),
    ("queue_5k_tasks", "5k queued tasks (batched wire)"),
    ("queue_100k_submit", "100k queued tasks, submit"),
    ("dag_2hop_execute", "compiled DAG, 2-hop execute"),
    ("dag_device_hop", "compiled DAG, device hop"),
]


def _fmt_result(rec: dict) -> str:
    if "per_second" in rec:
        out = f"{rec['per_second']:,} {rec.get('unit', 'ops')}/s"
        if "submit_per_second" in rec:
            out += f" (submit {rec['submit_per_second']:,}/s)"
        if "pool_speedup" in rec:
            out += f" (pool speedup {rec['pool_speedup']}x)"
        if "channel_speedup" in rec:
            out += f" (channel speedup {rec['channel_speedup']}x)"
        if "native_speedup" in rec:
            out += f" (native speedup {rec['native_speedup']}x)"
        if "delegate_speedup" in rec:
            out += f" (delegate speedup {rec['delegate_speedup']}x)"
        if "lease_batches" in rec:
            # r10 delegated-dispatch columns: grants went out in bulk
            out += (f" ({rec['lease_batches']} lease batches / "
                    f"{rec['tasks_leased']} tasks)")
        if "source_serves" in rec:
            # r8 broadcast columns: aggregate GB/s is per_second; the
            # serve count is the tree property (source <= fanout)
            out += (f" (source serves {rec['source_serves']}, "
                    f"depth {rec.get('depth', '?')})")
        if "tree_speedup" in rec:
            out += f" (tree speedup {rec['tree_speedup']}x)"
        if "manifest_speedup" in rec:
            out += f" (manifest speedup {rec['manifest_speedup']}x)"
        if "wal_overhead_pct" in rec:
            # r15 head-HA column-mate: throughput delta of the WAL-on
            # run vs its persistence-off twin (negative = box noise)
            out += f" (wal overhead {rec['wal_overhead_pct']:+}%)"
        if "vs_delegated_floor" in rec:
            # r16 acceptance metric: 100k per-task head CPU as a
            # multiple of the same-session 5k-delegated floor
            out += (f" ({rec['vs_delegated_floor']}x the 5k-delegated "
                    f"head-CPU floor)")
        if "staleness_p50" in rec:
            # r20 Sebulba columns: policy-version lag of each shard
            # the learner consumed (bounded by the trajectory ring
            # depth by construction — the queue bound IS the
            # staleness bound)
            out += (f" (staleness p50/p95 {rec['staleness_p50']}/"
                    f"{rec['staleness_p95']})")
        if "p50_ms" in rec:
            # r18 latency columns: sync scenarios carry per-call
            # percentiles so a latency regression can't hide behind
            # the throughput median
            out += f" (p50 {rec['p50_ms']} ms / p99 {rec['p99_ms']} ms)"
        if "direct_speedup" in rec:
            out += f" (direct speedup {rec['direct_speedup']}x)"
        if "head_frames_per_call" in rec:
            # r18 acceptance counter: the head's actor-plane frames
            # per steady-state call (~0 on the direct arm)
            out += (f" (head frames/call "
                    f"{rec['head_frames_per_call']})")
        if "overlap_speedup" in rec:
            out += f" (overlap speedup {rec['overlap_speedup']}x)"
        if "schedule_speedup" in rec:
            out += f" (1F1B speedup {rec['schedule_speedup']}x)"
        ab = rec.get("ab")
        if ab and "order_medians" in ab:
            # r12 order-bias control: the arm's median when it ran
            # first vs second in its alternating A/B pair
            om = ab["order_medians"]
            if "first" in om and "second" in om:
                out += (f" [ran-1st/2nd medians "
                        f"{om['first']}/{om['second']}]")
        return out
    extras = {k: v for k, v in rec.items()
              if k not in ("n", "unit", "frames_per_task",
                           "head_cpu_us_per_task",
                           "trace_overhead_pct",
                           "metrics_overhead_pct", "ab",
                           "serve_copies_per_byte",
                           "land_copies_per_byte",
                           "bubble_fraction")}
    return ", ".join(f"{k}={v}" for k, v in extras.items())


def _fmt_frames(rec: dict) -> str:
    """The r6 frames/task counter, joined with the r7 head-CPU µs/task
    timer when the scenario records one."""
    parts = []
    if "frames_per_task" in rec:
        parts.append(str(rec["frames_per_task"]))
    if "head_cpu_us_per_task" in rec:
        parts.append(f"{rec['head_cpu_us_per_task']} µs")
    return " · ".join(parts) if parts else "—"


def _fmt_trace(rec: dict) -> str:
    """The r9 tracing-plane overhead column: throughput delta of the
    traced run vs its RAY_TPU_TRACE=0 twin (negative = the traced run
    measured faster, i.e. the cost is below box noise)."""
    if "trace_overhead_pct" in rec:
        return f"{rec['trace_overhead_pct']:+}%"
    return "—"


def _fmt_metrics(rec: dict) -> str:
    """The r11 metrics-plane overhead column, next to the trace one:
    throughput delta of the metrics-on run vs its RAY_TPU_METRICS=0
    twin (same negative-means-noise reading)."""
    if "metrics_overhead_pct" in rec:
        return f"{rec['metrics_overhead_pct']:+}%"
    return "—"


def _fmt_copies(rec: dict) -> str:
    """The r12 copy-budget column: user-space bytes copied per byte
    transferred, serve side · land side, straight from the transfer
    code's own OBJECT_PLANE_STATS accounting (manifest target: 0 · 1;
    the blob land figure is a lower bound — the decode re-pickle is
    not counted)."""
    if "serve_copies_per_byte" in rec:
        return (f"{rec['serve_copies_per_byte']} · "
                f"{rec['land_copies_per_byte']}")
    return "—"


def _fmt_bubble(rec: dict) -> str:
    """The r13 pipeline column: per-stage idle fraction over the timed
    window, from the tracing plane's stage compute spans (1F1B floor
    is (S-1)/(M+S-1); same-box numbers include core contention)."""
    if "bubble_fraction" in rec:
        return f"{rec['bubble_fraction']:.2f}"
    return "—"


def render_block(results: dict, keep: dict = None) -> str:
    """`keep` maps scenario label -> previously rendered row: a
    partial run (e.g. ``bench_core.py --rl``) refreshes only
    its own rows and the rest of the table survives verbatim."""
    keep = keep or {}
    known = [k for k, _ in LABELS]
    rows = []
    for key, label in LABELS:
        if key in results:
            rows.append((label, results[key]))
        elif label in keep:
            rows.append((label, keep[label]))
    rows += [(key, rec) for key, rec in results.items()
             if key not in known]
    rows += [(label, row) for label, row in keep.items()
             if label not in [lb for lb in (dict(LABELS).values())]
             and label not in [r[0] for r in rows]]
    lines = [BEGIN,
             "### Latest `bench_core.py` run (machine-generated)",
             "",
             "| Scenario | Result | frames/task · head-CPU/task "
             "| trace overhead | metrics overhead "
             "| copies/byte serve · land | bubble |",
             "|---|---|---|---|---|---|---|"]
    for label, rec in rows:
        if isinstance(rec, str):          # retained pre-rendered row
            lines.append(rec)
            continue
        lines.append(f"| {label} | {_fmt_result(rec)} | "
                     f"{_fmt_frames(rec)} | {_fmt_trace(rec)} | "
                     f"{_fmt_metrics(rec)} | {_fmt_copies(rec)} | "
                     f"{_fmt_bubble(rec)} |")
    lines.append(END)
    return "\n".join(lines)


def _existing_rows(text: str) -> dict:
    """Parse scenario rows out of the current machine block so a
    partial refresh keeps them."""
    if BEGIN not in text or END not in text:
        return {}
    block = text.split(BEGIN, 1)[1].split(END, 1)[0]
    rows = {}
    for line in block.splitlines():
        line = line.rstrip()
        if not line.startswith("| ") or line.startswith("| Scenario"):
            continue
        if set(line) <= {"|", "-", " "}:
            continue
        label = line.split("|")[1].strip()
        rows[label] = line
    return rows


def update_envelope(results: dict, path: str) -> None:
    if os.path.exists(path):
        with open(path) as f:
            text = f.read()
    else:
        text = "# Scalability envelope\n"
    block = render_block(results, keep=_existing_rows(text))
    if BEGIN in text and END in text:
        head, rest = text.split(BEGIN, 1)
        _, tail = rest.split(END, 1)
        text = head + block + tail
    else:
        text = text.rstrip("\n") + "\n\n" + block + "\n"
    with open(path, "w") as f:
        f.write(text)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="update_envelope")
    p.add_argument("--json", help="bench_core.py --json output file "
                                  "(default: stdin)")
    p.add_argument("--run", action="store_true",
                   help="run bench_core.main() in-process instead")
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "ENVELOPE.md"))
    args = p.parse_args(argv)
    if args.run:
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        import bench_core
        results = bench_core.main(as_json=False)
    elif args.json:
        with open(args.json) as f:
            results = json.load(f)
    else:
        results = json.load(sys.stdin)
    update_envelope(results, args.out)
    print(f"updated {args.out} ({len(results)} scenarios)")


if __name__ == "__main__":
    main()
