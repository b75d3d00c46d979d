#!/usr/bin/env python
"""Run one test file many times, several at once, and count the hangs.

    python tools/rerun_under_load.py tests/test_data_shuffle.py \
        [--runs 60 --at-once 10 --limit 80] [--repo DIR] [--keep DIR]

A fault that needs CPU contention (a lost completion, a racy test) shows
in one run of some tens only when the box is loaded; this is the loop
that PR 47's hunt rests on (ROADMAP D8). Each run is `pytest FILE -x -m
"not slow"` in a process group of its own, under `--limit` seconds, with
pytest's faulthandler armed somewhat under that, so a hung run leaves
every thread's stack. Prints pass / fail / hang counts and, for each hang or
failure, the test and the innermost frames of the thread that ran it.
`--repo` runs the file of another checkout (the parent's copy).
Exit code: 0 when every run passed.
"""
from __future__ import annotations

import argparse
import os
import re
import signal
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

FRAME = re.compile(r'^\s+File "([^"]+)", line (\d+) in (\S+)')


def _stack_of_test(log: str, test_file: str,
                   repo: str) -> tuple[str, list[str]]:
    """(test name, innermost frames) of the thread that was inside
    `test_file` in the last faulthandler dump of `log`."""
    base = os.path.basename(test_file)
    root = os.path.join(os.path.abspath(repo), "")
    blocks = re.split(r"^(?:Current thread|Thread) 0x[0-9a-f]+.*$", log,
                      flags=re.M)
    for block in reversed(blocks):
        frames = [FRAME.match(ln) for ln in block.splitlines()]
        frames = [m for m in frames if m]
        names = [m.group(3) for m in frames
                 if os.path.basename(m.group(1)) == base]
        if names:
            short = [f"{m.group(1).removeprefix(root)}:{m.group(2)} "
                     f"{m.group(3)}" for m in frames[:8]]
            return names[-1], short
    return "?", []


def _run_one(i: int, args, out_dir: str) -> dict:
    log_path = os.path.join(out_dir, f"run_{i:03d}.log")
    cmd = [sys.executable, "-m", "pytest", args.file, "-x", "-q",
           "-m", "not slow", "-p", "no:cacheprovider", "-p", "no:randomly",
           "-o", f"faulthandler_timeout={max(5, int(args.limit * 3 / 8))}"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(cmd, cwd=args.repo, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=args.limit)
        except subprocess.TimeoutExpired:
            rc = None
        # the run's workers share its process group: none outlives it
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    with open(log_path, errors="replace") as f:
        text = f.read()
    res = {"i": i, "rc": rc, "seconds": time.monotonic() - t0,
           "log": log_path}
    if rc is None:
        res["test"], res["frames"] = _stack_of_test(text, args.file, args.repo)
    elif rc != 0:
        failed = re.findall(r"^(?:FAILED|ERROR) (\S+)", text, flags=re.M)
        res["test"], res["frames"] = (failed[0] if failed else "?"), []
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("file")
    ap.add_argument("--runs", type=int, default=60)
    ap.add_argument("--at-once", type=int, default=10)
    ap.add_argument("--limit", type=float, default=80.0,
                    help="seconds one run of the file may take")
    ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to run in")
    ap.add_argument("--keep", default=None,
                    help="directory for the runs' logs (default: a "
                         "temporary one, removed when every run passed)")
    args = ap.parse_args()
    out_dir = args.keep or tempfile.mkdtemp(prefix="rerun_under_load_")
    os.makedirs(out_dir, exist_ok=True)
    t0 = time.monotonic()
    with ThreadPoolExecutor(args.at_once) as pool:
        results = list(pool.map(lambda i: _run_one(i, args, out_dir),
                                range(args.runs)))
    hung = [r for r in results if r["rc"] is None]
    failed = [r for r in results if r["rc"] not in (0, None)]
    passed = len(results) - len(hung) - len(failed)
    times = sorted(r["seconds"] for r in results if r["rc"] == 0)
    print(f"{args.file}: {args.runs} runs, {args.at_once} at once, "
          f"limit {args.limit:g} s: {passed} passed, {len(failed)} failed, "
          f"{len(hung)} hung, in {time.monotonic() - t0:.0f} s"
          + (f" (a passing run: median {times[len(times) // 2]:.1f} s, "
             f"slowest {times[-1]:.1f} s)" if times else ""))
    for r in hung + failed:
        kind = "hung in" if r["rc"] is None else f"exit {r['rc']} at"
        print(f"  run {r['i']:03d} {kind} {r['test']}  ({r['log']})")
        for fr in r["frames"]:
            print(f"      {fr}")
    if not hung and not failed and not args.keep:
        for r in results:
            os.unlink(r["log"])
        os.rmdir(out_dir)
    return 0 if passed == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
