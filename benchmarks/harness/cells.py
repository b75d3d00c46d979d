"""What every run of a cell shares, whoever starts it (`run.py`, the tools):
finding the cell's files by the names in BENCHMARK.json, one compile cache,
a TPU or no run, and the count of compilations inside a window."""
from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_cell(workload: str):
    """(BENCHMARK.json, the cell's entry, its configuration file, its mix)."""
    from benchmarks.harness import traffic
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    return bench, cell, cfg, traffic.load_traffic(cell["traffic"])


def prepare_device(cell: dict, rehearse: bool):
    """Before JAX is touched. A rehearsal is pinned to the CPU and gets tiny
    sizes from its caller. A measurement gets one cache for the benchmark
    and the program, at a fixed path in the checkout unless the operator
    placed it, keeping every compile; and the peaks of the chips it found,
    or NoAccelerator."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        return None
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    from benchmarks.harness.peaks import require_tpu
    return require_tpu(int(cell["chips"]))[1]


def timed(fn, *args):
    """(result, seconds it took)."""
    import time
    t = time.perf_counter()
    out = fn(*args)
    return out, time.perf_counter() - t


class CompileCounter:
    """Counts backend compilations between start() and stop(): a measured
    window should see none."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.n += 1

    def start(self):
        self.n, self.on = 0, True

    def stop(self) -> int:
        self.on = False
        return self.n
