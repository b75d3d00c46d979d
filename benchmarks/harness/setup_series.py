"""What the program put on the metrics plane about its own start, read
back for the `setup.*` metrics: `ray_tpu_llm_setup_s` (seconds by phase:
the names of the `engine.setup*` spans), `ray_tpu_llm_program_build_s`
(seconds of the calls that built a program, by program and by `trace` /
`lower` / `compile` / `rest`) and `ray_tpu_llm_program_builds` (builds by
`cache` hit or miss and `rebuild`), which `ray_tpu/serve/llm/
setup_record.py` writes as an engine is made and as each of its programs
is built.

`run.py` holds no engine when metrics are read, but the engine ran in this
process and its series are still in the process's registry. They are
there whether or not the run is traced. A program that registers no such
series (the parent of PR 39) reads None, and the metric leaves its line.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

SETUP_S = "ray_tpu_llm_setup_s"
BUILD_S = "ray_tpu_llm_program_build_s"
BUILDS = "ray_tpu_llm_program_builds"


def snapshot(run: dict) -> Dict[str, dict]:
    """This process's registry as the metrics plane dumps it, taken once a
    run (a test hands in its own under `_metrics`)."""
    if "_metrics" not in run:
        from ray_tpu.util.metrics import DEFAULT_REGISTRY
        run["_metrics"] = DEFAULT_REGISTRY.collect()
    return run["_metrics"]


def total(run: dict, name: str, **labels: str) -> Optional[float]:
    """The sum of `name`'s series that carry `labels`; None where the
    program wrote no series of that name at all."""
    series: Dict[Tuple, float] = snapshot(run).get(name, {}).get(
        "series", {})
    if not series:
        return None
    want = set(labels.items())
    return float(sum(v for tags, v in series.items() if want <= set(tags)))
