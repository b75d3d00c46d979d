"""A traced serving run of a model whose attention reads the positions an
indexer chooses, seen from both sides: the device time of the regions
`r.attn_index` (the indexer: projections, scores over the cached index
keys, the choice) and `r.attn_core` (the attention over the chosen rows) in
the decode program (`jit__step`) and the prefill programs (`jit__pre`),
from the operations' metadata (`harness/op_scopes.py`), and the counts the
program wrote on the spans of the same steps (`engine.emit`:
`dsa_positions_scored`, `dsa_positions_selected`, `dsa_lanes_past_topk`,
counted on the device and fetched with the step's tokens;
`engine.decode_dispatch`: lanes and live positions; `engine.prefill`:
tokens). A program that writes no `dsa_*` count or has no such region (any
other class; the parent of PR 61) gives None everywhere, and the metric
leaves its line.
"""
from __future__ import annotations

from typing import Dict, Optional

from benchmarks.harness import op_scopes, spans
from benchmarks.harness.decode_events import EMIT

STEP, PREFILL_PROGRAM = "jit__step", "jit__pre"
INDEX, CORE = "r.attn_index", "r.attn_core"
KEYS = ("dsa_positions_scored", "dsa_positions_selected",
        "dsa_lanes_past_topk")


def emit_counts(run: dict) -> Optional[Dict[str, int]]:
    """The indexer's counts summed over the traced decode steps, `steps`
    the steps that carried them; None where no span has them."""
    r = spans.of_run(run)
    if r is None:
        return None
    emits = [s for s in r.named(EMIT) if KEYS[0] in s.stats]
    if not emits:
        return None
    out = {key: sum(int(s.stats.get(key, 0)) for s in emits) for key in KEYS}
    out["steps"] = len(emits)
    return out


def region_seconds(run: dict, program: str, regions) -> Optional[tuple]:
    """(executions of `program` in the trace, the device seconds all of
    them spent in `regions`); None where the trace holds no execution, or
    none of them an operation of these regions."""
    runs = op_scopes.executions(op_scopes.of_run(run), program)
    spent = sum(own for ex in runs for meta, own in ex.ops
                if op_scopes.region_of(meta.tf_op) in regions) * 1e-12
    return (len(runs), spent) if runs and spent else None


def step_region_ms(run: dict, region: str) -> Optional[float]:
    """Device milliseconds a decode step spends in `region`, the median
    over the traced steps; None for a program without that region."""
    return op_scopes.region_ms(run, STEP, (region,)) or None
