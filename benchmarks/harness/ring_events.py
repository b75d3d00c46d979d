"""A traced serving run of a model that keeps latent rows in a ring, seen
from the program's side: the counts the program wrote on the spans of the
traced decode steps (`engine.emit`: `ring_positions_read`, whole pages the
sliding layers' walks copied in, and `ring_positions_seen`, at most the
window a lane and sliding layer, both summed over the sliding layers and
counted on the device; beside them `dsa_lanes_past_topk`, the lanes, counted
once a full layer, that hold more than `index_topk` positions). A program
that writes no `ring_*` count (any other class; the parent of PR 65) gives
None, and the metric leaves its line.
"""
from __future__ import annotations

from typing import Dict, Optional

from benchmarks.harness import spans
from benchmarks.harness.decode_events import EMIT

KERNEL = "mla_paged_window_decode_attn"
KEYS = ("ring_positions_read", "ring_positions_seen", "dsa_lanes_past_topk")


def emit_counts(run: dict) -> Optional[Dict[str, int]]:
    """The counts summed over the traced decode steps, `steps` the steps
    that carried them; None where no span has them."""
    r = spans.of_run(run)
    if r is None:
        return None
    emits = [s for s in r.named(EMIT) if KEYS[0] in s.stats]
    if not emits:
        return None
    out = {key: sum(int(s.stats.get(key, 0)) for s in emits) for key in KEYS}
    out["steps"] = len(emits)
    return out


def dispatch_mean(run: dict, key: str) -> Optional[float]:
    """The mean of `engine.decode_dispatch`'s attribute `key` over the
    traced dispatches; None where there is none."""
    r = spans.of_run(run)
    steps = r.named(spans.DISPATCH) if r else []
    if not steps:
        return None
    return r.attr_sum(spans.DISPATCH, key) / float(len(steps))
