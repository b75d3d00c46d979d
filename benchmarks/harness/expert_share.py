"""A traced serving run's decode steps, for the metrics of a model whose
expert layer holds one chip's share of the experts behind a router that
also has slots that compute nothing: the counts the model wrote on the
`engine.emit` spans (`moe_pairs`: rows given to held experts,
`moe_experts_touched`, `moe_load_max`, `moe_zero_pairs`: choices of a slot
that computes nothing, `moe_away_pairs`: choices of an expert held
elsewhere), summed over the traced steps. A program that writes no
`moe_zero_pairs` (every model but this kind; the parent of PR 44) gives
None, and the metric leaves its line. `harness/decode_events.py` is the
twin for models that hold every expert."""
from __future__ import annotations

from typing import Dict, Optional

from benchmarks.harness import spans
from benchmarks.harness.decode_events import EMIT

KEYS = ("moe_pairs", "moe_experts_touched", "moe_load_max",
        "moe_zero_pairs", "moe_away_pairs")


def emit_counts(run: dict) -> Optional[Dict[str, int]]:
    """The five counts summed over the traced decode steps, `steps` the
    steps that carried them and `all_pairs` every choice of every lane
    (held, away and zero: lanes x top_k x layers); None where no span has
    `moe_zero_pairs`."""
    r = spans.of_run(run)
    if r is None:
        return None
    emits = [s for s in r.named(EMIT) if "moe_zero_pairs" in s.stats]
    if not emits:
        return None
    out = {key: sum(int(s.stats.get(key, 0)) for s in emits) for key in KEYS}
    out["steps"] = len(emits)
    out["all_pairs"] = (out["moe_pairs"] + out["moe_zero_pairs"]
                        + out["moe_away_pairs"])
    return out


def held_slots(run: dict) -> Optional[int]:
    """Held experts x layers: the experts a decode step could touch here;
    None for a model module without a share."""
    sz = run["sizes"]
    if not hasattr(sz, "held"):
        return None
    return sz.held * sz.layers
