"""Share of the prefilled positions that were padding: 1 - `tokens` over
`bucket`, summed over the traced `engine.prefill` spans (the engine's
counters `prefill_tokens` / `prefill_padded_tokens`)."""
from benchmarks.harness.spans import PREFILL, of_run


def read(run):
    r = of_run(run)
    padded = r.attr_sum(PREFILL, "bucket") if r else 0
    if not padded:
        return None
    return 100.0 * (1.0 - r.attr_sum(PREFILL, "tokens") / padded)
