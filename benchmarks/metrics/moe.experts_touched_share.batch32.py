"""Experts that got at least one (token, expert) pair as a share of the
experts held, over the traced decode steps: the engine's counter
`moe_experts_touched` (summed over expert layers and steps, counted by the
model on the device) over `n_routed_experts x expert layers x steps`. It is
the share of the expert weights a decode step has to read: 32 lanes x 4
experts a token touch 55 of 64 when the routing is even."""
from benchmarks.harness.decode_events import emit_counts


def read(run):
    counts, cfg = emit_counts(run), run["cfg"]
    if counts is None or "n_routed_experts" not in cfg:
        return None
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * counts["moe_experts_touched"] / (
        cfg["n_routed_experts"] * layers * counts["steps"])
