"""Experts that got at least one (token, expert) pair as a share of the
experts held, over the traced decode steps: the engine's counter
`moe_experts_touched` (summed over expert layers and steps, counted by the
model on the device) over `num_experts x expert layers x steps`, the expert
layers those whose `mlp_layer_types` entry is `sparse`. It is the share of
the expert weights a decode step has to read: 32 lanes x 8 experts a token
touch 162 of 256 when the routing is even. (`moe.experts_touched_share.
batch32` reads the keys of another configuration's file.)"""
from benchmarks.harness.decode_events import emit_counts


def read(run):
    counts, cfg = emit_counts(run), run["cfg"]
    if counts is None or "num_experts" not in cfg \
            or "mlp_layer_types" not in cfg:
        return None
    layers = cfg["mlp_layer_types"].count("sparse")
    if not layers or not counts["steps"]:
        return None
    return 100.0 * counts["moe_experts_touched"] / (
        cfg["num_experts"] * layers * counts["steps"])
