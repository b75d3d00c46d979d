"""Model FLOP/s utilization on required operations: tokens per second
outside the traced steps, times the operations forward and backward need a
token (`train_flops_per_token` of the configuration's model module), over the
chip's bf16 peak."""


def read(run):
    s = run["samples"]
    if "tokens_per_s_untraced" not in s or run["peaks"] is None:
        return None
    flops = run["model"].train_flops_per_token(run["sizes"], s["seq_len"])
    chips = int(run["cell"]["chips"])
    return (100.0 * s["tokens_per_s_untraced"] * flops
            / (chips * run["peaks"]["bf16_flops"]))
