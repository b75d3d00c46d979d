"""Roofline share of the flash attention kernels (forward, dK/dV, dQ) over
the traced steps: the least time the chip could take for what the algorithm
requires (the larger of operations over 197 TFLOP/s and bytes over 819 GB/s;
at 4096 tokens the operations bound it) over the kernels' device time."""
from benchmarks.harness.required_ops import flash_call, roofline_seconds
from benchmarks.harness.xplane import kernel_events

# Pallas calls whose result is a (batch, heads, seq, head_dim)-shaped array:
# flash forward, dK/dV and dQ. The rms_norm kernel returns (rows, d_model).
# The program gives its kernels no name of their own yet (PERF.md section 7).
KERNELS = r"^%[\w.\-]+ = \(?(?:bf16|f32)\[\d+,\d+,\d+,\d+\][^=]*custom-call\("


def read(run):
    trace, traced = run["trace"], run["result"].get("traced")
    if trace is None or not traced or "steps" not in traced:
        return None
    events = [e for e in kernel_events(trace, KERNELS)
              if "tpu_custom_call" in e.name]
    spent = sum(e.dur for e in events)
    if not spent:
        return None
    z, s = run["sizes"], run["samples"]
    one = flash_call(s["batch"], z.heads, z.kv_heads, s["seq_len"],
                     z.head_dim)
    calls = z.layers * traced["steps"]
    least, _bound = roofline_seconds(
        calls * (one["fwd_flops"] + one["bwd_flops"]),
        calls * (one["fwd_bytes"] + one["bwd_bytes"]), run["peaks"])
    return 100.0 * least / spent
