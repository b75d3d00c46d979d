"""Device milliseconds a decode step's layers hold their two mixers: inside
one execution of `jit__step`, from the start of a layer's first mixer
kernel (`paged_decode_attn`, ops/paged_attention.py, or `ssd_step`,
ops/ssd.py) to the end of its last, summed over the layers, median over the
traced steps. The two kernels of a layer read the same normed input and do
not depend on each other: run one after the other this is their two times
and what lies between them, overlapped it is less than their sum
(`step.attn_full_ms.mixed8k` and `step.ssm_ms.agent8k` read each alone).
None for a program whose step lacks either kernel."""
import statistics

from benchmarks.harness.layer_spans import layer_spans

KERNELS = ("paged_decode_attn", "ssd_step")


def read(run):
    found = layer_spans(run, KERNELS)
    if not found:
        return None
    return 1e3 * statistics.median(
        sum(end - start for start, end in layers) for _, layers in found)
