"""Device milliseconds of a decode step's end, behind its layers: the final
norm, the output head (vocabulary x width, read whole every step) and the
choice of each lane's next token. Inside one execution of `jit__step`: the
time from the end of the last layer's last mixer kernel (`paged_decode_attn`
or `ssd_step`) to the end of the program, less the mean time between one
layer's last mixer kernel and the next layer's first, which holds what the
last layer still has to do after its mixers (their output projections, the
feed-forward); median over the traced steps.

What it cannot take out: the time between two layers also holds the next
layer's norm, its input projections (`W_q`, `W_k`, `W_v`, `W_in`), the
rotation of q and k and the convolution's step, which the step's end has
not; the reading is low by those (a sixth of a layer's parameters). The
embedding's gather lies ahead of the first layer and is in neither. None
for a program whose step lacks either kernel or has one layer."""
import statistics

from benchmarks.harness.layer_spans import layer_spans

KERNELS = ("paged_decode_attn", "ssd_step")


def read(run):
    found = layer_spans(run, KERNELS)
    if not found or len(found[0][1]) < 2:
        return None
    ends = []
    for program, layers in found:
        between = [nxt[0] - cur[1] for cur, nxt in zip(layers, layers[1:])]
        ends.append(program.end - layers[-1][1]
                    - sum(between) / len(between))
    return 1e3 * statistics.median(ends)
