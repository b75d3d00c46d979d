"""TPU kernel layer: Pallas kernels for the hot ops, JAX references for CPU.

The reference framework has no kernel layer of its own (it orchestrates
torch/CUDA); ray_tpu's compute path is JAX/XLA and the ops here are where
hand-written Pallas beats XLA's default lowering — attention above all.
Every op has a pure-JAX reference implementation used (a) on CPU, (b) as
the ground truth in tests; Pallas kernels run in interpreter mode on CPU
so the same code path is testable without hardware.
"""
from ray_tpu.ops.norms import rms_norm, layer_norm  # noqa: F401
from ray_tpu.ops.rope import apply_rope, rope_frequencies  # noqa: F401
from ray_tpu.ops.losses import softmax_cross_entropy  # noqa: F401
from ray_tpu.ops.attention import (  # noqa: F401
    flash_attention,
    mha_reference,
)
from ray_tpu.ops.paged_attention import (  # noqa: F401
    paged_attention_reference,
    paged_decode_attention,
)
from ray_tpu.ops.ring_attention import ring_attention  # noqa: F401
