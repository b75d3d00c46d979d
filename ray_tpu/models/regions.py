"""Names of a model step's regions on the device: the contract between the
model blocks, which trace each region's work inside `jax.named_scope(name)`,
and whoever reads a device trace (`benchmarks/harness/op_scopes.py`,
`benchmarks/tools/regions.py`, XProf's framework-op view; PERF.md section 3
/ README "Distributed tracing"). A scope is metadata of the lowered program
(every operation's `op_name` path holds it, through `scan`, `checkpoint`,
`jvp` and `transpose`) and no instruction of it: always on, nothing to
switch. An operation belongs to the innermost region of its path; every
matmul, kernel, pool gather / scatter and reduction over the model width of
a served or trained program lies in one (`tests/test_regions.py`). No name
holds a layer's index: a scanned body has none, and unrolled layers keep
lowering to equal text. The prefix keeps a JAX function's own name
(`jit(norm)`) from reading as a region.
"""
from __future__ import annotations

import jax

# the embedding table's gather (scaled where the class scales it)
EMBED = "r.embed"
# every norm through a model's `_norm` (the `rms_norm` kernel over the
# stream's or a projection's whole width): the norms that open a mixer or a
# feed-forward, a post-norm with the residual addition behind it, q / k
# norms over all heads. The final norm is the head's, a mixer's gated norm
# the mixer's, a latent's the projection's it sits in
NORM = "r.norm"
# attention's way in: q / k / v or the latent down- and up-projections,
# the absorbed form's W_UK relay, a latent's norm, rotary, the cache write
ATTN_IN = "r.attn_in"
# the flash / paged / window / latent kernel, or the gather and einsum
# that stand in for it
ATTN_CORE = "r.attn_core"
# a learned sparse attention's indexer: its projections, its key's norm
# and rotary, the index pool's write, the scores over the cached index
# keys and the choice of the positions the attention reads
ATTN_INDEX = "r.attn_index"
# the absorbed form's W_UV relay, a head gate, the output projection and
# the residual addition it feeds
ATTN_OUT = "r.attn_out"
# a recurrent mixer's way in: its projections, the convolution (a prefill's
# `causal_conv`, a step's `conv_tail_step`), the scan's inputs (gates,
# decays, q / k norms)
MIXER_IN = "r.mixer_in"
# the recurrence: `gated_delta_*`, `kda_*`, `ssd_*`, and the state's write
MIXER_CORE = "r.mixer_core"
# the gated norm, the output projection and the residual addition
MIXER_OUT = "r.mixer_out"
# a dense SwiGLU: a dense layer's, the dense path beside a branch of
# experts, a shared expert; with the residual addition it feeds
FFN = "r.ffn"
# the router's scores, the top-k choice, the pairs' counts
MOE_ROUTE = "r.moe_route"
# the sort and gather of rows, the grouped matmuls, the weighted scatter
# back, a latent expert's projections in and out
MOE_EXPERTS = "r.moe_experts"
# the final norm, the vocabulary matmul (scaled where the class scales
# it), in training the loss
HEAD = "r.head"
# the engine's choice of each lane's next token (`_next`, `_place`)
SAMPLE = "r.sample"
# bookkeeping on the device that belongs to no layer: the page and slot a
# lane writes, a prefill's page ids, the experts' load counts
CACHE = "r.cache"

ALL = (EMBED, NORM, ATTN_IN, ATTN_INDEX, ATTN_CORE, ATTN_OUT, MIXER_IN,
       MIXER_CORE,
       MIXER_OUT, FFN, MOE_ROUTE, MOE_EXPERTS, HEAD, SAMPLE, CACHE)

# `with region(NORM): ...`, or `@region(NORM)` on a function that is one
# region whole
region = jax.named_scope
