"""Llama-family decoder: GQA + RoPE + SwiGLU on ray_tpu.ops kernels.

Pure-pytree parameters (no module framework): `init` builds the tree,
`param_logical_axes` mirrors it with logical axis names consumed by
ray_tpu.parallel.sharding, `apply`/`loss` are jit-friendly functions.
Layers are stacked on a leading "layers" axis and executed with
`lax.scan` so XLA compiles one layer body regardless of depth; with
`config.remat` the body is wrapped in `jax.checkpoint` trading FLOPs
for HBM (SURVEY.md §7 hardware notes).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import regions as R
from jax.sharding import Mesh

from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.paged import PagedDecoder
from ray_tpu.ops.attention import (ATTN_RESIDUAL_NAMES, flash_attention,
                                   flash_attention_saveable)
from ray_tpu.ops.dispatch import (compute_platform, kernel_mesh,
                                  mesh_platform, on_tpu, platform_pinned)
from ray_tpu.ops.losses import softmax_cross_entropy
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.ring_attention import ring_attention_sharded
from ray_tpu.parallel.sharding import with_logical_constraint

Params = Dict[str, Any]

# Activation logical axes (all optional constraints; params use the
# rules in parallel.sharding directly).
_ACT_RULES_EXTRA = {"act_embed": None, "expert_capacity": None}


# What `_layer` names for a checkpoint policy: q, k and v as the
# attention call takes them (rotated and transposed, so that the backward
# runs neither again), the stream after `x + attn @ wo`, and the MLP's two
# matmul outputs. The attention output and its log-sum-exp are named by
# `flash_attention_saveable` (ATTN_RESIDUAL_NAMES).
ATTN_INPUT_NAMES = ("attn_q", "attn_k", "attn_v")
ATTN_STREAM_NAME = "attn_stream"
MLP_NAMES = ("mlp_gate", "mlp_up")

# `TransformerConfig.remat_policy`: a rung keeps a prefix of this one tuple
# of names, most time spared a byte first (config.py has what each rung
# costs); a layer that lacks a name (a MoE layer has neither of the MLP's)
# keeps nothing under it.
REMAT_LADDER = (ATTN_RESIDUAL_NAMES + ATTN_INPUT_NAMES
                + (ATTN_STREAM_NAME, MLP_NAMES[1], MLP_NAMES[0]))
REMAT_RUNGS = {"full": 0, "save_attn": 2, "save_attn_qkv": 5,
               "save_attn_stream": 6, "save_attn_stream_up": 7,
               "save_matmuls": 8}
REMAT_SAVED_NAMES = {rung: REMAT_LADDER[:n]
                     for rung, n in REMAT_RUNGS.items()}
# The policy that names no rung takes the dearest one whose kept bytes a
# device fit the budget below, and never less than the floor.
REMAT_AUTO = "auto"
REMAT_FLOOR = "save_attn_qkv"
# A quarter of a v5e's 16 GiB: beside the weights, gradients and optimizer
# state of a step that fills the chip, that is what the records show
# fitting (the train cell keeps 3.53 GB on the top rung and its step peaks
# at 15.2 of the chip's 16.9e9 bytes). A constant and no probe of the
# device: a traced program may not depend on the host it is traced on.
REMAT_KEPT_BYTES_BUDGET = 4 * 2 ** 30


def remat_kept_bytes(config: TransformerConfig, rung: str,
                     tokens: int) -> int:
    """Bytes a device keeps for the backward under `rung` beside every
    layer's input: `tokens` tokens a device through all the layers."""
    c = config
    a = c.activation_dtype.itemsize
    h, kv = c.n_heads * c.head_dim, c.kv_heads * c.head_dim
    mlp = 0 if c.moe_num_experts else a * c.d_ff
    per_name = dict(zip(REMAT_LADDER, (
        a * h, 4 * c.n_heads,           # attention's output, float32 lse
        a * h, a * kv, a * kv,          # q, k, v
        a * c.d_model, mlp, mlp)))
    return (sum(per_name[n] for n in REMAT_SAVED_NAMES[rung])
            * tokens * c.n_layers)


def resolve_remat_rung(config: TransformerConfig, tokens: int) -> str:
    """The rung `config.remat_policy` means for `tokens` tokens a device:
    itself where it names one, else the dearest whose kept bytes fit
    REMAT_KEPT_BYTES_BUDGET, from REMAT_FLOOR up."""
    policy = config.remat_policy
    if policy in REMAT_SAVED_NAMES:
        return policy
    if policy != REMAT_AUTO:
        raise ValueError(
            f"remat_policy {policy!r}: {REMAT_AUTO!r} or one of "
            f"{list(REMAT_RUNGS)}")
    rungs = list(REMAT_RUNGS)
    rung = REMAT_FLOOR
    for dearer in rungs[rungs.index(REMAT_FLOOR) + 1:]:
        kept = remat_kept_bytes(config, dearer, tokens)
        # a rung that adds no byte names what this layer does not have
        if (kept > REMAT_KEPT_BYTES_BUDGET
                or kept == remat_kept_bytes(config, rung, tokens)):
            break
        rung = dearer
    return rung


def _rules():
    from ray_tpu.parallel.sharding import LOGICAL_AXIS_RULES
    rules = dict(LOGICAL_AXIS_RULES)
    rules.update(_ACT_RULES_EXTRA)
    return rules


class Transformer(PagedDecoder):
    """Functional model bundle for one TransformerConfig; of
    `PagedDecoder` it takes the six default answers alone."""

    def __init__(self, config: TransformerConfig,
                 mesh: Optional[Mesh] = None):
        self.config = config
        self.mesh = mesh
        # what the Pallas kernels are shard-mapped over (None on one
        # device: nothing to partition)
        self.kernel_mesh = kernel_mesh(mesh)

    def _platform(self):
        """Platform the forward will actually run on: the mesh's devices
        when bound to a mesh (may differ from the default backend — e.g.
        a virtual CPU mesh on a TPU host), else the default backend. A
        caller that already pinned a platform (an ahead-of-time
        lowering for another one) keeps its pin."""
        if self.mesh is None or platform_pinned():
            return None
        return mesh_platform(self.mesh)

    # ------------------------------------------------------------ init
    def init(self, key: jax.Array) -> Params:
        c = self.config
        pd = c.parameter_dtype
        e, f, hd = c.d_model, c.d_ff, c.head_dim
        qd, kvd = c.n_heads * hd, c.kv_heads * hd
        k = iter(jax.random.split(key, 16))
        std = 0.02
        out_std = std / math.sqrt(2 * c.n_layers)

        def w(key, shape, scale):
            return (jax.random.normal(key, shape, jnp.float32)
                    * scale).astype(pd)

        L = c.n_layers
        layers: Params = {
            "attn_norm": jnp.zeros((L, e), pd),
            "wq": w(next(k), (L, e, qd), std),
            "wk": w(next(k), (L, e, kvd), std),
            "wv": w(next(k), (L, e, kvd), std),
            "wo": w(next(k), (L, qd, e), out_std),
            "mlp_norm": jnp.zeros((L, e), pd),
        }
        if c.moe_num_experts:
            E = c.moe_num_experts
            layers.update({
                "router": w(next(k), (L, e, E), std),
                "moe_gate": w(next(k), (L, E, e, f), std),
                "moe_up": w(next(k), (L, E, e, f), std),
                "moe_down": w(next(k), (L, E, f, e), out_std),
            })
        else:
            layers.update({
                "gate": w(next(k), (L, e, f), std),
                "up": w(next(k), (L, e, f), std),
                "down": w(next(k), (L, f, e), out_std),
            })
        params: Params = {
            "embed": w(next(k), (c.vocab_size, e), std),
            "layers": layers,
            "final_norm": jnp.zeros((e,), pd),
        }
        if not c.tie_embeddings:
            params["lm_head"] = w(next(k), (e, c.vocab_size), std)
        return params

    def param_logical_axes(self) -> Params:
        layers = {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", "embed"),
        }
        if self.config.moe_num_experts:
            layers.update({
                "router": ("layers", "embed", None),
                "moe_gate": ("layers", "experts", "embed", "mlp"),
                "moe_up": ("layers", "experts", "embed", "mlp"),
                "moe_down": ("layers", "experts", "mlp", "embed"),
            })
        else:
            layers.update({
                "gate": ("layers", "embed", "mlp"),
                "up": ("layers", "embed", "mlp"),
                "down": ("layers", "mlp", "embed"),
            })
        axes = {
            "embed": ("vocab", "embed"),
            "layers": layers,
            "final_norm": ("embed",),
        }
        if not self.config.tie_embeddings:
            axes["lm_head"] = ("embed", "vocab")
        return axes

    # --------------------------------------------------------- forward
    def remat_plan(self, batch_tokens: int):
        """(rung, kept bytes a device) of a rematted step over a batch of
        `batch_tokens` tokens, without tracing it. Tokens divide over the
        mesh axes that split batch and sequence; tp and pp divide some
        of the values and are not credited, which counts high."""
        per_device = batch_tokens
        if self.mesh is not None:
            rules = _rules()
            shards = math.prod(self.mesh.shape.get(axis, 1) for axis in
                               (*rules["batch"], rules["seq"]))
            per_device = -(-batch_tokens // shards)
        rung = resolve_remat_rung(self.config, per_device)
        return rung, remat_kept_bytes(self.config, rung, per_device)

    def _saved_names(self, batch_tokens: int):
        """The checkpoint names a rematted layer keeps for its backward."""
        if not self.config.remat:
            return ()
        return REMAT_SAVED_NAMES[self.remat_plan(batch_tokens)[0]]

    @R.region(R.ATTN_CORE)
    def _attention(self, q, k, v, saved=()):
        """Causal attention for one layer. A policy that keeps the
        attention output (`saved` holds its name) spares the backward a
        second run of the forward kernel, which takes the call whose
        residuals carry names; off TPU there is no kernel to spare
        (attention is the einsum reference, recomputed from the q, k and
        v that are kept)."""
        c = self.config
        if (c.use_ring_attention and self.mesh is not None
                and self.mesh.shape.get("sp", 1) > 1):
            return ring_attention_sharded(q, k, v, self.mesh, causal=True)
        if ATTN_RESIDUAL_NAMES[0] in saved and on_tpu():
            return flash_attention_saveable(
                q, k, v, causal=True, block_q=c.attn_block_q,
                block_k=c.attn_block_k, mesh=self.kernel_mesh)
        return flash_attention(q, k, v, causal=True,
                               block_q=c.attn_block_q,
                               block_k=c.attn_block_k,
                               mesh=self.kernel_mesh)

    @R.region(R.NORM)
    def _norm(self, x, w):
        return rms_norm(x, w, self.config.norm_eps, self.kernel_mesh)

    @R.region(R.HEAD)
    def _final_norm(self, params: Params, x):
        """The stream's last norm: the head's, not a layer's."""
        return rms_norm(x, params["final_norm"], self.config.norm_eps,
                        self.kernel_mesh)

    def _constrain(self, x, axes):
        if self.mesh is None:
            return x
        return with_logical_constraint(x, axes, mesh=self.mesh,
                                       rules=_rules())

    @R.region(R.EMBED)
    def _embed_lookup(self, table, tokens):
        """Token embedding. With the table sharded (vocab->tp,
        embed->fsdp) a gather forces SPMD involuntary full
        rematerialization (xla spmd_partitioner.cc:652); the one-hot
        contraction partitions cleanly (the vocab axis reduces with a
        psum over tp) and runs on the MXU, so it is what the sharded
        path uses — the same trade MaxText makes on TPU."""
        m = self.mesh
        if m is None or (m.shape.get("tp", 1) == 1
                         and m.shape.get("fsdp", 1) == 1):
            return table[tokens]
        onehot = jax.nn.one_hot(tokens, self.config.vocab_size,
                                dtype=table.dtype)
        onehot = self._constrain(onehot, ("batch", "seq", "vocab"))
        return onehot @ table

    def _layer(self, x, layer: Params, rope, saved=()):
        """One block; returns (x, moe_aux_loss) — 0.0 for dense FFN.
        `saved`: the names the enclosing checkpoint keeps."""
        c = self.config
        ad = c.activation_dtype
        b, s, e = x.shape
        hd = c.head_dim

        h = self._norm(x, layer["attn_norm"])
        with R.region(R.ATTN_IN):
            q = (h @ layer["wq"].astype(ad)).reshape(b, s, c.n_heads, hd)
            k = (h @ layer["wk"].astype(ad)).reshape(b, s, c.kv_heads, hd)
            v = (h @ layer["wv"].astype(ad)).reshape(b, s, c.kv_heads, hd)
            from ray_tpu.ops.rope import apply_rope_cached
            cos, sin = rope
            q = apply_rope_cached(q, cos, sin)
            k = apply_rope_cached(k, cos, sin)
            q = q.transpose(0, 2, 1, 3)   # (b, h, s, hd)
            k = k.transpose(0, 2, 1, 3)
            v = v.transpose(0, 2, 1, 3)
            q = self._constrain(q, ("batch", "heads", "seq", "head_dim"))
            q, k, v = (checkpoint_name(a, name)
                       for a, name in zip((q, k, v), ATTN_INPUT_NAMES))
        attn = self._attention(q, k, v, saved)
        with R.region(R.ATTN_OUT):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, c.n_heads * hd)
            x = x + attn @ layer["wo"].astype(ad)
            x = self._constrain(x, ("batch", "seq", "act_embed"))
            x = checkpoint_name(x, ATTN_STREAM_NAME)

        h = self._norm(x, layer["mlp_norm"])
        if c.moe_num_experts:
            from ray_tpu.models.moe import moe_ffn
            y, aux = moe_ffn(
                h, layer["router"], layer["moe_gate"], layer["moe_up"],
                layer["moe_down"], top_k=c.moe_top_k,
                capacity_factor=c.moe_capacity_factor,
                constrain=(None if self.mesh is None else
                           lambda a, ax: self._constrain(a, ax)))
            with R.region(R.MOE_EXPERTS):
                x = x + y
                return (self._constrain(x, ("batch", "seq", "act_embed")),
                        aux["moe_load_balance_loss"])
        with R.region(R.FFN):
            gate = checkpoint_name(h @ layer["gate"].astype(ad),
                                   MLP_NAMES[0])
            up = checkpoint_name(h @ layer["up"].astype(ad), MLP_NAMES[1])
            mlp = self._constrain(jax.nn.silu(gate) * up,
                                  ("batch", "seq", "mlp"))
            x = x + mlp @ layer["down"].astype(ad)
            return (self._constrain(x, ("batch", "seq", "act_embed")),
                    jnp.float32(0.0))

    def hidden(self, params: Params, tokens: jax.Array,
               positions: Optional[jax.Array] = None) -> jax.Array:
        """Trunk: tokens (b, s) -> post-final-norm hidden states (b, s, e)."""
        return self.hidden_and_aux(params, tokens, positions)[0]

    def hidden_and_aux(self, params: Params, tokens: jax.Array,
                       positions: Optional[jax.Array] = None):
        """(hidden states, summed MoE load-balance loss across layers)."""
        with compute_platform(self._platform()):
            return self._hidden(params, tokens, positions)

    def _hidden(self, params: Params, tokens: jax.Array,
                positions: Optional[jax.Array] = None):
        c = self.config
        ad = c.activation_dtype
        b, s = tokens.shape
        custom_positions = positions is not None
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        with R.region(R.EMBED):
            x = self._embed_lookup(params["embed"].astype(ad), tokens)
            x = self._constrain(x, ("batch", "seq", "act_embed"))

        # cos/sin computed once; identical for every layer and cheap to
        # hold across remat (transcendentals dominate their recompute).
        from ray_tpu.ops.rope import rope_cos_sin
        with R.region(R.ATTN_IN):
            rope = rope_cos_sin(positions, c.head_dim, c.rope_theta)

        saved = self._saved_names(b * s)
        remat_policy = (jax.checkpoint_policies.save_only_these_names(*saved)
                        if saved else None)

        def _checkpointed(body):
            if c.remat:
                # prevent_cse=False: scan's loop structure already blocks
                # the CSE hazard; True inserts unfusable barriers.
                return jax.checkpoint(body, prevent_cse=False,
                                      policy=remat_policy)
            return body

        if (self.mesh is not None and self.mesh.shape.get("pp", 1) > 1
                and c.pipeline_microbatches > 0):
            if c.moe_num_experts:
                raise NotImplementedError(
                    "MoE + pipeline parallelism is not supported yet "
                    "(the pipeline stage carries activations only)")
            if custom_positions:
                raise NotImplementedError(
                    "pipeline parallelism assumes default positions "
                    "(rope caches are sliced per microbatch, which is "
                    "only exact when rows share the arange positions); "
                    "pass positions=None with pp>1")
            from ray_tpu.parallel.pipeline import pipeline_apply

            # rope rides as explicit consts: closures over tracers don't
            # cross the shard_map manual region. Caches are full-batch;
            # rows are identical (positions broadcast from arange), so
            # slicing to the microbatch is exact.
            def stage(stage_layers, xm, cos, sin):
                rope_mb = (cos[:xm.shape[0]], sin[:xm.shape[0]])

                def sbody(carry, layer):
                    y, _lb = self._layer(carry, layer, rope_mb, saved)
                    return y, None
                out, _ = lax.scan(_checkpointed(sbody), xm, stage_layers)
                return out

            x = pipeline_apply(self.mesh, stage, params["layers"], x,
                               c.pipeline_microbatches, consts=rope)
            return (self._final_norm(params, x),
                    jnp.float32(0.0))

        def body(carry, layer):
            x, aux = carry
            x, lb = self._layer(x, layer, rope, saved)
            return (x, aux + lb), None

        (x, moe_aux), _ = lax.scan(_checkpointed(body),
                                   (x, jnp.float32(0.0)),
                                   params["layers"])
        return self._final_norm(params, x), moe_aux

    def _head(self, params: Params) -> jax.Array:
        return (params["embed"].T if self.config.tie_embeddings
                else params["lm_head"])

    def apply(self, params: Params, tokens: jax.Array,
              positions: Optional[jax.Array] = None) -> jax.Array:
        """tokens (b, s) int32 -> logits (b, s, vocab) in f32."""
        c = self.config
        x = self.hidden(params, tokens, positions)
        with R.region(R.HEAD):
            logits = x @ self._head(params).astype(c.activation_dtype)
            logits = self._constrain(logits, ("batch", "seq", "vocab"))
            return logits.astype(jnp.float32)

    # ------------------------------------------- what an engine asks
    # The serving engine asks a model for its cache and its two programs
    # (`models.build_model`); this class answers with `models.decode`.
    def init_cache(self, num_pages: int, page_size: int, dtype=None):
        from ray_tpu.models import decode
        return decode.init_paged_cache(self.config, num_pages, page_size,
                                       dtype, mesh=self.mesh)

    def cache_page_bytes(self, page_size: int, tp_shards: int = 1,
                         dtype=None) -> int:
        from ray_tpu.models import decode
        return decode.cache_page_bytes(self.config, page_size,
                                       tp_shards=tp_shards, dtype=dtype)

    def decode_attention(self, page_size: int, dtype=None) -> str:
        from ray_tpu.models import decode
        return decode.decode_attention(self.config, page_size, dtype)

    def walk_block_pages(self, page_size: int, max_pages: int) -> int:
        from ray_tpu.models import decode
        tp = self.kernel_mesh.shape.get("tp", 1) if self.kernel_mesh else 1
        return decode.walk_block_pages(self.config, page_size, max_pages,
                                       tp_shards=tp)

    def prefill(self, params: Params, tokens, true_len, page_table, cache,
                page_size: int):
        from ray_tpu.models import decode
        return decode.prefill(self, params, tokens, true_len, page_table,
                              cache, page_size)

    def decode_step(self, params: Params, cache, tokens, positions,
                    page_tables, active, page_size: int):
        from ray_tpu.models import decode
        return decode.decode_step(self, params, cache, tokens, positions,
                                  page_tables, active, page_size)

    # ------------------------------------------------------------ loss
    def loss(self, params: Params, batch: Dict[str, jax.Array]):
        """Causal LM loss. batch: tokens (b, s); optional loss_mask
        (b, s) aligned with tokens-as-labels: loss_mask[i] = 0 excludes
        token i from being counted as a prediction target (use 0 on
        prompt/padding tokens, 1 on completion tokens)."""
        c = self.config
        tokens = batch["tokens"]
        mask = batch.get("loss_mask")

        def moe_term(aux):
            if not c.moe_num_experts:
                return 0.0
            return c.moe_aux_coef * aux / c.n_layers

        if c.loss_chunk:
            # Full-length formulation (keeps seq divisible by the chunk):
            # labels[i] = tokens[i+1], with the final position masked out.
            from ray_tpu.ops.losses import chunked_lm_loss
            b, s = tokens.shape
            x, aux = self.hidden_and_aux(params, tokens)
            with R.region(R.HEAD):
                labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]],
                                         axis=1)
                m = (jnp.ones((b, s), jnp.float32) if mask is None
                     else mask.astype(jnp.float32))
                m = jnp.concatenate([m[:, 1:], jnp.zeros((b, 1))], axis=1)
                head = self._head(params).astype(c.activation_dtype)
                return chunked_lm_loss(
                    x, head, labels, m,
                    chunk_size=c.loss_chunk) + moe_term(aux)
        x, aux = self.hidden_and_aux(params, tokens)
        with R.region(R.HEAD):
            logits = x @ self._head(params).astype(c.activation_dtype)
            logits = self._constrain(logits,
                                     ("batch", "seq", "vocab"))
            logits = logits.astype(jnp.float32)[:, :-1]
            labels = tokens[:, 1:]
            if mask is not None:
                mask = mask[:, 1:]
            loss, _ = softmax_cross_entropy(logits, labels, mask=mask)
            return loss + moe_term(aux)
