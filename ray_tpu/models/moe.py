"""Mixture-of-Experts FFN: two routed paths.

`moe_ffn` (below, first) is the capacity-factor layer `Transformer` trains
with: top-k of a softmax, a fixed capacity an expert, overflow dropped.
`dropless_moe_ffn` (at the end) is the serving path of the classes with
routed experts (`models.mla_moe`, `gqa_window_moe`, `shortcut_mla_moe`,
`hybrid_ssm_moe`, `hybrid_kda_moe`): no capacity and no dropped token at
any imbalance — the (token, expert) pairs are sorted by expert and
`ops.grouped_matmul` multiplies each expert's rows by its matrices, reading
only experts that have rows. Its scoring (sigmoid or softmax, renormalised
or not), a limit on the groups of slots a token may choose from, the slots
that compute nothing, the share of the experts it holds, the expert's form
(`EXPERT_FORMS`: three matrices with a gate, or two around a squared ReLU)
and what the experts read (the router's input, or a narrower projection of
it the caller made) are data of the caller's config (`route_topk`,
`dropless_moe_ffn`). A `simplicity` PR folds the two (ROADMAP D1c).

The capacity-factor layer: top-k routing + capacity-based dispatch.

Expert parallelism the TPU way (SURVEY.md §2.4 EP row — absent from the
reference in-tree, delivered here natively): expert weights carry the
"experts" logical axis (→ ep mesh axis), dispatch/combine are dense
einsums whose sharding constraints make XLA insert the token all-to-all
over ICI — no ragged buffers, no host-side routing. GShard-style
capacity discipline: each expert processes at most
`ceil(tokens·top_k/num_experts · capacity_factor)` tokens; overflow
tokens fall through the residual connection (standard drop semantics).

Parity property used by tests: with every expert initialised to the
same weights, normalised top-k routing makes the MoE block exactly
equal to its dense FFN (Σ w_k · F(x) = F(x)), so correctness reduces to
dense-FFN parity plus sharding-invariance on an ep>1 mesh.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import regions as R


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_jitter: float = 0.0    # train-time router noise (0 = off)


def expert_capacity(num_tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    return max(1, int(math.ceil(
        num_tokens * top_k / num_experts * capacity_factor)))


@R.region(R.MOE_EXPERTS)
def moe_ffn(x: jax.Array,
            router_w: jax.Array,
            gate_w: jax.Array, up_w: jax.Array, down_w: jax.Array,
            *, top_k: int, capacity_factor: float,
            constrain=None,
            rngs: Optional[jax.Array] = None,
            router_jitter: float = 0.0
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Apply the MoE FFN block.

    x: (b, s, d). router_w: (d, E). gate/up_w: (E, d, f); down_w:
    (E, f, d). `constrain(arr, logical_axes)` applies sharding
    constraints (models pass their mesh-bound constrainer). Returns
    (output (b, s, d), aux metrics incl. load-balance loss).
    """
    b, s, d = x.shape
    E = router_w.shape[-1]
    T = b * s
    C = expert_capacity(T, E, top_k, capacity_factor)
    cdtype = x.dtype

    xf = x.reshape(T, d)
    with R.region(R.MOE_ROUTE):
        logits = (xf @ router_w.astype(cdtype)).astype(jnp.float32)  # (T, E)
        if router_jitter and rngs is not None:
            logits = logits + router_jitter * jax.random.normal(
                rngs, logits.shape)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = lax.top_k(probs, top_k)                    # (T, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)    # renorm

    # Position of each (token, k) assignment within its expert's queue:
    # flatten assignments k-major so k=0 choices win capacity ties.
    assign = jax.nn.one_hot(top_e, E, dtype=jnp.int32)        # (T, k, E)
    flat = assign.transpose(1, 0, 2).reshape(top_k * T, E)    # (kT, E)
    pos_flat = jnp.cumsum(flat, axis=0) - flat                # (kT, E)
    pos = pos_flat.reshape(top_k, T, E).transpose(1, 0, 2)    # (T, k, E)
    within = (pos * assign).sum(-1)                           # (T, k)
    keep = within < C                                         # capacity

    # dispatch (T, k, E, C) one-hot -> collapsed over k to (T, E, C)
    disp = (assign[..., None]
            * jax.nn.one_hot(within, C, dtype=jnp.int32)[:, :, None, :]
            * keep[:, :, None, None].astype(jnp.int32))       # (T,k,E,C)
    combine = (disp.astype(jnp.float32)
               * top_p[:, :, None, None]).sum(1)              # (T, E, C)
    dispatch = disp.sum(1).astype(cdtype)                     # (T, E, C)

    # expert inputs: the big resharding einsum — tokens (dp-sharded)
    # -> expert-major (ep-sharded): XLA inserts the all-to-all here.
    ein = jnp.einsum("tec,td->ecd", dispatch, xf)             # (E, C, d)
    if constrain is not None:
        ein = constrain(ein, ("experts", "expert_capacity", "embed"))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein,
                               gate_w.astype(cdtype)))
    h = h * jnp.einsum("ecd,edf->ecf", ein, up_w.astype(cdtype))
    if constrain is not None:
        h = constrain(h, ("experts", "expert_capacity", "mlp"))
    eout = jnp.einsum("ecf,efd->ecd", h, down_w.astype(cdtype))
    if constrain is not None:
        eout = constrain(eout, ("experts", "expert_capacity", "embed"))

    y = jnp.einsum("tec,ecd->td", combine.astype(cdtype), eout)
    y = y.reshape(b, s, d)

    # Aux: switch-style load-balance loss + routing stats.
    with R.region(R.MOE_ROUTE):
        frac_tokens = jnp.mean(assign[:, 0, :].astype(jnp.float32), axis=0)
        frac_probs = jnp.mean(probs, axis=0)
        lb_loss = E * jnp.sum(frac_tokens * frac_probs)
        dropped = 1.0 - (jnp.sum(dispatch) / (T * top_k))
    return y, {"moe_load_balance_loss": lb_loss,
               "moe_dropped_fraction": dropped.astype(jnp.float32)}


MOE_PARAM_AXES = {
    "router": ("embed", None),
    "moe_gate": ("experts", "embed", "mlp"),
    "moe_up": ("experts", "embed", "mlp"),
    "moe_down": ("experts", "mlp", "embed"),
}


# ------------------------------------------------------------ dropless
SCORING = {"sigmoid": jax.nn.sigmoid,
           "softmax": functools.partial(jax.nn.softmax, axis=-1)}


@R.region(R.MOE_ROUTE)
def route_topk(x: jax.Array, router_w: jax.Array, bias: jax.Array, *,
               top_k: int, norm_topk_prob: bool = True,
               scale: float = 1.0, scoring: str = "sigmoid",
               n_group: int = 1, topk_group: int = 1
               ) -> Tuple[jax.Array, jax.Array]:
    """Scores in float32, `scoring` a key of `SCORING` (each slot's sigmoid,
    or a softmax over all slots): choose the top-k of `score + bias` (the
    bias moves the choice only), weigh by the score itself, divide by the
    chosen scores' sum where `norm_topk_prob`, times `scale`. Under a group
    limit (`n_group` > 1) the slots are `n_group` runs of equal length, a
    group's score is the sum of its two largest `score + bias`, and the
    choice is among the slots of the `topk_group` best groups alone.

    x (T, d); router_w (d, slots); bias (slots,). Returns (slots chosen
    (T, k) int32, weights (T, k) float32)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = SCORING[scoring](logits)
    choice = scores + bias.astype(jnp.float32)
    if n_group > 1:
        T, slots = choice.shape
        if slots % n_group or not 0 < topk_group <= n_group:
            raise ValueError(f"{slots} slots in {n_group} groups, "
                             f"{topk_group} kept")
        grouped = choice.reshape(T, n_group, slots // n_group)
        best2 = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)  # (T, n_group)
        kept = best2 >= lax.top_k(best2, topk_group)[0][:, -1:]
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(
            T, slots)
    _, top_e = lax.top_k(choice, top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if norm_topk_prob:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_e.astype(jnp.int32), top_w * scale


# An expert's form: "swiglu" `(silu(x W_gate) * x W_up) W_down`, three
# matrices; "relu2" `relu(x W_up)^2 W_down`, two and no gate.
EXPERT_FORMS = ("swiglu", "relu2")


@R.region(R.MOE_EXPERTS)
def dropless_moe_ffn(x: jax.Array, router_w: jax.Array, bias: jax.Array,
                     gate_w: Optional[jax.Array], up_w: jax.Array,
                     down_w: jax.Array,
                     *, top_k: int, norm_topk_prob: bool = True,
                     scale: float = 1.0,
                     valid: Optional[jax.Array] = None,
                     scoring: str = "sigmoid", zero_experts: int = 0,
                     held: Optional[Tuple[int, int]] = None,
                     expert_form: str = "swiglu",
                     expert_input: Optional[jax.Array] = None,
                     n_group: int = 1, topk_group: int = 1
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """y_t = sum over the k slots token t chose of w_ti * E_i(z_t), `E_i`
    of the form `expert_form` names (`EXPERT_FORMS`; one without a gate
    does not read `gate_w`) and `z` the experts' input: x itself, or
    `expert_input` (T, d') where the experts read something narrower than
    the router does (a latent the caller projected x into: the result is
    then d' wide too, the k pairs of a token summed there in float32, and
    the way back to x's width is the caller's, once a token). No token is
    dropped whatever the imbalance.

    The router is `slots` wide: the experts, then `zero_experts` slots
    that compute nothing, for which `E_i(x) = x` (a pair that chose one
    adds `w x` and is no row of the grouped matmul). `held` = `(first,
    count)` says which of the experts the given matrices are, one chip's
    share of a layer divided over chips: the layer routes over all slots,
    computes its own experts' rows and the identity part of every token it
    has, and leaves out what the experts held elsewhere would add (their
    pairs sort past the last held expert, as padding does). None: all.
    `n_group`, `topk_group`: `route_topk`'s group limit, over all the
    slots whatever is held.

    x (T, d); gate_w / up_w (held, d', f); down_w (held, f, d'), d' = d
    without an `expert_input`. `valid`
    (T,) bool: tokens that are padding get no pair and a zero result.
    Returns (y (T, d'), {"pairs": pairs given to held experts, "touched":
    held experts with a pair, "load": (held,) pairs an expert,
    "zero_pairs": pairs of slots that compute nothing, "away_pairs": pairs
    of experts held elsewhere}), the counts int32 on the device."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul
    if expert_form not in EXPERT_FORMS:
        raise ValueError(f"expert form {expert_form!r}; forms: "
                         f"{EXPERT_FORMS}")
    z = x if expert_input is None else expert_input
    T, d = z.shape
    experts = router_w.shape[-1] - zero_experts
    first, E = held or (0, experts)
    if E != up_w.shape[0] or not 0 <= first <= experts - E:
        raise ValueError(f"experts {first}..{first + E} of {experts} held, "
                         f"{up_w.shape[0]} given")
    top_e, top_w = route_topk(x, router_w, bias, top_k=top_k,
                              norm_topk_prob=norm_topk_prob, scale=scale,
                              scoring=scoring, n_group=n_group,
                              topk_group=topk_group)
    zero_pairs = away_pairs = jnp.int32(0)
    identity = None
    with R.region(R.MOE_ROUTE):     # the pairs' counts
        if zero_experts or E != experts:
            live = jnp.broadcast_to(
                True if valid is None else valid[:, None], top_e.shape)
            zero = live & (top_e >= experts)
            here = (top_e >= first) & (top_e < first + E)
            zero_pairs = jnp.sum(zero).astype(jnp.int32)
            away_pairs = jnp.sum(live & ~zero & ~here).astype(jnp.int32)
            if zero_experts:
                identity = jnp.sum(jnp.where(zero, top_w, 0.0), axis=-1)
            top_e = jnp.where(here, top_e - first, E)
        if valid is not None:       # padding sorts past the last expert
            top_e = jnp.where(valid[:, None], top_e, E)
        flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)    # pairs sorted by expert
    with R.region(R.MOE_ROUTE):
        load = jnp.bincount(flat_e, length=E + 1)[:E].astype(jnp.int32)
    xs = z[order // top_k]
    if expert_form == "relu2":
        h = jnp.square(jax.nn.relu(grouped_matmul(xs, up_w, load)))
    else:
        h = (jax.nn.silu(grouped_matmul(xs, gate_w, load))
             * grouped_matmul(xs, up_w, load))
    # (the rows of padding, past the last expert's, come back as zeros)
    ys = grouped_matmul(h, down_w, load).astype(jnp.float32)
    ys = ys * top_w.reshape(-1)[order][:, None]
    # back to token order: the inverse of the sort, then the k pairs of a
    # token summed in float32
    y = ys[jnp.argsort(order)].reshape(T, top_k, d).sum(axis=1)
    if identity is not None:
        y = y + identity[:, None] * z.astype(jnp.float32)
    y = y.astype(z.dtype)
    with R.region(R.MOE_ROUTE):
        return y, {
            "pairs": jnp.sum(load), "touched": jnp.sum(load > 0).astype(
                jnp.int32), "load": load, "zero_pairs": zero_pairs,
            "away_pairs": away_pairs}


# What a decode step counts over its expert layers, by the names the
# engine's counters take (a class whose layers hold all their experts
# writes the leading three: none is away, no slot computes nothing);
# `step_counts` gives one layer's, in this order.
STEP_COUNTS = ("moe_pairs", "moe_experts_touched", "moe_load_max",
               "moe_zero_pairs", "moe_away_pairs")


def step_counts(counts: Dict[str, jax.Array]) -> Tuple[jax.Array, ...]:
    return (counts["pairs"], counts["touched"], jnp.max(counts["load"]),
            counts["zero_pairs"], counts["away_pairs"])


@R.region(R.FFN)
def swiglu(x: jax.Array, gate_w, up_w, down_w) -> jax.Array:
    """The dense feed-forward `(SiLU(x W_gate) * x W_up) W_down`, the
    weights cast to x's dtype."""
    gate = jax.nn.silu(x @ gate_w.astype(x.dtype))
    return (gate * (x @ up_w.astype(x.dtype))) @ down_w.astype(x.dtype)


class DenseOrRoutedFFN:
    """The feed-forward of a class whose layers have a SwiGLU or, where
    the layer has a `"router"`, `dropless_moe_ffn` over all the layer's
    experts, plus a shared expert applied to every token where the layer
    has one (`"shared_gate"`, `"shared_up"`, `"shared_down"`). The class says
    `_routing(layer)`: (the router's bias, `dropless_moe_ffn`'s `top_k`,
    `norm_topk_prob` and `scale`, and `held` and the group limit where it
    has them). `PagedDecoder._block_ffn` puts it behind its norm."""

    def _ffn(self, layer, x, valid=None):
        """Feed-forward of one layer on tokens x (T, e) after the norm.
        Returns (y, expert counts or None for a dense layer)."""
        if "router" not in layer:
            return swiglu(x, layer["gate"], layer["up"],
                          layer["down"]), None
        bias, how = self._routing(layer)
        y, counts = dropless_moe_ffn(
            x, layer["router"], bias, layer["moe_gate"], layer["moe_up"],
            layer["moe_down"], valid=valid, **how)
        if "shared_gate" not in layer:
            return y, counts
        with R.region(R.FFN):       # the shared expert, added
            return y + swiglu(x, layer["shared_gate"], layer["shared_up"],
                              layer["shared_down"]), counts
