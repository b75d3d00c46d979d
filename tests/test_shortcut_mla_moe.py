"""The fifth architecture (double layers of two latent attentions and two
dense feed-forwards with a shortcut expert branch, a softmax router over
experts and slots that compute nothing, one chip's share of the experts)
against `benchmarks/models/longcat_flash.py`'s plain reference and against
itself: the full forward, prefill then decode through both of a layer's
pool rows, the same with the three kernels under the Pallas interpreter,
the control, the shares of a layer adding up to the uncut layer, the
router's cases, the flash forward at unlike key and value widths, and the
engine's counters and spans.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import modelcfg                      # noqa: E402
from benchmarks.harness.reference import _ident, rel_rms     # noqa: E402
from benchmarks.harness.weights import make_weights          # noqa: E402
from ray_tpu.models import (ShortcutMLAMoE, ShortcutMLAMoEConfig,  # noqa: E402
                            build_model, model_config)
from ray_tpu.models import latent                            # noqa: E402
from ray_tpu.models.gqa import FULL_BLOCKS                   # noqa: E402
from ray_tpu.models.moe import (STEP_COUNTS,                 # noqa: E402
                                dropless_moe_ffn, route_topk)
from ray_tpu.models.shortcut_mla_moe import (                # noqa: E402
    tiny_shortcut_mla_moe)
from ray_tpu.ops import attention as attn                    # noqa: E402
from ray_tpu.ops import grouped_matmul as gmm                # noqa: E402
from ray_tpu.ops import paged_attention as paged             # noqa: E402
from ray_tpu.serve.llm import spans as sp                    # noqa: E402
from ray_tpu.serve.llm.engine import EngineCore, _bucket     # noqa: E402
from test_llm_tracing import recorder                        # noqa: E402,F401

CONFIG = "longcat-flash-chat-1chip"


def _ref(**sizes):
    """(model module, Sizes, seeded float32 weights, the program's model)
    at `tiny(cfg)`, `sizes` changing keys of the tiny file."""
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    small = {**mod.tiny(cfg), **sizes}
    sz = mod.sizes(small)
    params = make_weights(mod.weight_shapes(sz), 7, dtype=jnp.float32)
    pc = mod.program_config(small, 128, dtype="float32",
                            param_dtype="float32")
    return mod, sz, params, build_model(pc)


@pytest.fixture(scope="module")
def tiny_ref():
    return _ref()


def _tokens(vocab, n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, n),
                       jnp.int32)


# ------------------------------------------------------- full forward
def test_apply_matches_the_reference_logits(tiny_ref):
    mod, sz, params, model = tiny_ref
    assert isinstance(model, ShortcutMLAMoE)
    assert (sz.first_held, sz.held, sz.experts, sz.zero) == (4, 4, 16, 8)
    toks = _tokens(sz.vocab, 48)
    got = model.apply(params, toks[None])[0]
    assert rel_rms(got, mod.logits_fn(sz, params, toks)) < 1e-5
    # the jitted parts `reference_rows` is made of give the same rows
    padded = jnp.zeros((64,), jnp.int32).at[:48].set(toks)
    rows = mod.reference_rows(sz, params, padded, jnp.int32(40), 8)
    assert rel_rms(rows, got[40:48]) < 1e-5


def test_the_fp8_control_is_told_from_the_reference(tiny_ref):
    mod, sz, params, _ = tiny_ref
    toks = jnp.zeros((128,), jnp.int32).at[:40].set(_tokens(sz.vocab, 40))
    args = (sz, params, toks, jnp.int32(31), 9)
    err = rel_rms(mod.reference_rows(*args, True),
                  mod.reference_rows(*args, False))
    assert err > 0.02


def test_served_path_in_bf16_passes_and_the_fp8_control_fails():
    """What decides `correct`, at this size: prefill then 8 decode steps in
    the configuration's own precision against the float32 reference, and
    the reference in fp8 put in the system's place (sound reads 0.005, the
    control 0.036-0.04)."""
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    small = mod.tiny(cfg)
    sz = mod.sizes(small)
    params = make_weights(mod.weight_shapes(sz), 6)
    model = build_model(mod.program_config(small, 128))
    assert model.config.dtype == "bfloat16"
    p, steps, limit = 21, 8, 0.015
    toks = _tokens(sz.vocab, p + steps, seed=6)
    got, _, _ = _prefill_decode(model, params, np.asarray(toks), p, steps)
    padded = jnp.zeros((64,), jnp.int32).at[:p + steps].set(toks)
    args = (sz, params, padded, jnp.int32(p - 1), steps + 1)
    want = mod.reference_rows(*args)
    assert rel_rms(got, want) <= limit < rel_rms(
        mod.reference_rows(*args, True), want)


# ------------------------------------------- prefill, decode, the cache
def _prefill_decode(model, params, toks, p, steps, page=8, pages=16,
                    lanes=3, lane=1):
    """Logits of positions p - 1 .. p + steps - 1: one prefill of the first
    p tokens, then `steps` decode steps in one lane of `lanes`, the pages
    handed out in a shuffled order. Returns (rows, cache, the steps'
    counts)."""
    cache = model.init_cache(pages, page)
    order = np.random.default_rng(3).permutation(pages)
    held = -(-(p + steps) // page)
    pt = np.full((pages,), -1, np.int32)
    pt[:held] = order[:held]
    padded = np.zeros((32,), np.int32)
    padded[:p] = toks[:p]
    pre = jax.jit(lambda *a: model.prefill(*a, page), donate_argnums=(4,))
    step = jax.jit(lambda *a: model.decode_step(*a, page),
                   donate_argnums=(1,))
    logits, cache = pre(params, jnp.asarray(padded), jnp.int32(p),
                        jnp.asarray(pt), cache)
    rows, counts = [logits], []
    for k in range(steps):
        tokens = np.zeros((lanes,), np.int32)
        positions = np.zeros((lanes,), np.int32)
        pts = np.full((lanes, pages), -1, np.int32)
        active = np.zeros((lanes,), bool)
        tokens[lane], positions[lane] = toks[p + k], p + k
        pts[lane], active[lane] = pt, True
        logits, cache = step(params, cache, jnp.asarray(tokens),
                             jnp.asarray(positions), jnp.asarray(pts),
                             jnp.asarray(active))
        rows.append(logits[lane])
        counts.append({k: int(v) for k, v in cache["moe_step"].items()})
    return jnp.stack(rows), cache, counts


@pytest.mark.parametrize("p", [5, 16, 23])
def test_prefill_then_eight_decode_steps_match_the_reference(tiny_ref, p):
    mod, sz, params, model = tiny_ref
    steps = 8
    toks = _tokens(sz.vocab, p + steps, seed=p)
    got, cache, counts = _prefill_decode(model, params, np.asarray(toks), p,
                                         steps)
    assert rel_rms(got, mod.logits_fn(sz, params, toks)[p - 1:]) < 1e-5
    c = model.config
    # a layer owns two rows of the pool, both written at every position
    assert cache["kv"].shape[0] == 2 * c.n_layers == model.pool_rows
    assert all(float(jnp.abs(cache["kv"][r]).max()) > 0
               for r in range(model.pool_rows))
    # every choice of the one lane is a held, a zero or an away pair
    for n in counts:
        assert set(n) == set(STEP_COUNTS)
        assert (n["moe_pairs"] + n["moe_zero_pairs"] + n["moe_away_pairs"]
                == c.num_experts_per_tok * c.n_layers)
        assert n["moe_experts_touched"] <= n["moe_pairs"]
    assert sum(n["moe_zero_pairs"] for n in counts) > 0
    assert sum(n["moe_away_pairs"] for n in counts) > 0
    assert int(cache["moe_load"].sum()) == sum(n["moe_pairs"]
                                               for n in counts)
    assert cache["moe_load"].shape == (c.n_layers, 4)


def test_served_path_with_the_kernels_under_the_interpreter(monkeypatch):
    """Prefill through the flash forward at unlike widths and the grouped
    matmul, eight decode steps through the latent paged kernel and the
    grouped matmul, all three through the Pallas interpreter, at sizes the
    kernels tile."""
    mod, sz, params, model = _ref(hidden_size=128, kv_lora_rank=128,
                                  q_lora_rank=64, ffn_hidden_size=256,
                                  expert_ffn_hidden_size=128)
    calls = {"flash": 0, "mla": 0, "gmm": 0}

    def counted(name, fn):
        def call(*a, **k):
            calls[name] += 1
            return fn(*a, **k)
        return call

    monkeypatch.setattr(latent, "flash_attention", counted(
        "flash", lambda q, k, v, causal, sm_scale, block_q, block_k:
        attn.flash_attention_kernel(q, k, v, causal, sm_scale, block_q,
                                    block_k)))
    monkeypatch.setattr(paged, "mla_paged_decode_attention", counted(
        "mla", paged.mla_paged_decode_attention_kernel))
    monkeypatch.setattr(gmm, "grouped_matmul", counted(
        "gmm", gmm.grouped_matmul_kernel))
    assert paged.mla_paged_decode_tiles(model.config.row_width, 128, 8,
                                        jnp.float32)
    p, steps = 21, 8
    toks = _tokens(sz.vocab, p + steps, seed=11)
    got, _, _ = _prefill_decode(model, params, np.asarray(toks), p, steps,
                                lanes=4)
    # traced once a program: 4 attentions and 6 grouped matmuls each
    assert calls == {"flash": 4, "mla": 4, "gmm": 12}
    assert rel_rms(got, mod.logits_fn(sz, params, toks)[p - 1:]) < 1e-4


def test_both_attentions_of_a_layer_walk_the_run_the_class_answered(
        monkeypatch):
    """`page_run` at the reason4k cell's shapes is the rule's 4 where the
    kernel runs and 1 on the CPU; a step hands both attentions' kernels
    what the class answered (its own `decode_step` sets the `Walk`'s)."""
    from ray_tpu.ops.dispatch import compute_platform
    served = ShortcutMLAMoE(ShortcutMLAMoEConfig(
        n_layers=1, experts_held=(0, 16)))       # the published widths
    assert served.page_run(16, 256) == 1                     # off the TPU
    with compute_platform("tpu"):
        assert served.fixed_pages(16) == 0
        assert served.page_run(16, 256) == 4     # 20,480 B a page
    handed = []
    real = paged.mla_paged_decode_attention

    def spy(*a):
        handed.append(a[7])
        return real(*a[:7])
    monkeypatch.setattr(paged, "mla_paged_decode_attention", spy)
    monkeypatch.setattr(ShortcutMLAMoE, "page_run", lambda self, *a: 4)
    cfg = tiny_shortcut_mla_moe()
    model = ShortcutMLAMoE(cfg)
    B, page = 2, 8
    mp = cfg.max_seq_len // page
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)     # noqa
    jax.eval_shape(
        lambda p, c, t, pos, pts, a: model.decode_step(p, c, t, pos, pts, a,
                                                       page),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        jax.eval_shape(lambda: model.init_cache(B * mp, page)),
        i32(B), i32(B), i32(B, mp), jax.ShapeDtypeStruct((B,), jnp.bool_))
    assert handed == [4] * 2 * cfg.n_layers


# --------------------------------------------------- the shares add up
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(tiny_ref):
    """Four chips share a layer of 16 experts, 4 each: the held parts of
    all four plus the identity part counted once are the uncut reference's
    `MoE(u)`, in the reference and in the program."""
    import dataclasses
    mod, sz, _, _ = tiny_ref
    whole = dataclasses.replace(sz, first_held=0, held=sz.experts)
    layer = make_weights(mod.weight_shapes(whole)["layers"][0], 13,
                         dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(4), (40, sz.d_model))
    uncut = mod._experts(whole, u, layer, _ident)
    weight = mod.slot_weights(whole, u, layer)
    identity = jnp.sum(weight[:, sz.experts:], axis=-1, keepdims=True) * u
    assert float(jnp.abs(identity).max()) > 0
    ref_parts, prog_parts, pairs = [], [], 0
    for first in range(0, sz.experts, 4):
        share = dataclasses.replace(sz, first_held=first, held=4)
        mine = {**layer, **{k: layer[k][first:first + 4]
                            for k in ("moe_gate", "moe_up", "moe_down")}}
        ref_parts.append(mod._experts(share, u, mine, _ident) - identity)
        y, counts = dropless_moe_ffn(
            u, mine["router"], mine["router_bias"], mine["moe_gate"],
            mine["moe_up"], mine["moe_down"], top_k=sz.top_k,
            norm_topk_prob=False, scale=sz.route_scale, scoring="softmax",
            zero_experts=sz.zero, held=(first, 4))
        prog_parts.append(y - identity)
        pairs += int(counts["pairs"])
        assert (int(counts["pairs"]) + int(counts["zero_pairs"])
                + int(counts["away_pairs"])) == 40 * sz.top_k
    assert rel_rms(sum(ref_parts) + identity, uncut) < 1e-5
    assert rel_rms(sum(prog_parts) + identity, uncut) < 1e-5
    # and the experts' parts alone, which are small beside the identity
    # part at seeded weights (float32 leaves them three digits)
    assert rel_rms(sum(ref_parts), uncut - identity) < 5e-3
    assert rel_rms(sum(prog_parts), uncut - identity) < 5e-3
    # every pair of an expert is some share's, and none is two shares'
    assert pairs == int(jnp.sum(weight[:, :sz.experts] > 0))
    # one share alone is not the layer
    assert rel_rms(ref_parts[0], uncut - identity) > 0.5


# ------------------------------------------------------------ the router
def _layer_weights(seed=0, d=16, f=128, slots=12, experts=8):
    rng = np.random.default_rng(seed)

    def w(*shape, s=0.3):
        return jnp.asarray(rng.normal(size=shape) * s, jnp.float32)
    return {"router": w(d, slots, s=1.0), "bias": jnp.zeros((slots,)),
            "gate": w(experts, d, f), "up": w(experts, d, f),
            "down": w(experts, f, d)}


def _per_token_loop(x, w, top_k, scale, bias, zero, held):
    """The layer one token and one chosen slot at a time, in numpy."""
    first, count = held
    experts = w["router"].shape[1] - zero
    out = np.zeros_like(np.asarray(x, np.float64))
    for t, xt in enumerate(np.asarray(x, np.float64)):
        logits = xt @ np.asarray(w["router"], np.float64)
        score = np.exp(logits - logits.max())
        score /= score.sum()
        chosen = np.argsort(-(score + bias), kind="stable")[:top_k]
        for e, we in zip(chosen, score[chosen] * scale):
            if e >= experts:
                out[t] += we * xt
            elif first <= e < first + count:
                g = xt @ np.asarray(w["gate"][e - first], np.float64)
                u = xt @ np.asarray(w["up"][e - first], np.float64)
                out[t] += we * ((g / (1 + np.exp(-g)) * u)
                                @ np.asarray(w["down"][e - first],
                                             np.float64))
    return out


def test_softmax_scores_are_not_renormalised():
    w = _layer_weights()
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 16))
    top_e, top_w = route_topk(x, w["router"], w["bias"], top_k=3,
                              norm_topk_prob=False, scale=6.0,
                              scoring="softmax")
    scores = jax.nn.softmax(x @ w["router"], axis=-1)
    np.testing.assert_allclose(
        top_w, 6.0 * jnp.take_along_axis(scores, top_e, axis=1), rtol=1e-5)
    assert float(top_w.sum(axis=1).max()) < 6.0         # a part of the mass
    _, normed = route_topk(x, w["router"], w["bias"], top_k=3,
                           norm_topk_prob=True, scale=6.0,
                           scoring="softmax")
    np.testing.assert_allclose(normed.sum(axis=1), 6.0, rtol=1e-5)
    with pytest.raises(KeyError):
        route_topk(x, w["router"], w["bias"], top_k=3, scoring="tanh")


def test_the_bias_moves_the_choice_and_not_the_weight_under_softmax():
    w = _layer_weights(seed=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 16))
    bias = jnp.zeros((12,)).at[2].set(5.0)              # slot 2 always
    kw = dict(top_k=2, norm_topk_prob=False, scoring="softmax")
    plain_e, _ = route_topk(x, w["router"], w["bias"], **kw)
    top_e, top_w = route_topk(x, w["router"], bias, **kw)
    assert (top_e == 2).any(axis=1).all()
    assert not (plain_e == 2).any(axis=1).all()
    scores = jax.nn.softmax(x @ w["router"], axis=-1)
    np.testing.assert_allclose(
        top_w, jnp.take_along_axis(scores, top_e, axis=1), atol=1e-6)


@pytest.mark.parametrize("held", [(0, 8), (2, 4), (5, 3)])
def test_dropless_share_matches_a_per_token_loop(held):
    w = _layer_weights(seed=2)
    first, count = held
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 16))
    bias = np.random.default_rng(5).normal(size=12) * 0.05
    y, counts = dropless_moe_ffn(
        x, w["router"], jnp.asarray(bias, jnp.float32),
        w["gate"][first:first + count], w["up"][first:first + count],
        w["down"][first:first + count], top_k=3, norm_topk_prob=False,
        scale=6.0, scoring="softmax", zero_experts=4, held=held)
    sliced = {**w, **{k: w[k][first:first + count]
                      for k in ("gate", "up", "down")}}
    np.testing.assert_allclose(
        y, _per_token_loop(x, sliced, 3, 6.0, bias, 4, held), atol=3e-5)
    assert (int(counts["pairs"]) + int(counts["zero_pairs"])
            + int(counts["away_pairs"])) == 32 * 3
    assert counts["load"].shape == (count,)
    with pytest.raises(ValueError, match="held"):
        dropless_moe_ffn(x, w["router"], w["bias"], w["gate"], w["up"],
                         w["down"], top_k=3, zero_experts=4, held=(2, 4))


def test_an_identity_pair_adds_w_u_and_is_no_row_of_the_grouped_matmul():
    """The bias sends every token to zero slot 9 and to expert 0, which
    this share (experts 4..7) does not hold: the result is `w_9 u`, no
    expert gets a row or is touched, and the away pair adds nothing."""
    w = _layer_weights(seed=3)
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 16))
    bias = jnp.zeros((12,)).at[9].set(9.0).at[0].set(5.0)
    y, counts = dropless_moe_ffn(
        x, w["router"], bias, w["gate"][4:], w["up"][4:], w["down"][4:],
        top_k=2, norm_topk_prob=False, scale=6.0, scoring="softmax",
        zero_experts=4, held=(4, 4))
    scores = jax.nn.softmax(x @ w["router"], axis=-1)
    np.testing.assert_allclose(y, 6.0 * scores[:, 9:10] * x, rtol=1e-5,
                               atol=1e-6)
    assert {k: int(v) for k, v in counts.items() if k != "load"} == {
        "pairs": 0, "touched": 0, "zero_pairs": 16, "away_pairs": 16}
    assert not np.asarray(counts["load"]).any()
    # padding makes no pair of any kind and adds no identity part
    valid = jnp.arange(16) < 10
    y, counts = dropless_moe_ffn(
        x, w["router"], bias, w["gate"][4:], w["up"][4:], w["down"][4:],
        top_k=2, norm_topk_prob=False, scale=6.0, scoring="softmax",
        zero_experts=4, held=(4, 4), valid=valid)
    assert not np.asarray(y[10:]).any() and np.asarray(y[:10]).any()
    assert (int(counts["zero_pairs"]), int(counts["away_pairs"])) == (10, 10)


def test_defaults_are_the_layer_that_holds_every_expert():
    """Sigmoid, renormalised, no zero slot, every expert held: what the
    classes that were there trace (their own tests pin the numbers)."""
    w = _layer_weights(seed=4, slots=8)
    x = jax.random.normal(jax.random.PRNGKey(4), (16, 16))
    args = (x, w["router"], w["bias"], w["gate"], w["up"], w["down"])
    y, counts = dropless_moe_ffn(*args, top_k=2)
    same, _ = dropless_moe_ffn(*args, top_k=2, scoring="sigmoid",
                               zero_experts=0, held=(0, 8))
    np.testing.assert_array_equal(y, same)
    assert (int(counts["pairs"]), int(counts["zero_pairs"]),
            int(counts["away_pairs"])) == (32, 0, 0)


# ------------------------------------- the flash forward, unlike widths
@pytest.mark.parametrize("s,dk,dv,kvh", [(256, 192, 128, 4), (200, 32, 8, 4),
                                         (128, 64, 64, 2)])
def test_flash_forward_takes_values_narrower_than_keys(s, dk, dv, kvh):
    keys = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(keys[0], (1, 4, s, dk))
    k = jax.random.normal(keys[1], (1, kvh, s, dk))
    v = jax.random.normal(keys[2], (1, kvh, s, dv))
    scale = 1.0 / np.sqrt(dk)
    got = attn.flash_attention_kernel(q, k, v, True, scale, 128, 128)
    assert got.shape == (1, 4, s, dv)
    want = attn.mha_reference(q, k, v, causal=True, sm_scale=scale)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("bucket", [1 << n for n in range(4, 15)])
def test_prefill_blocks_tile_every_bucket(bucket):
    """For every bucket `_bucket` makes of a prompt up to 16,384: the
    latent prefills' blocks, cut to the bucket as the call cuts them, are
    whole (8, 128) tiles or the bucket itself and divide it (no tail
    block); none larger than the GQA classes', the largest swept."""
    assert _bucket(bucket) == bucket == _bucket(bucket // 2 + 1)
    assert max(latent.PREFILL_BLOCKS) <= max(FULL_BLOCKS)
    for block in latent.PREFILL_BLOCKS:
        block = min(block, bucket)
        assert block % 8 == 0 and bucket % block == 0
        assert block % 128 == 0 or block == bucket


def test_flash_backward_refuses_unlike_widths():
    q = jnp.ones((1, 2, 128, 32))
    v = jnp.ones((1, 2, 128, 16))
    with pytest.raises(NotImplementedError, match="forward"):
        jax.grad(lambda q_: attn.flash_attention_kernel(
            q_, q, v, True, 0.2, 128, 128).sum())(q)


# ------------------------------------------------------------ the engine
def _greedy(model, params, prompt, n, pad=32):
    """Greedy tokens by the full forward, one jitted program: the sequence
    padded at its end (causal: the padding touches nothing before it)."""
    apply = jax.jit(model.apply)
    toks = list(prompt)
    for _ in range(n):
        padded = jnp.zeros((1, pad), jnp.int32).at[0, :len(toks)].set(
            jnp.asarray(toks, jnp.int32))
        toks.append(int(apply(params, padded)[0, len(toks) - 1].argmax()))
    return toks[len(prompt):]


def test_engine_core_serves_it_and_counts_its_pairs(recorder):   # noqa: F811
    cfg = tiny_shortcut_mla_moe()
    model = ShortcutMLAMoE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    core = EngineCore(cfg, params, num_pages=6, page_size=8, max_batch=3)
    assert isinstance(core.model, ShortcutMLAMoE)
    prompts = {"a": [3, 17, 91, 254, 8, 1, 2, 9, 11, 30],
               "b": [5, 6, 7], "c": [200, 100, 50, 25, 12, 6, 3]}
    for rid, n in (("a", 9), ("b", 8), ("c", 6)):
        core.submit(prompts[rid], max_tokens=n, rid=rid)
    got = {rid: [] for rid in prompts}
    for _ in range(200):
        if not core.has_work:
            break
        for ev in core.step():
            got[ev["rid"]].append(ev["token"])
    for rid, n in (("a", 9), ("b", 8), ("c", 6)):
        assert got[rid] == _greedy(model, params, prompts[rid], n), rid
    c = core.counters
    assert (c["moe_pairs"] + c["moe_zero_pairs"] + c["moe_away_pairs"]
            == c["decode_lane_steps"] * cfg.num_experts_per_tok
            * cfg.n_layers)
    assert 0 < c["moe_experts_touched"] <= c["moe_pairs"]
    assert c["moe_zero_pairs"] > 0 and c["moe_away_pairs"] > 0
    st = core.device_stats()
    assert st["decode_attention"] == "einsum"
    # two pool rows a layer, 128 numbers a row, float32
    assert st["cache_bytes_per_position"] == 2 * cfg.n_layers * 128 * 4
    assert np.asarray(st["moe_load"]).shape == (cfg.n_layers, 4)
    assert np.asarray(st["moe_load"]).sum() == c["moe_pairs"]
    # the set-up span says how many rows the pool has, and a step's emit
    # span carries the five counts
    spans = {e[4]: e[7] for e in recorder.snapshot()
             if e[4] in (sp.SETUP_CACHE, sp.EMIT) and e[7]}
    assert spans[sp.SETUP_CACHE]["pool_rows"] == 4
    assert set(STEP_COUNTS) <= set(spans[sp.EMIT])


def test_a_config_names_the_class_and_refusals_are_plain():
    cfg = model_config({"type": "shortcut_mla_moe", "d_model": 64,
                        "experts_held": (0, 16)})
    assert isinstance(cfg, ShortcutMLAMoEConfig)
    assert cfg.held == (0, 16) and cfg.router_slots == 768
    assert ShortcutMLAMoEConfig().held == (0, 512)
    assert ShortcutMLAMoEConfig().q_lora_scale == 2.0
    assert ShortcutMLAMoEConfig().kv_lora_scale == pytest.approx(12 ** 0.5)
    assert ShortcutMLAMoEConfig(mla_scale_kv_lora=False).kv_lora_scale == 1
    with pytest.raises(ValueError, match="experts_held"):
        ShortcutMLAMoEConfig(experts_held=(500, 16))
    with pytest.raises(ValueError, match="scoring"):
        ShortcutMLAMoEConfig(scoring_func="tanh")
    from ray_tpu.parallel.mesh import MeshSpec
    mesh = MeshSpec(dp=1, tp=2).build(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="no mesh"):
        ShortcutMLAMoE(tiny_shortcut_mla_moe(), mesh=mesh)
