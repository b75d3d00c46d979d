"""The model module `models/kda_mla_moe.py` under the comparison that decides
`correct`: at a size a test run can hold, the program in bf16 through the
engine's own prefill and decode programs (a padded bucket, then steps
through the state slot, the latent rows and the held experts) passes against
the float32 reference, and the control (the reference in fp8) does not; the
required operations pinned by hand arithmetic, the configuration's keys
against the published values, the parameter count; then the new cell walked
at rehearsal size. The limit here is this size's own; the chip-size readings
are in the configuration file and PERF.md."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import modelcfg
from benchmarks.harness.reference import rel_rms
from benchmarks.harness.weights import make_weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "ling-3.0-flash-vl-1chip"
CELL = CONFIG + ".serve.docs16k"


@pytest.fixture(scope="module")
def small():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    cfg = model.tiny(cfg)
    cfg.update(hidden_size=256, num_attention_heads=4, head_dim=32,
               kda_chunk_size=16, kv_lora_rank=96, qk_nope_head_dim=32,
               qk_rope_head_dim=32, rotary_dim=32, v_head_dim=32,
               intermediate_size=512, moe_intermediate_size=96,
               moe_shared_expert_intermediate_size=96)
    sz = model.sizes(cfg)
    return cfg, model, sz, make_weights(model.weight_shapes(sz), 5)


def test_served_logits_pass_and_the_fp8_control_fails(small):
    from ray_tpu.serve.llm.engine import EngineCore
    cfg, model, sz, params = small
    limit = 0.06        # this size: sound reads 0.02, the control 0.2
    core = EngineCore(model.program_config(cfg, 256), params, num_pages=40,
                      page_size=8, max_batch=2)
    p, steps = 90, 40           # a bucket of 128: 38 padded positions
    toks = np.zeros((256,), np.int32)
    toks[:p + steps] = np.random.default_rng(0).integers(0, sz.vocab,
                                                         p + steps)
    pages = core.alloc.alloc(-(-(p + steps) // 8))
    pt = np.full((core.max_pages_per_seq,), -1, np.int32)
    pt[:len(pages)] = pages
    padded = np.zeros((128,), np.int32)
    padded[:p] = toks[:p]
    logits, core._cache = core._prefill_fn(128)(
        params, jnp.asarray(padded), jnp.int32(p), jnp.asarray(pt),
        core._cache)
    rows = [logits]
    for k in range(steps):
        lane = lambda a, dt: jnp.asarray(np.array([0, a], dt))   # noqa
        logits, core._cache = core._decode_fn(
            params, core._cache, lane(toks[p + k], np.int32),
            lane(p + k, np.int32),
            jnp.asarray(np.stack([np.full_like(pt, -1), pt])),
            lane(True, bool))
        rows.append(logits[1])
    want = model.reference_rows(sz, params, jnp.asarray(toks),
                                jnp.int32(p - 1), steps + 1)
    control = model.reference_rows(sz, params, jnp.asarray(toks),
                                   jnp.int32(p - 1), steps + 1, True)
    sound_err = rel_rms(jnp.stack(rows), want)
    control_err = rel_rms(control, want)
    print("sound", sound_err, "control", control_err)
    assert sound_err <= limit < control_err
    assert control_err > 3 * sound_err


def test_reference_prefix_is_untouched_by_padding(small):
    _, model, sz, params = small
    toks = np.random.default_rng(2).integers(0, sz.vocab, 128)
    padded = np.zeros(256, np.int64)
    padded[:128] = toks
    a = model.reference_rows(sz, params, jnp.asarray(toks, jnp.int32),
                             jnp.int32(100), 8)
    b = model.reference_rows(sz, params, jnp.asarray(padded, jnp.int32),
                             jnp.int32(100), 8)
    assert rel_rms(a, b) < 1e-5


def test_the_configuration_holds_the_published_values():
    cfg = modelcfg.load_config(CONFIG)
    published = {
        "hidden_size": 2560, "num_attention_heads": 32, "head_dim": 128,
        "num_key_value_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "rotary_dim": 64, "short_conv_kernel_size": 4,
        "intermediate_size": 6144, "moe_intermediate_size": 768,
        "moe_shared_expert_intermediate_size": 768,
        "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
        "routed_scaling_factor": 2.5, "score_function": "sigmoid",
        "layer_group_size": 6, "kda_lower_bound": -5, "kda_safe_gate": True,
        "no_kda_lora": True, "rope_theta": 6000000, "rms_norm_eps": 1e-06,
        "gated_attention_proj_granularity_type": "head_wise",
        "image_patch_token": 157157}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "max_position_embeddings", "expert_swiglu_limit_list",
        "share_expert_swiglu_limit_list"]
    pub = cfg["published"]
    assert {k: pub[k] for k in cfg["reduced"][:5]} == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184,
        "max_position_embeddings": 131072}
    assert len(pub["expert_swiglu_limit_list"]) == 42 == len(
        pub["share_expert_swiglu_limit_list"])
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_experts"], cfg["vocab_size"]) == (7, 1, 128, 39296)
    dep = cfg["deployment"]
    assert dep["layers_held"] == [0, 6, 7, 8, 9, 10, 11]
    # the held layers' limits are the published lists' entries, all 0
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        assert cfg[key] == [pub[key][l] for l in dep["layers_held"]]
        assert not any(cfg[key])
    assert dep["chips_sharing_a_layer"] == 4
    assert dep["experts_held"] == [0, 128] and dep["vocab_share"] == "1/4"
    assert cfg["vocab_size"] * 4 == pub["vocab_size"]
    assert dep["max_batch"] == 32 and dep["context_limit"] == 16384
    assert dep["num_pages"] * dep["page_size"] == 32 * 16384
    assert set(cfg["not_held"]) == {"vision_tower", "multi_token_prediction",
                                    "clamped_swiglu", "training"}
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    assert sz.experts == 512 and sz.held == 128 and sz.top_k == 8
    assert sz.layer_types == ("linear_attention",) * 6 + (
        "latent_attention",)
    assert sz.mlp_types == ("dense",) + ("E",) * 6
    assert (len(sz.of_kind("linear_attention")), sz.attentions,
            len(sz.of_kind("E"))) == (6, 1, 6)
    # a clamped layer is refused, not computed without its clamp
    clamped = dict(cfg, expert_swiglu_limit_list=[0] * 6 + [4])
    with pytest.raises(ValueError, match="clamped"):
        model.sizes(clamped)


def test_the_parameter_count_is_the_issues_arithmetic():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    kda = (2560 * 12288 + 2 * 2560 * 4096 + 2560 * 32 + 4 * 12288 + 32
           + 32 * 128 + 128 + 4096 * 2560)
    latent = (2560 * 6144 + 2560 * 576 + 512 + 512 * 32 * 256
              + 4096 * 2560 + 2560 * 32)
    outside = 2560 * 512 + 512 + 3 * 2560 * 768     # router, shared expert
    expert = 3 * 2560 * 768
    dense = 3 * 2560 * 6144
    norms = 2 * 2560
    assert (kda, latent, outside, expert, dense) == (
        63049888, 31965696, 7209472, 5898240, 47185920)
    total = (kda + dense + norms
             + 5 * (kda + outside + 128 * expert + norms)
             + latent + outside + 128 * expert + norms
             + 2 * 39296 * 2560 + 2560)
    assert total == 5231790016 == model.param_count(sz) == cfg["parameters"]
    assert cfg["deployment"]["weight_bytes"] == 2 * total
    # and what the program holds is the same tree
    from ray_tpu.models import build_model
    assert build_model(model.program_config(cfg, 16384)).param_count() \
        == total


def test_required_operations_by_hand():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    # a lane-step of the six KDA layers: a state of 128 x 4096 float32 in
    # and out, q, k, v (12,288 bf16), 4,096 decays and 32 betas and 4,096
    # outputs in float32; seven operations a state element
    step = model.kda_step_call(sz, 32)
    state = 128 * 4096 * 4
    assert step["bytes"] == 6 * 32 * (2 * state + 12288 * 2
                                      + (4096 + 32) * 4 + 4096 * 4)
    assert step["flops"] == 6 * 32 * 7.0 * 32 * 128 * 128
    assert step["bytes"] / 819e9 > step["flops"] / 197e12
    # a prefill of 4,096 tokens, 64 chunks of 64 a head
    chunk = model.kda_chunk_call(sz, 4096)
    per = (2 * 64 * 64 * 128 + 64 ** 3 / 3 + 64 * 64 * (2 * 128 + 128)
           + 6 * 64 * 128 * 128)
    assert chunk["flops"] == pytest.approx(6 * 32 * 64 * per)
    assert chunk["bytes"] == 6 * (4096 * (12288 + 4096) * 2
                                  + 4096 * (4096 + 32) * 4 + state)
    # 100 pairs over 60 touched experts: three matrices of 2560 x 768
    gmm = model.moe_gmm_call(sz, 100, 60)
    assert gmm["flops"] == 6.0 * 2560 * 768 * 100
    assert gmm["bytes"] == 60 * 3 * 2560 * 768 * 2 + 100 * 2 * 2560 * 2
    # one latent layer: a row of 576 numbers a position
    mla = model.mla_decode_call(sz, 64000, 32)
    assert mla["bytes"] == 2 * (64000 * 576 + 32 * 32 * (576 + 512))
    assert mla["flops"] == 2.0 * 32 * (576 + 512) * 64000
    flash = model.flash_prefill_call(sz, 2048)
    assert flash["flops"] == 2.0 * (2048 * 2048 / 2) * 32 * (192 + 128)
    assert flash["bytes"] == 2048 * 32 * ((2 * 192 + 2 * 128) * 2 + 4)
    # a token's matmuls: two of the 8 choices' experts a layer among them
    kda = 2560 * (12288 + 4096 + 4096 + 32) + 4096 * 2560
    latent = (2560 * 6144 + 2560 * 576 + 512 * 32 * 256 + 4096 * 2560
              + 2560 * 32)
    experts = 2560 * 512 + 3 * 2560 * 768 + 8 * 128 / 512 * 3 * 2560 * 768
    assert model.matmul_params(sz) == (
        6 * kda + latent + 3 * 2560 * 6144 + 6 * experts + 2560 * 39296)
    assert model.train_flops_per_token(sz, 4096) == (
        6 * model.matmul_params(sz)
        + 3 * (2.0 * 32 * 320 * 4097 / 2 + 6 * 7.0 * 32 * 128 * 128))


def test_the_new_cell_rehearses():
    run = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", CELL, "--seed", "3000000050", "--seconds", "2",
           "--rehearse", "1"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rehearsed = subprocess.run(run, env=env, capture_output=True, text=True,
                               timeout=900)
    assert rehearsed.returncode == 0, rehearsed.stderr[-2000:]
    line = json.loads(rehearsed.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["workload"] == CELL and line["failed"] == 0
