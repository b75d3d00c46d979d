"""The model module `models/nemotron_h.py` under the comparison that decides
`correct`: at a size a test run can hold, the program in bf16 through the
engine's own prefill and decode programs (a padded bucket, then steps
through the state slot and the held experts) passes against the float32
reference, and the control (the reference in fp8) does not; the required
operations pinned by hand arithmetic, the configuration's keys against the
published values, the parameter count; then the new cell walked at
rehearsal size. The limit here is this size's own; the chip-size readings
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
CONFIG = "nemotron-3-super-120b-a12b-1chip"
CELL = CONFIG + ".serve.agent8k"


@pytest.fixture(scope="module")
def small():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    cfg = model.tiny(cfg)
    cfg.update(hidden_size=256, mamba_num_heads=8, mamba_head_dim=32,
               n_groups=2, ssm_state_size=32, chunk_size=16, head_dim=64,
               moe_latent_size=128, moe_intermediate_size=192,
               moe_shared_expert_intermediate_size=384)
    sz = model.sizes(cfg)
    return cfg, model, sz, make_weights(model.weight_shapes(sz), 5)


def test_served_logits_pass_and_the_fp8_control_fails(small):
    from ray_tpu.serve.llm.engine import EngineCore
    cfg, model, sz, params = small
    limit = 0.03        # this size: sound reads 0.008, the control 0.10
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
        "hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64,
        "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
        "chunk_size": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "moe_latent_size": 1024,
        "moe_intermediate_size": 2688,
        "moe_shared_expert_intermediate_size": 5376,
        "num_experts_per_tok": 22, "routed_scaling_factor": 5,
        "mlp_hidden_act": "relu2", "expand": 2, "n_shared_experts": 1,
        "layer_norm_epsilon": 1e-05, "norm_topk_prob": True,
        "use_conv_bias": True, "time_step_min": 0.001,
        "time_step_max": 0.1}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "max_position_embeddings", "num_nextn_predict_layers"]
    assert cfg["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "max_position_embeddings": 262144,
        "num_nextn_predict_layers": 1,
        "hybrid_override_pattern": cfg["published"][
            "hybrid_override_pattern"]}
    whole = cfg["published"]["hybrid_override_pattern"]
    assert len(whole) == 88 and (whole.count("M"), whole.count("E"),
                                 whole.count("*")) == (40, 40, 8)
    kept = cfg["hybrid_override_pattern"]
    assert kept == "MEMEMEMEM*E" and kept in whole
    assert cfg["num_hidden_layers"] == 11 and cfg["n_routed_experts"] == 128
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 4
    assert dep["experts_held"] == [0, 128] and dep["vocab_share"] == "1/4"
    assert dep["max_batch"] == 32 and dep["context_limit"] == 8192
    assert dep["num_pages"] * dep["page_size"] == 32 * 8192
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    assert sz.experts == 512 and sz.held == 128 and sz.top_k == 22
    assert (len(sz.of_kind("M")), len(sz.of_kind("E")),
            len(sz.of_kind("*"))) == (5, 5, 1)


def test_the_parameter_count_is_the_issues_arithmetic():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    mamba = (4096 * 18560 + 8192 * 4096 + 4 * 10240 + 10240 + 8192
             + 3 * 128 + 4096)
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    outside = (4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096)
    expert = 2 * 1024 * 2688
    assert (mamba, attention, outside, expert) == (
        109640064, 35655680, 54530560, 5505024)
    total = (5 * mamba + 5 * (outside + 128 * expert) + attention
             + 2 * 32768 * 4096 + 4096)
    assert total == 4648163712 == model.param_count(sz) == cfg["parameters"]
    assert cfg["deployment"]["weight_bytes"] == 2 * total
    # and what the program holds is the same tree
    from ray_tpu.models import build_model
    assert build_model(model.program_config(cfg, 8192)).param_count() == total


def test_required_operations_by_hand():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    # a lane-step of the five Mamba layers: a state of 128 x 8192 float32
    # in and out, x, B, C (10,240 bf16), 128 steps and 8,192 outputs in
    # float32; five operations a state element
    step = model.ssd_step_call(sz, 32)
    state = 128 * 8192 * 4
    assert step["bytes"] == 5 * 32 * (2 * state + 10240 * 2 + 128 * 4
                                      + 8192 * 4)
    assert step["flops"] == 5 * 32 * 5.0 * 8192 * 128
    # a prefill of 4,096 tokens, 32 chunks of 128: a group's C B^T
    # triangle, a head's masked triangle times x, two products with the
    # state; the state written once a layer
    chunk = model.ssd_chunk_call(sz, 4096)
    per_chunk = 8 * 128 * 128 * 128 + 128 * (128 * 128 * 64
                                              + 4 * 128 * 128 * 64)
    assert chunk["flops"] == 5 * 32 * per_chunk
    assert chunk["bytes"] == 5 * (4096 * ((10240 + 8192) * 2 + 128 * 4)
                                  + state)
    # 100 pairs over 60 touched experts: two matrices of 1024 x 2688
    gmm = model.moe_gmm_call(sz, 100, 60)
    assert gmm["flops"] == 4.0 * 1024 * 2688 * 100
    assert gmm["bytes"] == 60 * 2 * 1024 * 2688 * 2 + 100 * 2 * 1024 * 2
    # one attention layer of 2 kv heads of 128 under 32 query heads
    full = model.full_decode_call(sz, 64000, 32)
    assert full["flops"] == 4.0 * 64000 * 4096
    assert full["bytes"] == (2 * 64000 * 256 + 2 * 32 * 4096) * 2
    # a token's matmuls: some 1.14 G parameters, a quarter of the 22
    # choices' experts among them
    layer_e = (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
               + 22 * 128 / 512 * 2 * 1024 * 2688)
    layer_m = 4096 * 18560 + 8192 * 4096
    layer_a = 2 * 4096 * 4096 + 2 * 4096 * 256
    assert model.matmul_params(sz) == (5 * layer_e + 5 * layer_m + layer_a
                                       + 4096 * 32768)
    assert model.train_flops_per_token(sz, 4096) == (
        6 * model.matmul_params(sz)
        + 3 * (4.0 * 128 * 32 * 4097 / 2 + 5 * 5.0 * 8192 * 128))


def test_the_new_cell_rehearses():
    run = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", CELL, "--seed", "3000000046", "--seconds", "2",
           "--rehearse", "1"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rehearsed = subprocess.run(run, env=env, capture_output=True, text=True,
                               timeout=900)
    assert rehearsed.returncode == 0, rehearsed.stderr[-2000:]
    line = json.loads(rehearsed.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["workload"] == CELL and line["failed"] == 0
