"""The model module `models/falcon_h1.py` under the comparison that decides
`correct`: at a size a test run can hold, the program in bf16 through the
engine's own prefill and decode programs (a padded bucket, then steps
through the state slot and the pages of every layer) passes against the
float32 reference, and the control (the reference in fp8) does not; the
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
CONFIG = "falcon-h1-34b-instruct-1chip"
CELL = CONFIG + ".serve.chat2k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def small():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    cfg = model.tiny(cfg)
    cfg.update(hidden_size=256, num_attention_heads=10,
               num_key_value_heads=2, head_dim=64, mamba_n_heads=8,
               mamba_d_head=32, mamba_d_ssm=256, mamba_n_groups=2,
               mamba_d_state=32, mamba_chunk_size=16,
               intermediate_size=512)
    sz = model.sizes(cfg)
    return cfg, model, sz, make_weights(model.weight_shapes(sz), 5)


def test_served_logits_pass_and_the_fp8_control_fails(small):
    from ray_tpu.serve.llm.engine import EngineCore
    cfg, model, sz, params = small
    limit = 0.04        # this size: sound reads 0.012, the control 0.19
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
        "hidden_size": 5120, "num_attention_heads": 20,
        "num_key_value_heads": 4, "head_dim": 128, "mamba_n_heads": 32,
        "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_n_groups": 2,
        "mamba_d_state": 256, "mamba_d_conv": 4, "mamba_chunk_size": 128,
        "mamba_expand": 2, "intermediate_size": 21504,
        "vocab_size": 261120, "rope_theta": 100000000000,
        "rms_norm_eps": 1e-05, "mamba_norm_before_gate": False,
        "mamba_rms_norm": True, "mamba_conv_bias": True,
        "tie_word_embeddings": False, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381,
        "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375,
        "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
        "ssm_out_multiplier": 0.08838834764831845,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284]}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers",
                              "max_position_embeddings"]
    assert cfg["published"] == {"num_hidden_layers": 72,
                                "max_position_embeddings": 262144}
    assert cfg["num_hidden_layers"] == 6
    dep = cfg["deployment"]
    assert dep["chips"] == 1 and dep["chips_sharing_a_layer"] == 1
    assert dep["max_batch"] == 32 and dep["context_limit"] == 2560 == cfg[
        "max_position_embeddings"]
    assert dep["num_pages"] * dep["page_size"] == 32 * 2560
    # the traffic never asks for a position past the context
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "serve.chat2k.json")) as f:
        mix = json.load(f)
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == 2560
    sz = modelcfg.load_model(cfg).sizes(cfg)
    assert sz.of_kind("M") == sz.of_kind("*") == tuple(range(6))
    assert sz.of_kind("E") == () and sz.kv_dim == 512


def test_every_key_of_the_catalogs_row_is_in_the_file_as_published():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    cfg = modelcfg.load_config(CONFIG)
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, k) != v]
    assert sorted(differs) == sorted(cfg["reduced"])
    assert {k: row["config"][k] for k in differs} == cfg["published"]


def test_the_parameter_count_is_the_issues_arithmetic():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    attention = 2 * 5120 * 2560 + 2 * 5120 * 512
    mixer = (5120 * 9248 + 4096 * 5120 + 4 * 5120 + 5120 + 3 * 32 + 4096)
    feed_forward = 3 * 5120 * 21504
    layer = attention + mixer + feed_forward + 2 * 5120
    assert (attention, mixer, feed_forward, layer) == (
        31457280, 68351072, 330301440, 430120032)
    total = 6 * layer + 2 * 261120 * 5120 + 5120
    assert total == 5254594112 == model.param_count(sz) == cfg["parameters"]
    assert cfg["deployment"]["weight_bytes"] == 2 * total
    # and what the program holds is the same tree
    from ray_tpu.models import build_model
    assert build_model(model.program_config(cfg, 2560)).param_count() == total


def test_required_operations_by_hand():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    # a lane-step of the six layers: a state of 256 x 4096 float32 (4.19
    # MB) in and out, x, B, C (5,120 bf16), 32 steps and 4,096 outputs in
    # float32; five operations a state element
    step = model.ssd_step_call(sz, 32)
    state = 256 * 4096 * 4
    assert state == 4194304
    assert step["bytes"] == 6 * 32 * (2 * state + 5120 * 2 + 32 * 4
                                      + 4096 * 4)
    assert step["flops"] == 6 * 32 * 5.0 * 4096 * 256
    # a prefill of 1,024 tokens, 8 chunks of 128: a group's C B^T
    # triangle, a head's masked triangle times x, two products with the
    # state; the state written once a layer
    chunk = model.ssd_chunk_call(sz, 1024)
    per_chunk = 2 * 128 * 128 * 256 + 32 * (128 * 128 * 128
                                            + 4 * 128 * 256 * 128)
    assert chunk["flops"] == 6 * 8 * per_chunk
    assert chunk["bytes"] == 6 * (1024 * ((5120 + 4096) * 2 + 32 * 4)
                                  + state)
    # six layers of 4 kv heads of 128 under 20 query heads: five a kv head
    full = model.full_decode_call(sz, 64000, 32)
    assert full["flops"] == 6 * 4.0 * 64000 * 2560
    assert full["bytes"] == 6 * (2 * 64000 * 512 + 2 * 32 * 2560) * 2
    # a token's matmuls: 3.92 G parameters, the head 1.34 G of them
    layer = (2 * 5120 * 2560 + 2 * 5120 * 512 + 5120 * 9248 + 4096 * 5120
             + 3 * 5120 * 21504)
    assert model.matmul_params(sz) == 6 * layer + 5120 * 261120
    assert model.train_flops_per_token(sz, 2048) == (
        6 * model.matmul_params(sz)
        + 3 * 6 * (4.0 * 128 * 20 * 2049 / 2 + 5.0 * 4096 * 256))
    # the head's share of the bytes a decode step of 32 lanes moves (a
    # layer's weights, its states in and out): 28 % here, 3 % at the
    # published depth (4 % of the weights alone)
    head, weights = 2 * 5120 * 261120, 2 * (layer + 2 * 5120)
    states = 32 * 2 * state
    assert round(100 * head / (head + 6 * (weights + states))) == 28
    assert round(100 * head / (head + 72 * (weights + states))) == 3
    assert round(100 * head / (head + 72 * weights)) == 4


def test_the_new_cell_rehearses():
    run = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", CELL, "--seed", "3000000054", "--seconds", "2",
           "--rehearse", "1"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rehearsed = subprocess.run(run, env=env, capture_output=True, text=True,
                               timeout=900)
    assert rehearsed.returncode == 0, rehearsed.stderr[-2000:]
    line = json.loads(rehearsed.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["workload"] == CELL and line["failed"] == 0
