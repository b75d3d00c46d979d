"""The model module `models/longcat_flash.py` under the comparison that
decides `correct`: at a size a test run can hold, the program in bf16
through the engine's own prefill and decode programs (both of a layer's
pool rows, one chip's share of the experts, the identity slots) passes
against the float32 reference, and the control (the reference in fp8) does
not; the configuration's keys against the published file; the required
operations pinned by hand; then the new cell walked at rehearsal size. The
limit here is this size's own; the chip-size readings are in the
configuration file and PERF.md."""
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
CONFIG = "longcat-flash-chat-1chip"
CELL = CONFIG + ".serve.reason4k"

# the catalog's `config` for LongCat-Flash-Chat (its public config.json)
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
REDUCED = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384,
           "max_position_embeddings": 4096}


@pytest.fixture(scope="module")
def small():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    cfg = model.tiny(cfg)
    cfg.update(hidden_size=128, ffn_hidden_size=256,
               expert_ffn_hidden_size=64, q_lora_rank=64)
    sz = model.sizes(cfg)
    return cfg, model, sz, make_weights(model.weight_shapes(sz), 5)


def test_served_logits_pass_and_the_fp8_control_fails(small):
    from ray_tpu.serve.llm.engine import EngineCore
    cfg, model, sz, params = small
    limit = 0.02        # this size: sound reads 0.005, the control 0.04-0.09
    core = EngineCore(model.program_config(cfg, 256), params, num_pages=0,
                      page_size=8, max_batch=2)
    p, steps = 90, 32
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
        lane = lambda a, dt: jnp.asarray(np.array([a, 0], dt))   # noqa
        logits, core._cache = core._decode_fn(
            params, core._cache, lane(toks[p + k], np.int32),
            lane(p + k, np.int32),
            jnp.asarray(np.stack([pt, np.full_like(pt, -1)])),
            lane(True, bool))
        rows.append(logits[0])
    want = model.reference_rows(sz, params, jnp.asarray(toks),
                                jnp.int32(p - 1), steps + 1)
    control = model.reference_rows(sz, params, jnp.asarray(toks),
                                   jnp.int32(p - 1), steps + 1, True)
    sound_err = rel_rms(jnp.stack(rows), want)
    control_err = rel_rms(control, want)
    assert sound_err <= limit < control_err
    assert control_err > 2 * sound_err
    # the steps counted every choice of the one lane as one of three kinds
    counts = {k: int(v) for k, v in core._cache["moe_step"].items()}
    assert (counts["moe_pairs"] + counts["moe_zero_pairs"]
            + counts["moe_away_pairs"]) == sz.top_k * sz.layers


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


def test_the_configuration_keeps_every_published_key_but_the_four_cuts():
    cfg = modelcfg.load_config(CONFIG)
    assert cfg["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg[key] == REDUCED[key], key
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 32
    assert dep["experts_held"] == [0, 16]
    assert dep["chips_sharing_a_layer"] * cfg["n_routed_experts"] == 512
    assert (dep["max_batch"], dep["page_size"], dep["num_pages"],
            dep["context_limit"]) == (32, 16, 8192, 4096)
    sz = modelcfg.load_model(cfg).sizes(cfg)
    # the router stays as wide as published: 512 experts + 256 zero slots
    assert (sz.slots, sz.experts, sz.held, sz.zero) == (768, 512, 16, 256)
    assert (sz.a_q, round(sz.a_kv ** 2)) == (2.0, 12)
    # the floors of a cut: 4 layers, 8 experts, an eighth of the vocabulary
    assert sz.layers >= 4 and sz.held >= 8 and sz.vocab * 8 >= 131072


def test_required_operations_of_the_cells_kernels():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    # a double layer: two attentions, two dense feed-forwards, the router,
    # 16 experts of 3 x 6144 x 2048; 16,384 rows of embedding and of head
    attn = (6144 + 6144 * 1536 + 1536 + 1536 * 64 * 192 + 6144 * 576 + 512
            + 512 * 64 * 256 + 64 * 128 * 6144)
    ffn = 6144 + 3 * 6144 * 12288
    layer = (2 * attn + 2 * ffn + 6144 * 768 + 768
             + 16 * 3 * 6144 * 2048)
    assert layer == 1242854144
    assert model.param_count(sz) == cfg["parameters"] == (
        4 * layer + 2 * 16384 * 6144 + 6144) == 5172749312
    # 8 pool rows; a row 576 numbers; 64 query rows a lane in, 512 out
    mla = model.mla_decode_call(sz, 1000, 32)
    assert mla["bytes"] == 8 * 2 * (1000 * 576 + 32 * 64 * (576 + 512))
    assert mla["flops"] == 2.0 * 64 * (576 + 512) * 1000 * 8
    gmm = model.moe_gmm_call(sz, 100, 50)
    assert gmm["bytes"] == 50 * 3 * 6144 * 2048 * 2 + 100 * 2 * 6144 * 2
    assert gmm["flops"] == 6.0 * 6144 * 2048 * 100
    # a prefill of 1024 tokens: keys 192 wide, values 128, 8 attentions
    flash = model.flash_prefill_call(sz, 1024)
    assert flash["flops"] == 8 * 2.0 * (1024 * 1024 / 2) * 64 * (192 + 128)
    assert flash["bytes"] == 8 * 1024 * 64 * ((2 * 192 + 2 * 128) * 2 + 4)
    # bytes bound it under some 900 tokens, operations above
    from benchmarks.harness.peaks import PEAKS
    from benchmarks.harness.required_ops import roofline_seconds
    peaks = PEAKS["TPU v5 lite"]
    for tokens, bound in ((512, "bytes"), (2048, "ops")):
        need = model.flash_prefill_call(sz, tokens)
        assert roofline_seconds(need["flops"], need["bytes"],
                                peaks)[1] == bound
    # a token's choices give this share a quarter of an expert a layer
    assert model.matmul_params(sz) == pytest.approx(
        4 * (2 * (attn - 6144 - 1536 - 512) + 2 * (ffn - 6144)
             + 6144 * 768 + 0.25 * 3 * 6144 * 2048) + 6144 * 16384)


def test_the_new_cell_rehearses():
    run = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", CELL, "--seed", "3000000044", "--seconds", "2",
           "--rehearse", "1"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rehearsed = subprocess.run(run, env=env, capture_output=True, text=True,
                               timeout=900)
    assert rehearsed.returncode == 0, rehearsed.stderr[-2000:]
    line = json.loads(rehearsed.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["workload"] == CELL and line["failed"] == 0
