"""The model module `models/lfm2_moe.py` under the comparison that decides
`correct`: at a size a test run can hold, the program in bf16 through the
engine's own prefill and decode programs (a padded bucket, then steps
through the tail slot and the pages) passes against the float32 reference,
and the control (the reference in fp8) does not; the required operations
pinned by hand arithmetic at the published widths, the configuration's keys
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
CONFIG = "lfm2-8b-a1b-1chip"
CELL = CONFIG + ".serve.turns4k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["conv", "conv", "full_attention", "conv"]


@pytest.fixture(scope="module")
def small():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    cfg = model.tiny(cfg)
    cfg.update(hidden_size=512, num_attention_heads=8,
               num_key_value_heads=2, head_dim=64, intermediate_size=1024,
               moe_intermediate_size=256)
    sz = model.sizes(cfg)
    return cfg, model, sz, make_weights(model.weight_shapes(sz), 5)


def test_served_logits_pass_and_the_fp8_control_fails(small):
    from ray_tpu.serve.llm.engine import EngineCore
    cfg, model, sz, params = small
    # this size: sound reads 0.057 (three routers of 8 experts: a token
    # whose bf16 scores choose another expert moves its row), the control
    # 0.176
    limit = 0.1
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
    assert control_err > 2.5 * sound_err


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


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "models",
                           "lfm2_moe.py")) as f:
        lines = [ln.strip() for ln in f]
    imports = [ln for ln in lines if ln.startswith(("import ", "from "))]
    program = [ln for ln in imports if "ray_tpu" in ln]
    # (the two functions that hand the harness the program's own config
    # and class import it inside themselves, for nothing else)
    assert sorted(program) == [
        "from ray_tpu.models.gated_conv_moe import GatedConvMoE",
        "from ray_tpu.models.gated_conv_moe import GatedConvMoEConfig"]
    assert not any("ray_tpu.ops" in ln for ln in imports)


def test_the_configuration_holds_the_published_values():
    cfg = modelcfg.load_config(CONFIG)
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "conv_L_cache": 3, "conv_bias": False,
        "intermediate_size": 7168, "moe_intermediate_size": 1792,
        "num_experts": 32, "num_experts_per_tok": 4, "num_dense_layers": 2,
        "use_expert_bias": True, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "norm_eps": 1e-05,
        "rope_theta": 1000000, "vocab_size": 65536,
        "model_type": "lfm2_moe"}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "max_position_embeddings"]
    assert cfg["published"] == {
        "num_hidden_layers": 24,
        "layer_types": PERIOD * 5 + ["conv", "full_attention", "conv",
                                     "conv"],
        "max_position_embeddings": 128000}
    # four whole periods: the published pattern's first sixteen
    assert cfg["num_hidden_layers"] == 16
    assert cfg["layer_types"] == PERIOD * 4 == cfg["published"][
        "layer_types"][:16]
    dep = cfg["deployment"]
    assert dep["chips"] == 1 and dep["chips_sharing_a_layer"] == 1
    assert dep["max_batch"] == 64 and dep["context_limit"] == 4096 == cfg[
        "max_position_embeddings"]
    assert dep["num_pages"] * dep["page_size"] == 64 * 4096
    # the traffic never asks for a position past the context
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "serve.turns4k.json")) as f:
        mix = json.load(f)
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == 4096
    assert mix["clients"] == 2 * dep["max_batch"]
    sz = modelcfg.load_model(cfg).sizes(cfg)
    assert sz.of_kind("*") == sz.of_kind("full_attention") == (2, 6, 10, 14)
    assert len(sz.of_kind("conv")) == 12
    assert sz.of_kind("E") == tuple(range(2, 16)) and sz.held == 32
    assert (sz.head_dim, sz.kv_dim, sz.q_dim) == (64, 512, 2048)
    assert set(cfg["assumed"]) >= {
        "tie_word_embeddings", "conv", "rope", "norm_weight", "expert_bias",
        "norm_topk_epsilon"}


def test_every_key_of_the_catalogs_row_is_in_the_file_as_published():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    cfg = modelcfg.load_config(CONFIG)
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k, k) != v]
    assert sorted(differs) == sorted(cfg["reduced"])
    assert {k: row["config"][k] for k in differs} == cfg["published"]


def test_the_parameter_count_is_the_issues_arithmetic():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    conv = 2048 * 3 * 2048 + 2048 * 2048 + 3 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    dense = 3 * 2048 * 7168
    routed = 32 * 3 * 2048 * 1792 + 2048 * 32 + 32
    assert (conv, attention, dense, routed) == (
        16783360, 10485888, 44040192, 352387104)
    table = 65536 * 2048
    total = (12 * conv + 4 * attention + 2 * dense + 14 * routed
             + 16 * 2 * 2048 + table + 2048)
    assert total == 5399129024 == model.param_count(sz) == cfg["parameters"]
    assert cfg["deployment"]["weight_bytes"] == 2 * total
    # the whole model, tied and untied: published as 8.3B
    whole = (18 * conv + 6 * attention + 2 * dense + 22 * routed
             + 24 * 2 * 2048 + table + 2048)
    assert (whole, whole + table) == (8339930560, 8474148288)
    # and what the program holds is the same tree
    from ray_tpu.models import build_model
    assert build_model(model.program_config(cfg, 4096)).param_count() == total


def test_required_operations_by_hand():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    # four attention layers of 8 kv heads of 64 under 32 query heads: a
    # position is 512 numbers a pool, whatever pairs of heads a kernel reads
    full = model.full_decode_call(sz, 64000, 64)
    assert full["flops"] == 4 * 4.0 * 64000 * 2048
    assert full["bytes"] == 4 * (2 * 64000 * 512 + 2 * 64 * 2048) * 2
    # a prefill of 1,000 true tokens: the causal half, 64 numbers a head
    pre = model.flash_prefill_call(sz, 1000)
    assert pre["flops"] == 4 * 4.0 * 64 * 32 * (1000 * 1001 / 2)
    assert pre["bytes"] == 4 * (2 * 1000 * 2048 + 2 * 1000 * 512) * 2
    # 256 pairs over the 32 experts of one layer, all touched: 22 MB each
    gmm = model.moe_gmm_call(sz, 256, 32)
    assert gmm["flops"] == 6.0 * 2048 * 1792 * 256
    assert gmm["bytes"] == 32 * 3 * 2048 * 1792 * 2 + 256 * 2 * 2048 * 2
    assert 3 * 2048 * 1792 * 2 == 22020096
    # a token's matmuls: 1.25 G parameters at 16 layers
    mixers = 12 * 4 * 2048 * 2048 + 4 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    ffs = 2 * 3 * 2048 * 7168 + 14 * (2048 * 32 + 4 * 3 * 2048 * 1792)
    assert model.matmul_params(sz) == mixers + ffs + 2048 * 65536
    assert model.train_flops_per_token(sz, 2048) == (
        6 * model.matmul_params(sz)
        + 3 * (4 * 4.0 * 64 * 32 * 2049 / 2 + 12 * 8.0 * 2048))
    # the head's share of the bytes a decode step of 64 lanes at 700
    # positions moves when every expert is read: 2.4 % here, 1.6 % at the
    # published depth
    head = 2 * 65536 * 2048

    def step(convs, attns, routed):
        return (head + 2 * (convs * 4 * 2048 * 2048 + attns * (
            2 * 2048 * 2048 + 2 * 2048 * 512) + 2 * 3 * 2048 * 7168
            + routed * 32 * 3 * 2048 * 1792)
            + 64 * 700 * attns * 2 * 512 * 2)

    assert round(1000 * head / step(12, 4, 14)) == 24
    assert round(1000 * head / step(18, 6, 22)) == 16


def test_the_new_cell_rehearses():
    run = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", CELL, "--seed", "3000000058", "--seconds", "2",
           "--rehearse", "1"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rehearsed = subprocess.run(run, env=env, capture_output=True, text=True,
                               timeout=900)
    assert rehearsed.returncode == 0, rehearsed.stderr[-2000:]
    line = json.loads(rehearsed.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["workload"] == CELL and line["failed"] == 0
