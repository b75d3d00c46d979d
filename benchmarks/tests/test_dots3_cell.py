"""The model module `models/dots3_note.py` under the comparison that decides
`correct`: at a size a test run can hold, the program in bf16 through the
engine's own prefill and decode programs, the ring wrapped and `index_topk`
passed, passes against the float32 reference; the control (the reference in
fp8), the selection ignored and the window ignored do not; then the new cell
walked at rehearsal size. The limit here is this size's own; the chip-size
readings are in the configuration file and PERF.md."""
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
CONFIG = "dots3-note-prev-1chip"
CELL = CONFIG + ".serve.notes12k"


@pytest.fixture(scope="module")
def small():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    cfg = model.tiny(cfg)
    cfg.update(hidden_size=128, intermediate_size=256,
               moe_intermediate_size=64, index_topk=48)
    sz = model.sizes(cfg)
    return cfg, model, sz, make_weights(model.weight_shapes(sz), 5)


def test_served_logits_pass_and_the_three_controls_fail(small):
    from ray_tpu.serve.llm.engine import EngineCore
    cfg, model, sz, params = small
    core = EngineCore(model.program_config(cfg, 256), params, num_pages=0,
                      page_size=8, max_batch=2)
    assert core.alloc.fixed_pages == 2 * 6      # a window of 37: 6 pages
    p, steps = 90, 40       # 130 positions: the ring wraps, the sets choose
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
    got = jnp.stack(rows)
    args = (sz, params, jnp.asarray(toks), jnp.int32(p - 1), steps + 1)
    want = model.reference_rows(*args)
    sound = rel_rms(got, want)
    fp8 = rel_rms(model.reference_rows(*args, True), want)
    dense = rel_rms(model.reference_rows(*args, dense=True), want)
    windowless = rel_rms(model.reference_rows(*args, windowless=True), want)
    limit = 2 * sound       # this size's own
    assert sound < 0.05 and min(fp8, dense, windowless) > limit, (
        sound, fp8, dense, windowless)


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


def test_required_operations_of_the_ring_kernel():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    assert model.param_count(sz) == cfg["parameters"] == 4087154176
    # 32 lanes past the window, 3 sliding layers: 513 rows of 1,088 each
    ring = model.window_latent_decode_call(sz, 3 * 32 * 513, 3 * 32)
    assert ring["bytes"] == 2 * (3 * 32 * 513 * 1088
                                 + 3 * 32 * 64 * (1088 + 1024))
    assert ring["flops"] == 2.0 * 64 * (1088 + 1024) * 3 * 32 * 513
    # the bytes bound it: 109 operations a byte of row, 240 at the ridge
    assert ring["flops"] / ring["bytes"] < 197e12 / 819e9
    gmm = model.moe_gmm_call(sz, 1024, 100)
    assert gmm["bytes"] == 100 * 3 * 5120 * 1536 * 2 + 1024 * 2 * 5120 * 2


def test_the_new_cell_rehearses():
    run = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", CELL, "--seed", "3000000065", "--seconds", "2",
           "--rehearse", "1"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rehearsed = subprocess.run(run, env=env, capture_output=True, text=True,
                               timeout=900)
    assert rehearsed.returncode == 0, rehearsed.stderr[-2000:]
    line = json.loads(rehearsed.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["workload"] == CELL and line["failed"] == 0
