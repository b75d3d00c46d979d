"""The model module `models/hybrid_delta.py` under the comparison that
decides `correct`: at a size a test run can hold, the program in bf16
through the engine's own prefill and decode programs (a padded bucket, then
steps through the state slot) passes against the float32 reference, and the
control (the reference in fp8) does not; then the new cell walked at
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
CONFIG = "olmo-hybrid-7b-1chip"
CELL = CONFIG + ".serve.answers3k"


@pytest.fixture(scope="module")
def small():
    cfg = modelcfg.load_config(CONFIG)
    model = modelcfg.load_model(cfg)
    cfg = model.tiny(cfg)
    cfg.update(hidden_size=256, intermediate_size=512,
               linear_key_head_dim=32, linear_value_head_dim=64,
               linear_chunk_size=16)
    sz = model.sizes(cfg)
    return cfg, model, sz, make_weights(model.weight_shapes(sz), 5)


def test_served_logits_pass_and_the_fp8_control_fails(small):
    from ray_tpu.serve.llm.engine import EngineCore
    cfg, model, sz, params = small
    limit = 0.2         # this size: sound reads 0.06, the control 0.72
    core = EngineCore(model.program_config(cfg, 256), params, num_pages=0,
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


def test_the_new_cell_rehearses():
    run = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", CELL, "--seed", "3000000037", "--seconds", "2",
           "--rehearse", "1"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rehearsed = subprocess.run(run, env=env, capture_output=True, text=True,
                               timeout=900)
    assert rehearsed.returncode == 0, rehearsed.stderr[-2000:]
    line = json.loads(rehearsed.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["workload"] == CELL and line["failed"] == 0
