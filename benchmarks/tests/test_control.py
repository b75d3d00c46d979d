"""The comparison that decides `correct`, shown to fail: at a size a test run
can hold, the program in its stated precision passes against the float32
reference and the control (the reference in fp8, the precision below bf16)
does not. Errors grow with the widths, so the limits here are this size's
own, set by the same rule as the chip-size ones (three times above the sound
reading, three times below the control's); the chip-size readings are in the
configuration files and PERF.md, and `test_chip_limits_...` holds the files
to that rule."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import modelcfg, train_cell
from benchmarks.harness.reference import rel_rms
from benchmarks.harness.weights import make_weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def small():
    cfg = modelcfg.load_config("internlm2-1.8b")
    model = modelcfg.load_model(cfg)
    cfg = model.tiny(cfg)
    cfg.update(hidden_size=128, num_attention_heads=4, head_dim=32,
               intermediate_size=512, num_hidden_layers=4)
    sz = model.sizes(cfg)
    return cfg, model, sz, make_weights(model.weight_shapes(sz), 3)


def test_serving_logits_pass_and_the_fp8_control_fails(small):
    from ray_tpu.models import Transformer
    cfg, model, sz, params = small
    limit = 0.025       # this size: sound reads 0.008, the control 0.056
    toks = jnp.asarray(np.random.default_rng(0).integers(0, sz.vocab, 256),
                       jnp.int32)
    want = model.reference_rows(sz, params, toks, jnp.int32(200), 9)
    program = Transformer(model.program_config(cfg, 256, remat=False))
    got = program.apply(params, toks[None])[0, 200:209]
    control = model.reference_rows(sz, params, toks, jnp.int32(200), 9, True)
    sound_err = rel_rms(got, want)
    control_err = rel_rms(control, want)
    assert sound_err <= limit < control_err
    assert control_err > 3 * sound_err


def test_training_gradient_passes_and_the_fp8_control_fails(small):
    cfg, model, sz, params = small
    # this size: sound reads 0.0075, the control 0.068
    cfg = dict(cfg, reference={"grad_limit": 0.02},
               deployment={"remat": True})
    program = model.train_model(cfg, 256)
    seq = jnp.asarray(np.random.default_rng(1).integers(0, sz.vocab, 256),
                      jnp.int32)
    sound = train_cell.check_against_reference(
        program, model, sz, params, seq, cfg, lambda m: None)
    control = train_cell.check_against_reference(
        program, model, sz, params, seq, cfg, lambda m: None, control=True)
    assert sound["ok"] and not control["ok"]
    assert control["grad_error"] > 3 * sound["grad_error"]


@pytest.mark.parametrize("name,key", [("internlm2-1.8b", "limit"),
                                      ("mistral-7b-v0.1-1chip", "grad_limit")])
def test_chip_limits_stand_three_times_clear_of_both_readings(name, key):
    ref = modelcfg.load_config(name)["reference"]
    assert 3 * ref["sound_largest"] <= ref[key] <= ref["control_smallest"] / 3


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


def test_rehearsal_prints_no_metric_and_the_measured_path_needs_a_tpu():
    import subprocess
    import sys
    run = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", "internlm2-1.8b.serve.chat", "--seed", "1",
           "--seconds", "2"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    measured = subprocess.run(run, env=env, capture_output=True, text=True)
    assert measured.returncode != 0 and measured.stdout.strip() == ""
    rehearsed = subprocess.run(run + ["--rehearse", "1"], env=env,
                               capture_output=True, text=True, timeout=600)
    assert rehearsed.returncode == 0, rehearsed.stderr[-2000:]
    line = json.loads(rehearsed.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu" and line["correct"] is None
