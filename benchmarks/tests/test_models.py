"""The seam between the harness and an architecture: a configuration names
its model module, the harness asks that module for everything it knows of
the architecture, and `dense_gqa` gives what the harness gave before the
move (checksums taken on the parent tree, PR 28, and pinned here)."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import modelcfg, spans, xplane
from benchmarks.harness.peaks import PEAKS
from benchmarks.harness.weights import leaves, make_weights

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SECOND = os.path.join(HERE, "data", "second_model")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CONFIGS = json.load(_f)["configs"]


def sha256_of(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(leaf.astype(jnp.float32)).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def tiny_dense():
    cfg = modelcfg.load_config("internlm2-1.8b")
    model = modelcfg.load_model(cfg)
    return model, model.sizes(model.tiny(cfg))


# ------------------------------------------------------------ the move
@pytest.mark.parametrize("seed,want", [
    (3, "2bc188d7e30786584661cbf5e4946dba73ed4abfe2fcb98e743d2f82fd5e77e0"),
    (3000000007,
     "020e9b0ececc1674fc87bceca51ff39373f9aa71163d9ef6e6b8f375ad3ee0b4")])
def test_dense_gqa_weights_are_bit_for_bit_the_parent_s(tiny_dense, seed,
                                                        want):
    model, sz = tiny_dense
    assert model.param_count(sz) == 139_584
    assert sha256_of(make_weights(model.weight_shapes(sz), seed)) == want


def test_dense_gqa_reference_reads_what_the_parent_s_read(tiny_dense):
    model, sz = tiny_dense
    params = make_weights(model.weight_shapes(sz), 3)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, sz.vocab, 128),
                       jnp.int32)
    rows = np.asarray(model.reference_rows(sz, params, toks, jnp.int32(100),
                                           9), np.float64)
    assert rows.shape == (9, 512)
    assert rows.sum() == pytest.approx(-5.5254931949748425, abs=2e-3)
    assert np.abs(rows).sum() == pytest.approx(597.3525835557812, rel=1e-5)
    assert rows[0, :4] == pytest.approx(
        [-0.02238375, 0.29519308, -0.08657937, -0.2320416], abs=1e-5)
    control = np.asarray(model.reference_rows(
        sz, params, toks, jnp.int32(100), 9, True), np.float64)
    assert np.abs(control).sum() == pytest.approx(596.8794027028052,
                                                  rel=1e-4)
    assert float(model.loss_fn(sz, params, toks)) == pytest.approx(
        6.254292964935303, rel=1e-5)


# ------------------------------------------------------------ the seam
@pytest.mark.parametrize("entry", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_every_configuration_names_a_model_with_the_whole_interface(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    model = modelcfg.load_model(cfg)
    for name in modelcfg.INTERFACE:
        assert callable(getattr(model, name)), name
    sz = model.sizes(cfg)
    assert sz.vocab == cfg["vocab_size"] and hash(sz) == hash(model.sizes(cfg))
    assert model.sizes(model.tiny(cfg)).vocab < sz.vocab
    assert model.tiny(cfg)["model"] == cfg["model"]
    flat, _ = leaves(model.weight_shapes(sz))
    assert model.param_count(sz) == sum(
        int(np.prod(shape)) for shape, _ in flat) > 0
    assert all(std > 0 for _, std in flat)
    assert model.train_flops_per_token(sz, 4096) > 6 * model.matmul_params(sz)
    # one module a process: its Sizes is a static argument of jitted programs
    assert modelcfg.load_model(cfg) is model


def test_a_configuration_without_a_model_is_an_error_not_a_default():
    cfg = modelcfg.load_config("internlm2-1.8b")
    del cfg["model"]
    with pytest.raises(KeyError, match="names no"):
        modelcfg.load_model(cfg)
    with pytest.raises(FileNotFoundError):
        modelcfg.load_model({"model": "no_such_architecture"})


def test_a_module_with_a_function_missing_is_refused_when_loaded(
        tmp_path, monkeypatch):
    (tmp_path / "models").mkdir()
    with open(os.path.join(SECOND, "plain_mha.py")) as f:
        whole = f.read()
    (tmp_path / "models" / "whole.py").write_text(whole)
    (tmp_path / "models" / "holed.py").write_text(
        whole.replace("def train_flops_per_token(", "def _gone("))
    monkeypatch.setattr(modelcfg, "HERE", str(tmp_path))
    assert modelcfg.load_model({"model": "whole"}).Sizes
    with pytest.raises(AttributeError, match="train_flops_per_token"):
        modelcfg.load_model({"model": "holed"})


def test_make_weights_builds_any_tree_the_same_from_the_same_seed():
    """A leading layer unlike the rest, experts with an axis of their own:
    nothing of a tree's shape is the harness's."""
    shapes = {"embed": ((64, 8), 0.02),
              "lead": {"gate": ((8, 40), 0.02), "down": ((40, 8), 0.01)},
              "rest": {"router": ((3, 8, 4), 0.02),
                       "experts": {"gate": ((3, 4, 8, 6), 0.02),
                                   "down": ((3, 4, 6, 8), 0.01)}}}
    a, b = make_weights(shapes, 3000000011), make_weights(shapes, 3000000011)
    other = make_weights(shapes, 3000000012)
    assert jax.tree_util.tree_map(lambda x: x.shape, a) == {
        "embed": (64, 8), "lead": {"gate": (8, 40), "down": (40, 8)},
        "rest": {"router": (3, 8, 4),
                 "experts": {"gate": (3, 4, 8, 6), "down": (3, 4, 6, 8)}}}
    assert sha256_of(a) == sha256_of(b) != sha256_of(other)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree_util.tree_leaves(a))
    std = float(jnp.std(a["rest"]["experts"]["gate"].astype(jnp.float32)))
    assert std == pytest.approx(0.02, rel=0.15)


# ------------------------------------------------------------ a second model
@pytest.fixture(scope="module")
def benchmark_with_a_second_model(tmp_path_factory):
    """A copy of the benchmark as a later PR would leave it: every file as
    it is, plus a model module, a configuration naming it and entries in
    BENCHMARK.json. No file of the benchmark is edited."""
    root = tmp_path_factory.mktemp("second") / "checkout"
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".pytest_cache"))
    shutil.copy(os.path.join(SECOND, "plain_mha.py"),
                root / "benchmarks" / "models" / "plain_mha.py")
    shutil.copy(os.path.join(SECOND, "plain-mha-toy.json"),
                root / "benchmarks" / "configs" / "plain-mha-toy.json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "plain-mha-toy", "source": "none: a test's toy",
        "file": "benchmarks/configs/plain-mha-toy.json", "reduced": [],
        "why": "a second architecture"})
    for traffic in ("train.packed4k", "serve.batch"):
        bench["workloads"].append({
            "name": "plain-mha-toy." + traffic, "config": "plain-mha-toy",
            "traffic": traffic, "chips": 1, "why": "the seam"})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("traffic", ["train.packed4k", "serve.batch"])
def test_a_second_model_rehearses_with_no_file_of_the_benchmark_edited(
        benchmark_with_a_second_model, traffic):
    root = benchmark_with_a_second_model
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, str(root / "benchmarks" / "run.py"), "--workload",
         "plain-mha-toy." + traffic, "--seed", "3000000013", "--seconds", "2",
         "--rehearse", "1"], env=env, capture_output=True, text=True,
        timeout=900, cwd=str(root))
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert "model module plain_mha" in done.stderr
    assert "reference check" in done.stderr
    # the copy's own files are the benchmark's, byte for byte
    for sub in ("run.py", "harness/serve_cell.py", "harness/train_cell.py",
                "harness/modelcfg.py", "models/dense_gqa.py"):
        with open(os.path.join(BENCH, sub), "rb") as a, \
                open(root / "benchmarks" / sub, "rb") as b:
            assert a.read() == b.read()


# ------------------------------------------------------------ its metric
def metric(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_paged_decode_roofline_from_kernel_events_and_live_positions():
    """Two decode steps of the batch cell's shape: 24 kernel events a step,
    1.67 ms of them a step, 8 lanes at 576 positions: 33.2 %."""
    E = xplane.Event
    sz = modelcfg.load_model({"model": "dense_gqa"}).sizes(
        modelcfg.load_config("internlm2-1.8b"))
    per = 1.67e-3 / 24
    ops = [E(f"%paged_decode_attn.{i} = bf16[8,2048] custom-call(...), "
             "custom_call_target=\"tpu_custom_call\"", 0.01 * i, per)
           for i in range(48)]
    ops.append(E("%fusion.1 = bf16[8,2048] fusion(...)", 1.0, 0.5))
    steps = [E(spans.DISPATCH, t, 1e-4, {"lanes": 8, "live_positions": 4608,
                                         "read_positions": 4608 + 64})
             for t in (0.0, 0.3)]
    run = {"trace": xplane.Trace({}, {0: ops}, {}, {}), "sizes": sz,
           "peaks": PEAKS["TPU v5 lite"], "result": {"traced": {}},
           "_spans": spans.Reading(steps, {}, 0.0)}
    assert metric("kernel.paged_decode_roofline.batch")(run) == pytest.approx(
        33.2, abs=0.1)
    # no kernel of that name in the trace (the einsum path): left out
    run["trace"] = xplane.Trace({}, {0: ops[-1:]}, {}, {})
    assert metric("kernel.paged_decode_roofline.batch")(run) is None
