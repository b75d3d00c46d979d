"""What the chip bring-up (PR 21) rests on, checked without a chip.

chip_smoke.py itself only runs on a TPU; here its phase functions run at
the `tiny` preset on CPU workers, the mesh model is cross-lowered for
`tpu` (the Mosaic partition error needs no hardware to reproduce), the
shard-mapped kernels are compared with their references in interpret
mode, and the scheduler's chip grants are driven with declared chips.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from llm_streams import read_stream
from ray_tpu.models import Transformer
from ray_tpu.models.config import tiny
from ray_tpu.ops.attention import flash_attention_kernel, mha_reference
from ray_tpu.ops.dispatch import compute_platform
from ray_tpu.ops.norms import rms_norm, rms_norm_reference
from ray_tpu.parallel import MeshSpec, param_shardings

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


# --------------------------------------------------------- chip_smoke.py
def test_chip_smoke_without_a_chip_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_CHIPS", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode != 0
    assert "TPU chip" in p.stderr and "no CPU mode" in p.stderr
    assert '"ok"' not in p.stdout


def test_smoke_train_phase_tiny_on_cpu(ray_cluster, tmp_path):
    r = chip_smoke.train_phase(tiny(), chips=0, out_dir=str(tmp_path),
                               batch=2, seq=32, steps=5, lr=3e-3)
    assert r["platform"] == "cpu" and len(r["losses"]) == 5
    assert r["losses"][-1] < r["losses"][0]
    assert r["custom_calls"] == 0       # interpreted: no Mosaic on a CPU
    assert max(r["kernel_errors"].values()) < 1e-4      # float32 here


@pytest.fixture(scope="module")
def smoke_served():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    cfg = tiny()
    prompts = chip_smoke.smoke_prompts(cfg.vocab_size)
    return cfg, prompts, chip_smoke.serve_phase(cfg, prompts, chips=0)


def test_smoke_serve_phase_tiny_on_cpu(smoke_served):
    _cfg, prompts, served = smoke_served
    assert [len(t) for t in served["served"]] == [16] * len(prompts)
    (replica,) = served["replicas"]
    assert replica["platform"] == "cpu" and replica["chips"] == []
    assert replica["admitted"] == len(prompts)


def test_smoke_check_phase_tiny_on_cpu(smoke_served):
    cfg, prompts, served = smoke_served
    c = chip_smoke.check_phase(cfg, prompts, [served["served"]], chips=0)
    assert c["tokens_checked"] == c["exact_argmax"] == 16 * len(prompts)
    # a wrong token is caught
    bad = [list(t) for t in served["served"]]
    bad[0][3] = (bad[0][3] + 1) % cfg.vocab_size
    with pytest.raises(Exception, match="trails the teacher-forced"):
        chip_smoke.check_phase(cfg, prompts, [bad], chips=0)


# ------------------------------------------------- kernels that partition
def _mesh_fsdp_tp():
    return MeshSpec(dp=1, fsdp=2, tp=2).build(jax.devices()[:4])


@pytest.mark.parametrize("over", [
    {}, {"remat": True, "remat_policy": "save_attn"},
    {"remat": True}])       # the default rung: what the train cell runs
def test_mesh_model_lowers_for_tpu(over):
    """GSPMD cannot partition a Mosaic kernel: before the shard_map
    wrappers this lowering died with 'Mosaic kernels cannot be
    automatically partitioned' on every mesh."""
    mesh = _mesh_fsdp_tp()
    model = Transformer(dataclasses.replace(tiny(), **over), mesh=mesh)
    shard = param_shardings(mesh, model.param_logical_axes())
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), shard)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 128), jnp.int32)}
    with compute_platform("tpu"):
        text = jax.jit(jax.value_and_grad(model.loss)).trace(
            params, batch).lower(lowering_platforms=("tpu",)).as_text()
    # two norms, flash fwd, the one flash backward in the layer, the final
    # norm; remat recomputes the two norms
    assert text.count("tpu_custom_call") == (7 if over else 5)


@pytest.mark.parametrize("mesh_axes,kv_heads", [
    (dict(dp=1, fsdp=2, tp=2), 4),      # kv heads split over tp
    (dict(dp=1, fsdp=2, tp=2), 1),      # MQA: kv replicated, dk/dv psum
    (dict(dp=1, tp=4), 2),              # GQA that does not split: repeat
])
def test_sharded_flash_kernels_match_reference(mesh_axes, kv_heads):
    n = int(np.prod(list(mesh_axes.values())))
    mesh = MeshSpec(**mesh_axes).build(jax.devices()[:n])
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 32))
    k = jax.random.normal(ks[1], (2, kv_heads, 128, 32))
    v = jax.random.normal(ks[2], (2, kv_heads, 128, 32))

    def loss(attn):
        return lambda *a: jnp.sum(attn(*a) ** 2)

    kern = lambda *a: flash_attention_kernel(  # noqa: E731
        *a, block_q=64, block_k=64, mesh=mesh)
    val, grads = jax.jit(jax.value_and_grad(
        loss(kern), argnums=(0, 1, 2)))(q, k, v)
    ref, ref_grads = jax.value_and_grad(
        loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(val, ref, rtol=1e-5)
    for g, rg in zip(grads, ref_grads):
        np.testing.assert_allclose(g, rg, atol=2e-4)


def test_sharded_rms_norm_matches_reference_and_never_skips_the_kernel():
    mesh = _mesh_fsdp_tp()
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    w = 0.1 * jax.random.normal(ks[1], (64,))
    # 4 x 75 = 300 rows: more than one 256-row block and not a multiple,
    # the shape that used to return the reference without a word
    x = jax.random.normal(ks[0], (4, 75, 64))
    for m in (None, mesh):
        f = lambda x_, w_: jnp.sum(rms_norm(x_, w_, 1e-5, m) ** 2)  # noqa
        r = lambda x_, w_: jnp.sum(  # noqa: E731
            rms_norm_reference(x_, w_, 1e-5) ** 2)
        jaxpr = str(jax.make_jaxpr(f)(x, w))
        assert "pallas_call" in jaxpr
        val, grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(x, w)
        ref, ref_grads = jax.value_and_grad(r, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(val, ref, rtol=1e-5)
        for g, rg in zip(grads, ref_grads):
            np.testing.assert_allclose(g, rg, atol=1e-3)


# --------------------------------------------- one process for each chip
def test_chip_grants_bind_workers_to_distinct_chips():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=2)
    try:
        @ray_tpu.remote(num_tpus=1)
        class Holder:
            def env(self):
                return (os.environ.get("TPU_VISIBLE_CHIPS"),
                        os.environ["JAX_PLATFORMS"], os.getpid())

        @ray_tpu.remote
        class Bystander:
            def backend(self):
                import jax
                return (os.environ.get("TPU_VISIBLE_CHIPS"),
                        jax.default_backend())

        @ray_tpu.remote(num_tpus=1)
        def task():
            return os.environ.get("TPU_VISIBLE_CHIPS"), os.getpid()

        a, b = Holder.remote(), Holder.remote()
        ea, eb = ray_tpu.get([a.env.remote(), b.env.remote()], timeout=60)
        assert {ea[0], eb[0]} == {"0", "1"}
        assert ea[1] == eb[1] == "tpu"      # a missing chip is an error
        # no grant: the CPU, whatever the machine has
        assert ray_tpu.get(Bystander.remote().backend.remote(),
                           timeout=60) == (None, "cpu")
        # a killed holder's chip goes to the next grant, and only once
        # its process is gone
        ray_tpu.kill(a)
        chip, pid = ray_tpu.get(task.remote(), timeout=60)
        assert chip == ea[0]
        assert not os.path.exists(f"/proc/{ea[2]}")
        # a task's worker does not keep its chip either
        chip2, pid2 = ray_tpu.get(task.remote(), timeout=60)
        assert chip2 == chip and pid2 != pid
        assert not os.path.exists(f"/proc/{pid}")
    finally:
        ray_tpu.shutdown()


def test_apply_chip_grant_sets_what_libtpu_reads(monkeypatch):
    from ray_tpu._private.accelerators import apply_chip_grant
    for var in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS",
                "TPU_HOST_BOUNDS", "JAX_PLATFORMS"):
        monkeypatch.setenv(var, "before")
    monkeypatch.setenv("RAY_TPU_CHIPS", "4")
    # jax is imported here, so the call also sets jax_platforms; keep
    # this process on the CPU afterwards
    monkeypatch.setattr(jax.config, "update", lambda *a: None)
    apply_chip_grant((2,))
    assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
    assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    assert os.environ["TPU_HOST_BOUNDS"] == "1,1,1"
    assert os.environ["JAX_PLATFORMS"] == "tpu"
    # the whole host keeps the host's own topology variables
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    apply_chip_grant((0, 1, 2, 3))
    assert os.environ["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"
    with pytest.raises(ValueError, match="3 of this host's chips"):
        apply_chip_grant((0, 1, 2))
    apply_chip_grant(())
    assert os.environ["JAX_PLATFORMS"] == "cpu"


# ------------------------------------------------------- compile cache
def test_compile_cache_follows_env_else_fixed_path(monkeypatch):
    from ray_tpu.util.compile_cache import use_compile_cache
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert use_compile_cache() == "/somewhere/else"
    assert seen == []                   # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(ROOT, ".jax_cache")
    assert use_compile_cache() == fixed and seen == []   # pinned to cpu
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert use_compile_cache() == use_compile_cache() == fixed
    assert seen[0] == ("jax_compilation_cache_dir", fixed)


# --------------------------------------------- a dead engine says so
def test_failing_engine_step_reaches_streams_and_generate():
    from ray_tpu.serve.llm.engine import LLMEngine
    eng = LLMEngine(model="tiny", num_pages=64, max_batch=2)
    try:
        def boom():
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")
        eng.core.step = boom
        acc = eng.generate([1, 2, 3], max_tokens=4)
        _, out = read_stream(acc)
        assert out["done"] and out["reason"] == "error"
        assert "out of HBM" in out["err"]
        assert "out of HBM" in eng.engine_stats()["failed"]
        with pytest.raises(RuntimeError, match="out of HBM"):
            eng.generate([4, 5], max_tokens=2)
        with pytest.raises(RuntimeError, match="out of HBM"):
            eng.check_health()
    finally:
        eng.close()


def test_engine_liveness_stats_never_wait_for_a_step():
    """The replica's report thread calls __serve_stats__, and its
    reports are its liveness: on the chip the first step held the
    engine lock through an 11 s compile, the report thread waited with
    it, and the controller killed the replica as dead."""
    import threading
    import time

    from ray_tpu.serve.llm.engine import LLMEngine
    eng = LLMEngine(model="tiny", num_pages=64, max_batch=2)
    try:
        before = eng.__serve_stats__()
        holding, release = threading.Event(), threading.Event()

        def long_step():
            with eng._lock:
                holding.set()
                release.wait(10)
        t = threading.Thread(target=long_step)
        t.start()
        assert holding.wait(10)
        t0 = time.monotonic()
        assert eng.__serve_stats__() == before
        assert time.monotonic() - t0 < 1.0
        release.set()
        t.join(10)
    finally:
        eng.close()


def test_engine_on_a_mesh_shards_params_and_cache():
    from ray_tpu.serve.llm.engine import LLMEngine
    eng = LLMEngine(model="tiny", mesh={"dp": 1, "tp": 2}, num_pages=32,
                    max_batch=2)
    try:
        cache = eng.core._cache["k"]
        assert cache.sharding.spec[3] == "tp"
        assert len({s.data.shape for s in cache.addressable_shards}) == 1
        # a row holds 2 kv heads of 16 side by side: one head a shard
        assert cache.addressable_shards[0].data.shape[3] == 16
        wq = eng.core.params["layers"]["wq"]
        assert wq.addressable_shards[0].data.shape[-1] == wq.shape[-1] // 2
        st = eng.engine_stats()
        assert st["platform"] == "cpu" and len(st["device_ids"]) == 2
        # and it still decodes what the unsharded engine decodes
        plain = LLMEngine(model="tiny", num_pages=32, max_batch=2)
        try:
            toks = []
            for e in (eng, plain):
                toks.append(read_stream(
                    e.generate([5, 6, 7, 8], max_tokens=6))[0])
            assert toks[0] == toks[1] and len(toks[0]) == 6
        finally:
            plain.close()
    finally:
        eng.close()
    with pytest.raises(ValueError, match="does not divide"):
        LLMEngine(model="tiny", mesh={"dp": 1, "tp": 4}, num_pages=32)


# ------------------------------- a frozen host is not a dead cluster
def test_liveness_monitor_judges_nobody_after_its_own_pause(monkeypatch):
    """A worker opening a TPU froze the chip machine for 6.5 s; the
    head's monitor woke first and declared the head's own node dead for
    the heartbeats its frozen scheduler thread could not make."""
    import types

    from ray_tpu._private import cluster as cl
    clock, sweeps = [0.0], []
    naps = iter([0.5, 6.5, 0.5])        # the second nap: the freeze
    stub = types.SimpleNamespace(
        _running=True, _sweep_liveness=lambda: sweeps.append(clock[0]))

    def sleep(_period):
        nap = next(naps, None)
        if nap is None:
            stub._running = False
        else:
            clock[0] += nap

    monkeypatch.setattr(cl, "time", types.SimpleNamespace(
        monotonic=lambda: clock[0], sleep=sleep))
    cl.ClusterTaskManager._monitor_loop(stub)
    assert sweeps[0] == 0.5 and 7.5 in sweeps
    assert 7.0 not in sweeps            # the sweep right after the freeze


# ------------------------- no live frame ahead of a subscriber's replay
def test_stream_subscribe_replays_before_any_live_frame():
    """The step loop re-takes the engine lock at once, so a subscriber
    waiting for its replay could starve for a whole generation while
    live frames reached it first; the client drops those as gaps and
    the stream ended with no tokens (1 stream in 25 on the CPU)."""
    import threading
    import time

    from ray_tpu.serve.llm.stream import TokenStreamServer

    engine_lock = threading.Lock()
    toks = [11, 12]                     # emitted before anyone listens

    def backlog(rid, cursor):
        return {"rid": rid, "attempt": 0, "base": cursor,
                "toks": list(toks[cursor:]), "done": False,
                "reason": None, "err": None}

    class Conn:
        frames = []

        def send(self, frame):
            self.frames += [(r["base"], list(r["toks"]))
                            for r in frame["recs"]]

    server = TokenStreamServer("inc0", backlog, engine_lock)
    try:
        conn = Conn()
        with engine_lock:               # the engine is mid-step
            t = threading.Thread(target=server._handle, args=(
                conn, {"type": "llm_sub", "req": "r1", "cursor": 0}))
            t.start()
            time.sleep(0.2)             # the subscriber waits its turn
            toks.append(13)             # the step's token, ingested and
            server.publish([{           # published under the lock
                "rid": "r1", "token": 13, "seq": 2, "first": False,
                "done": False, "reason": None, "attempt": 0}])
        t.join(timeout=10)
        assert not t.is_alive()
        with engine_lock:
            toks.append(14)
            server.publish([{
                "rid": "r1", "token": 14, "seq": 3, "first": False,
                "done": False, "reason": None, "attempt": 0}])
        assert conn.frames == [(0, [11, 12, 13]), (3, [14])]
    finally:
        server.close()
