"""MPMD pipeline parallelism (r13): channel rings, the wire transport,
stage-death propagation, and stage-per-worker-group training parity.

The heavy 4-stage wire e2e (parity with the single-process pp axis +
Perfetto overlap assertion) is @slow; every feature keeps a fast
tier-1 sibling here.
"""
import os
import time

import numpy as np
import pytest

import ray_tpu


# ------------------------------------------------------ ring buffers
def test_channel_ring_depth_buffers_writes():
    """depth=2 double-buffers: two publishes complete without any
    reader progress; the third blocks until a slot frees (the property
    transfer/compute overlap rests on). depth=1 keeps the old
    single-slot semantics."""
    from ray_tpu.experimental.channel import Channel, ChannelClosed, \
        ChannelTimeout
    ch = Channel.create(capacity=1 << 14, n_readers=1, depth=2)
    w, r = ch.writer(), ch.reader(0)
    w.write(b"m1")
    w.write(b"m2")                      # second slot: no reader needed
    with pytest.raises(ChannelTimeout):
        w.write(b"m3", timeout=0.2)     # ring full
    assert r.read() == b"m1"
    w.write(b"m3", timeout=5.0)         # slot freed by the read
    assert r.read() == b"m2" and r.read() == b"m3"
    arr = np.arange(64, dtype=np.float32)
    w.write(arr)                        # raw frames ride ring slots too
    assert np.array_equal(r.read(), arr)
    w.close()
    with pytest.raises(ChannelClosed):
        r.read(timeout=5.0)
    ch.destroy()

    ch1 = Channel.create(capacity=1 << 12, n_readers=1, depth=1)
    w1, r1 = ch1.writer(), ch1.reader(0)
    w1.write("a")
    with pytest.raises(ChannelTimeout):
        w1.write("b", timeout=0.2)      # single slot: writer gated
    assert r1.read() == "a"
    ch1.destroy()


def test_channel_ring_close_drains_buffered_messages():
    """The closed marker lands in its own ring slot: messages already
    published drain before readers see ChannelClosed."""
    from ray_tpu.experimental.channel import Channel, ChannelClosed
    ch = Channel.create(capacity=1 << 12, n_readers=1, depth=3)
    w, r = ch.writer(), ch.reader(0)
    w.write(1)
    w.write(2)
    w.close()
    assert r.read() == 1 and r.read() == 2
    with pytest.raises(ChannelClosed):
        r.read(timeout=5.0)
    ch.destroy()


# ---------------------------------------------------- wire transport
def test_wire_channel_roundtrip_ring_and_close():
    from ray_tpu.experimental.channel import ChannelClosed, ChannelTimeout
    from ray_tpu.experimental.wire_channel import CH_STATS, serve_channel
    ch = serve_channel(capacity=1 << 20, n_readers=1, depth=2,
                       label="t0")
    r = ch.reader(0)
    w = ch.writer()
    raw0 = CH_STATS["tx_raw"]
    arr = np.arange(256, dtype=np.int64)
    w.write(arr)                        # ndarray -> Envelope raw field
    got = r.read(timeout=10.0)
    assert np.array_equal(got, arr)
    assert CH_STATS["tx_raw"] == raw0 + 1
    w.write({"k": [1, 2]})              # non-array -> pickled body
    assert r.read(timeout=10.0) == {"k": [1, 2]}
    # ring flow control over the wire: depth unacked messages max
    w.write(b"a")
    w.write(b"b")
    with pytest.raises(ChannelTimeout):
        w.write(b"c", timeout=0.2)
    assert r.read(timeout=10.0) == b"a"
    w.write(b"c", timeout=10.0)
    assert r.read(10.0) == b"b" and r.read(10.0) == b"c"
    w.close()
    with pytest.raises(ChannelClosed):
        r.read(timeout=10.0)
    r.release()
    ch.destroy()


def test_wire_channel_old_peer_falls_back_to_pickled_body():
    """MINOR negotiation: toward a peer that demonstrated a pre-r13
    wire version, CH_DATA payloads ship in the pickled body instead of
    the Envelope raw field — same values, old peers unaffected."""
    from ray_tpu.experimental import wire_channel as wc
    ch = wc.serve_channel(capacity=1 << 20, n_readers=1, depth=2,
                          label="old")
    r = ch.reader(0)
    w = ch.writer()
    srv = wc._SERVERS[ch.name]
    with srv._cv:                       # simulate an old (MINOR 4) peer
        for conn in srv._conns.values():
            conn.peer_wire_version = 104
    blob0, raw0 = wc.CH_STATS["tx_blob"], wc.CH_STATS["tx_raw"]
    arr = np.arange(64, dtype=np.float32)
    w.write(arr)
    got = r.read(timeout=10.0)
    assert np.array_equal(got, arr)
    assert wc.CH_STATS["tx_blob"] == blob0 + 1
    assert wc.CH_STATS["tx_raw"] == raw0
    w.close()
    r.release()
    ch.destroy()


# ------------------------------------------------------ tracing gate
def test_channel_spans_recorded_and_zero_when_disabled():
    """Channel write/wait/read land tracing-plane spans when a trace
    is active; with RAY_TPU_TRACE=0 nothing is recorded (the hot-path
    zero-cost discipline)."""
    from ray_tpu._private import tracing_plane as tp
    from ray_tpu._private.config import CONFIG
    from ray_tpu.experimental.channel import Channel
    prev = os.environ.get("RAY_TPU_TRACE")
    try:
        os.environ["RAY_TPU_TRACE"] = "1"
        CONFIG.reload()
        rec = tp.recorder()
        base = rec.watermark()
        tp.set_current(tp.new_id(), 0)
        ch = Channel.create(capacity=1 << 12, n_readers=1, depth=2)
        w, r = ch.writer(), ch.reader(0)
        w.write(b"x")
        assert r.read() == b"x"
        ch.destroy()
        tp.clear_current()
        assert tp.recorder().watermark() > base
        names = {e[4] for e in tp.recorder().snapshot()
                 if e[3] == "channel"}
        assert any(n.startswith("ch.write:") for n in names), names
        assert any(n.startswith("ch.read:") for n in names), names

        os.environ["RAY_TPU_TRACE"] = "0"
        CONFIG.reload()
        tp.set_current(tp.new_id(), 0)
        ch2 = Channel.create(capacity=1 << 12, n_readers=1, depth=2)
        w2, r2 = ch2.writer(), ch2.reader(0)
        w2.write(b"y")
        assert r2.read() == b"y"
        ch2.destroy()
        assert tp.recorder().watermark() == 0   # zero records
    finally:
        if prev is None:
            os.environ.pop("RAY_TPU_TRACE", None)
        else:
            os.environ["RAY_TPU_TRACE"] = prev
        CONFIG.reload()
        tp.clear_current()


# ----------------------------------------------- uneven layer splits
def test_partition_layers_remainder_to_last_stage():
    from ray_tpu.parallel.pipeline import partition_layers, slice_stage
    assert partition_layers(8, 4) == [(0, 2), (2, 2), (4, 2), (6, 2)]
    assert partition_layers(7, 3) == [(0, 2), (2, 2), (4, 3)]
    assert partition_layers(5, 2) == [(0, 2), (2, 3)]
    with pytest.raises(ValueError, match="cannot fill"):
        partition_layers(2, 3)
    import jax.numpy as jnp
    sl = slice_stage({"w": jnp.zeros((7, 3))}, 4, 3)
    assert sl["w"].shape == (3, 3)
    # split_stages still rejects uneven whole-stack mode with guidance
    from ray_tpu.parallel.pipeline import split_stages
    with pytest.raises(ValueError, match="not divisible"):
        split_stages({"w": jnp.zeros((7, 3))}, 2)


@pytest.mark.slow        # ~19s compile-bound parity
def test_spmd_pipeline_uneven_layer_fn_parity():
    """pipeline_apply/pipeline_grads_1f1b accept L % S != 0 via the
    masked per-layer path: outputs, loss AND grads match the sequential
    stack (remainder layers on the last stage)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ray_tpu.parallel.pipeline import (pipeline_apply,
                                           pipeline_grads_1f1b)
    L, D, B, S, M = 7, 8, 12, 3, 4
    kw, kx, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {"w": jax.random.normal(kw, (L, D, D)) * 0.2,
              "b": jnp.zeros((L, D))}
    x = jax.random.normal(kx, (B, D))
    targets = jax.random.normal(kt, (B, D))

    def layer_fn(lp, h):
        return jnp.tanh(h @ lp["w"] + lp["b"])

    def seq_apply(p, h):
        for i in range(L):
            h = layer_fn({"w": p["w"][i], "b": p["b"][i]}, h)
        return h

    mesh = Mesh(np.array(jax.devices()[:S]).reshape(S), ("pp",))
    out = pipeline_apply(mesh, None, params, x, M, layer_fn=layer_fn)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(seq_apply(params, x)),
                               atol=1e-5, rtol=1e-5)

    def loss_fn(y, t):
        return jnp.sum((y - t) ** 2)

    def full_loss(p):
        return jnp.sum((seq_apply(p, x) - targets) ** 2) / M
    gt_loss, gt_grads = jax.value_and_grad(full_loss)(params)
    loss, grads = pipeline_grads_1f1b(mesh, None, loss_fn, params, x,
                                      targets, M, layer_fn=layer_fn)
    np.testing.assert_allclose(float(loss), float(gt_loss), rtol=1e-5)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(gt_grads[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)


# --------------------------------------------- stage-death propagation
@pytest.mark.parametrize("transport", [
    "shm",      # ~8s each; PR 21 budget: shm stays tier-1
    pytest.param("wire", marks=pytest.mark.slow)])
def test_dag_stage_death_surfaces_and_leaves_no_segments(
        ray_cluster, transport):
    """A stage actor killed mid-pipeline: the error surfaces at
    execute()/get() within seconds (no hang), surviving loops unwedge
    via the abort flag, and teardown leaves no channel shm segments —
    on both transports."""
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class Stage:
        def work(self, x):
            time.sleep(0.05)
            return x + 1

    a, b, c = Stage.remote(), Stage.remote(), Stage.remote()
    with InputNode() as inp:
        out = c.work.bind(b.work.bind(a.work.bind(inp)))
    dag = out.experimental_compile(enable_shm_channels=True,
                                   channel_transport=transport)
    try:
        assert dag.execute(1).get(timeout=60) == 4
        ray_tpu.kill(b)                     # middle stage dies
        t0 = time.time()
        with pytest.raises((RuntimeError, Exception)) as ei:
            dag.execute(10).get(timeout=60)
        assert time.time() - t0 < 40        # surfaced, not hung
        assert "died mid-pipeline" in str(ei.value) or \
            "ChannelClosed" in type(ei.value).__name__
        names = {ch.name for ch in dag._channels.values()}
    finally:
        dag.teardown()
    leaked = [n for n in os.listdir("/dev/shm") if n in names]
    assert not leaked, leaked
    for act in (a, c):
        try:
            ray_tpu.kill(act)
        except Exception:
            pass


# ------------------------------------------------ MPMD training parity
def _mlp_fixture(L, D, steps, B, seed=0):
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    params = {"w": jnp.asarray(rng.normal(size=(L, D, D)) * 0.2,
                               jnp.float32),
              "b": jnp.zeros((L, D), jnp.float32)}

    def stage_fn(p, h):
        def layer(h, wb):
            w, b = wb
            return jnp.tanh(h @ w + b), None
        h, _ = jax.lax.scan(layer, h, (p["w"], p["b"]))
        return h

    def loss_fn(y, t):
        return jnp.sum((y - t) ** 2)

    X = rng.normal(size=(steps, B, D)).astype(np.float32)
    T = rng.normal(size=(steps, B, D)).astype(np.float32)
    return params, stage_fn, loss_fn, X, T


def _sequential_sgd(params, stage_fn, loss_fn, X, T, M, lr):
    """Reference trajectory: full-stack microbatch-mean loss + SGD."""
    import jax
    losses = []
    p = params
    for step in range(X.shape[0]):
        x, t = X[step], T[step]
        bs = x.shape[0] // M

        def step_loss(pp):
            tot = 0.0
            for m in range(M):
                y = stage_fn(pp, x[m * bs:(m + 1) * bs])
                tot = tot + loss_fn(y, t[m * bs:(m + 1) * bs])
            return tot / M
        l, g = jax.value_and_grad(step_loss)(p)
        losses.append(float(l))
        p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
    return losses, p


@pytest.mark.slow        # ~29s; the dag-stage-death and channel
                         # tests keep MPMD wiring in tier-1
def test_mpmd_pipeline_2stage_1f1b_parity(ray_cluster):
    """Fast tier-1 e2e: JaxTrainer pipeline_stages=2 over shm channels
    matches the sequential full-stack trajectory — losses AND final
    params (uneven 5-layer split: stage 0 gets 2 layers, stage 1 gets
    3)."""
    from ray_tpu.train import JaxTrainer, PipelineConfig
    L, D, B, M, STEPS, LR = 5, 8, 8, 4, 2, 1e-2
    params, stage_fn, loss_fn, X, T = _mlp_fixture(L, D, STEPS, B)
    trainer = JaxTrainer(
        pipeline_stages=2,
        pipeline_config=PipelineConfig(
            init_params=params, stage_fn=stage_fn, loss_fn=loss_fn,
            batch_fn=lambda s: (X[s], T[s]), steps=STEPS,
            num_microbatches=M, schedule="1f1b", transport="shm",
            channel_capacity_bytes=1 << 20, lr=LR))
    res = trainer.fit()
    assert res.error is None, res.error
    ref_losses, ref_params = _sequential_sgd(params, stage_fn, loss_fn,
                                             X, T, M, LR)
    got = [h["loss"] for h in res.metrics_history]
    assert len(got) == STEPS
    for a, b in zip(got, ref_losses):
        assert abs(a - b) < 1e-3 * max(1.0, abs(b)), (got, ref_losses)
    final = res.artifacts["params"]
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(final[k]),
                                   np.asarray(ref_params[k]),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow        # ~21s schedule parity sweep
def test_mpmd_gpipe_schedule_parity_in_threads():
    """GPipe fallback schedule, hermetic: the stage loops run in two
    THREADS of this process over shm ring channels (no actor spawns —
    the schedule/channel logic is identical to the actor deployment),
    and the trajectory matches the sequential reference."""
    import threading

    from ray_tpu.experimental.channel import Channel
    from ray_tpu.parallel.pipeline import partition_layers, slice_stage
    from ray_tpu.train.pipeline import _stage_loop
    L, D, B, M, STEPS, LR = 4, 8, 8, 4, 2, 1e-2
    params, stage_fn, loss_fn, X, T = _mlp_fixture(L, D, STEPS, B,
                                                   seed=3)
    S = 2
    mk = lambda label: Channel.create(capacity=1 << 20, n_readers=1,  # noqa: E731
                                      depth=2, label=label)
    data_ch, tgt_ch, act0, grad0, loss_ch = (
        mk("data"), mk("tgt"), mk("act0"), mk("grad0"), mk("loss"))
    parts = partition_layers(L, S)
    out: dict = {}

    def run_stage(s):
        args = [None, s, S, slice_stage(params, *parts[s]), stage_fn,
                loss_fn, (), "gpipe", M, STEPS,
                data_ch if s == 0 else act0,          # in
                tgt_ch if s == 1 else None,           # targets
                act0 if s == 0 else None,             # act out
                grad0 if s == 0 else None,            # cot in
                grad0 if s == 1 else None,            # cot out
                loss_ch if s == 1 else None,
                None, None, LR, 0]
        try:
            out[s] = _stage_loop(*args)
        except BaseException as e:  # noqa: BLE001
            out[s] = e

    threads = [threading.Thread(target=run_stage, args=(s,),
                                daemon=True) for s in range(S)]
    for t in threads:
        t.start()
    data_w, tgt_w, loss_r = data_ch.writer(), tgt_ch.writer(), \
        loss_ch.reader(0)
    got = []
    bs = B // M
    for step in range(STEPS):
        for m in range(M):
            data_w.write(np.ascontiguousarray(
                X[step][m * bs:(m + 1) * bs]), timeout=60.0)
            tgt_w.write(np.ascontiguousarray(
                T[step][m * bs:(m + 1) * bs]), timeout=60.0)
        got.append(loss_r.read(timeout=60.0)["loss"])
    for t in threads:
        t.join(timeout=60)
    for s in range(S):
        assert not isinstance(out.get(s), BaseException), out[s]
    ref_losses, ref_params = _sequential_sgd(params, stage_fn, loss_fn,
                                             X, T, M, LR)
    for a, b in zip(got, ref_losses):
        assert abs(a - b) < 1e-3 * max(1.0, abs(b)), (got, ref_losses)
    full_w = np.concatenate([np.asarray(out[s]["w"]) for s in range(S)])
    np.testing.assert_allclose(full_w, np.asarray(ref_params["w"]),
                               rtol=1e-4, atol=1e-5)
    for ch in (data_ch, tgt_ch, act0, grad0, loss_ch):
        ch.destroy()


@pytest.mark.slow
def test_mpmd_4stage_wire_parity_and_overlap(ray_cluster):
    """The r13 acceptance e2e: a 4-stage multi-process pipeline over
    WIRE channels matches the single-process pp-axis 1F1B trajectory
    (MULTICHIP_r05 parity), and the collected cross-process timeline
    shows stage transfer spans CONCURRENT with neighbor stages'
    compute spans, with a finite bubble fraction reported."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ray_tpu.parallel.pipeline import pipeline_grads_1f1b
    from ray_tpu.train import JaxTrainer, PipelineConfig
    from ray_tpu.train.pipeline import bubble_fraction, overlap_pairs
    L, D, B, S, M, STEPS, LR = 8, 64, 16, 4, 8, 3, 1e-2
    params, stage_fn, loss_fn, X, T = _mlp_fixture(L, D, STEPS, B,
                                                   seed=1)
    trainer = JaxTrainer(
        pipeline_stages=S,
        pipeline_config=PipelineConfig(
            init_params=params, stage_fn=stage_fn, loss_fn=loss_fn,
            batch_fn=lambda s: (X[s], T[s]), steps=STEPS,
            num_microbatches=M, schedule="1f1b", transport="wire",
            channel_capacity_bytes=1 << 20, lr=LR))
    res = trainer.fit()
    assert res.error is None, res.error

    # single-process pp-axis baseline (the MULTICHIP_r05 machinery)
    mesh = Mesh(np.array(jax.devices()[:S]).reshape(S), ("pp",))
    p_sp = params
    sp_losses = []
    for step in range(STEPS):
        l, g = pipeline_grads_1f1b(mesh, stage_fn, loss_fn, p_sp,
                                   jnp.asarray(X[step]),
                                   jnp.asarray(T[step]), M)
        sp_losses.append(float(l))
        p_sp = jax.tree_util.tree_map(lambda a, b: a - LR * b, p_sp, g)
    got = [h["loss"] for h in res.metrics_history]
    for a, b in zip(got, sp_losses):
        assert abs(a - b) < 1e-3 * max(1.0, abs(b)), (got, sp_losses)

    procs = res.artifacts["trace_processes"]
    assert overlap_pairs(procs) > 0, \
        "no transfer/compute overlap in the stage timeline"
    bf = res.metrics.get("bubble_fraction", bubble_fraction(procs))
    assert 0.0 <= bf < 1.0
    # the timeline renders end-to-end (Perfetto JSON)
    from ray_tpu._private.tracing_plane import chrome_trace
    events = chrome_trace(procs)
    names = {e.get("name") for e in events if e.get("ph") == "X"}
    assert any(n.startswith("fwd:s") for n in names)
    assert any(n.startswith("ch.") for n in names)
