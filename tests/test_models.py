"""Model zoo tests on the virtual 8-device mesh."""
import collections
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer, TransformerConfig
from ray_tpu.models.config import tiny, llama2_7b, llama3_8b, PRESETS
from ray_tpu.models.transformer import (REMAT_KEPT_BYTES_BUDGET, REMAT_RUNGS,
                                        remat_kept_bytes)
from ray_tpu.parallel import prepare_mesh, param_shardings, shard_pytree


def test_param_count_exact():
    cfg = tiny()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params()


def test_llama2_7b_param_count():
    # canonical 6.74B
    assert abs(llama2_7b().num_params() - 6.738e9) < 2e7


def test_forward_shapes_and_loss():
    cfg = tiny()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    loss = model.loss(params, {"tokens": tokens})
    # random init ≈ uniform: CE ~ log(vocab)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.0


@pytest.mark.slow        # ~19s compile-bound; the dp/tp grad-step
                         # and MoE capacity gates keep mesh-sharded
                         # training in tier-1 (870s budget)
def test_sharded_train_step_runs_and_matches_single():
    cfg = tiny()
    mesh = prepare_mesh(dp=2, fsdp=2, tp=2)
    model = Transformer(cfg, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    shardings = param_shardings(mesh, model.param_logical_axes())
    sharded = shard_pytree(params, shardings)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size)

    loss_sharded = jax.jit(model.loss)(sharded, {"tokens": tokens})
    model_local = Transformer(cfg)  # no mesh: single device
    loss_local = model_local.loss(params, {"tokens": tokens})
    np.testing.assert_allclose(float(loss_sharded), float(loss_local),
                               rtol=1e-4)


@pytest.mark.slow        # ~27s end-to-end learning gate; forward
                         # parity + loss shape stay in tier-1
def test_grad_step_decreases_loss():
    cfg = tiny()
    mesh = prepare_mesh(dp=4, tp=2)
    model = Transformer(cfg, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    shardings = param_shardings(mesh, model.param_logical_axes())
    params = shard_pytree(params, shardings)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(model.loss)(p, batch)
        return loss, jax.tree.map(lambda w, gw: w - 0.5 * gw, p, g)

    loss0, params = step(params)
    for _ in range(4):
        loss, params = step(params)
    assert float(loss) < float(loss0)


def test_loss_mask():
    cfg = tiny()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    full = model.loss(params, {"tokens": tokens})
    masked = model.loss(params, {
        "tokens": tokens,
        "loss_mask": jnp.zeros((2, 16)).at[:, :8].set(1.0)})
    assert not np.isclose(float(full), float(masked))


def test_ring_attention_model_matches_flash():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq_len=64, remat=False, dtype="float32",
        param_dtype="float32", use_ring_attention=True)
    mesh = prepare_mesh(sp=4)
    model_ring = Transformer(cfg, mesh=mesh)
    params = model_ring.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    logits_ring = jax.jit(model_ring.apply)(params, tokens)
    cfg_flash = TransformerConfig(**{
        **cfg.__dict__, "use_ring_attention": False})
    model_flash = Transformer(cfg_flash)
    logits_flash = model_flash.apply(params, tokens)
    np.testing.assert_allclose(np.asarray(logits_ring),
                               np.asarray(logits_flash),
                               atol=2e-4, rtol=1e-3)


@pytest.mark.slow        # ~23s XLA compile-bound parity sweep; the
                         # other model parity/learning gates stay in
                         # tier-1 (870s budget, ROADMAP.md)
def test_chunked_loss_matches_dense():
    cfg = tiny()
    cfg_chunk = TransformerConfig(**{**cfg.__dict__, "loss_chunk": 32})
    model = Transformer(cfg)
    model_chunk = Transformer(cfg_chunk)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg.vocab_size)
    mask = jnp.zeros((2, 64)).at[:, 10:50].set(1.0)
    for batch in ({"tokens": tokens},
                  {"tokens": tokens, "loss_mask": mask}):
        dense = model.loss(params, batch)
        chunked = model_chunk.loss(params, batch)
        np.testing.assert_allclose(float(chunked), float(dense), rtol=1e-5)
    # grads agree too
    g1 = jax.grad(model.loss)(params, {"tokens": tokens})
    g2 = jax.grad(model_chunk.loss)(params, {"tokens": tokens})
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_tied_embeddings():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        tie_embeddings=True, remat=False, dtype="float32",
        param_dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert "lm_head" not in params
    logits = model.apply(params, jnp.zeros((1, 8), jnp.int32))
    assert logits.shape == (1, 8, 64)


def test_presets_importable():
    for name, fn in PRESETS.items():
        cfg = fn()
        assert cfg.num_params() > 0


# ------------------------------------------------------------------ moe
@pytest.mark.slow        # ~27s compile-bound; MoE tier-1 coverage
                         # rides test_moe_capacity_drops_tokens
def test_moe_identical_experts_equals_dense():
    """With every expert initialised to the dense FFN weights and
    renormalised top-k routing, the MoE block IS the dense block
    (sum_k w_k F(x) = F(x)) — the correctness anchor for dispatch."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.config import tiny
    dense_cfg = tiny()
    moe_cfg = dataclasses.replace(
        dense_cfg, moe_num_experts=4, moe_top_k=2,
        moe_capacity_factor=8.0)
    dense = Transformer(dense_cfg)
    moe = Transformer(moe_cfg)
    dp = dense.init(jax.random.PRNGKey(0))
    mp = moe.init(jax.random.PRNGKey(0))
    E = moe_cfg.moe_num_experts
    for name, src in (("moe_gate", "gate"), ("moe_up", "up"),
                      ("moe_down", "down")):
        mp["layers"][name] = jnp.broadcast_to(
            dp["layers"][src][:, None],
            (dense_cfg.n_layers, E) + dp["layers"][src].shape[1:])
    for k in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"):
        mp["layers"][k] = dp["layers"][k]
    mp["embed"] = dp["embed"]
    mp["final_norm"] = dp["final_norm"]
    mp["lm_head"] = dp["lm_head"]
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (4, 32), 0, dense_cfg.vocab_size))
    h_d = jax.jit(dense.hidden)(dp, tokens)
    h_m = jax.jit(moe.hidden)(mp, tokens)
    np.testing.assert_allclose(np.asarray(h_m), np.asarray(h_d),
                               atol=1e-5)


@pytest.mark.slow        # ~47s ep-mesh parity sweep, the heaviest
                         # passing tier-1 test in the suite
def test_moe_ep_mesh_invariance_and_router_grads():
    """The same MoE model on an (dp,ep,tp) mesh must match single-device
    outputs; router gets gradient signal through the load-balance loss
    and combine weights."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.config import tiny
    from ray_tpu.parallel.mesh import MeshSpec
    cfg = dataclasses.replace(tiny(), moe_num_experts=4, moe_top_k=2,
                              moe_capacity_factor=2.0)
    mesh = MeshSpec(dp=2, ep=2, tp=2).build()
    model = Transformer(cfg)
    model_mesh = Transformer(cfg, mesh=mesh)
    params = model.init(jax.random.PRNGKey(3))
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size))
    h1 = jax.jit(model.hidden)(params, tokens)
    h2 = jax.jit(model_mesh.hidden)(params, tokens)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h1), atol=1e-5)
    loss, g = jax.value_and_grad(model_mesh.loss)(
        params, {"tokens": jnp.asarray(tokens)})
    assert np.isfinite(float(loss))
    assert float(jnp.linalg.norm(g["layers"]["router"])) > 0
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(g))


def test_moe_capacity_drops_tokens():
    """A tiny capacity factor must drop tokens (reported metric) while
    keeping outputs finite (dropped tokens ride the residual)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.moe import expert_capacity, moe_ffn
    T, d, E, f = 64, 8, 4, 16
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (2, T // 2, d))
    out, aux = moe_ffn(
        x, jax.random.normal(ks[1], (d, E)) * 5.0,  # skewed router
        jax.random.normal(ks[2], (E, d, f)) * 0.1,
        jax.random.normal(ks[3], (E, d, f)) * 0.1,
        jax.random.normal(ks[4], (E, f, d)) * 0.1,
        top_k=2, capacity_factor=0.25)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux["moe_dropped_fraction"]) > 0.1
    assert expert_capacity(64, 4, 2, 0.25) == 8


# ------------------------------------- what a rematted layer keeps
# GQA at widths that tell the projections apart: wq and wo (48, 48),
# wk and wv (48, 24), gate and up (48, 80)
_REMAT_CFG = dict(vocab_size=64, d_model=48, n_layers=2, n_heads=4,
                  n_kv_heads=2, d_ff=80, max_seq_len=32, dtype="float32",
                  param_dtype="float32")


def _remat_batch():
    return {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                         0, 64)}


def _remat_model(policy):
    """`policy` None: `remat=True` and nothing said about a policy."""
    over = {} if policy is None else {"remat_policy": policy}
    return Transformer(TransformerConfig(**_REMAT_CFG, remat=True, **over))


@pytest.fixture(scope="module")
def unrematted():
    model = Transformer(TransformerConfig(**_REMAT_CFG, remat=False))
    params = model.init(jax.random.PRNGKey(0))
    return params, jax.value_and_grad(model.loss)(
        params, _remat_batch())


@pytest.mark.parametrize("policy", ["full", "save_attn", "save_attn_qkv",
                                    "save_attn_stream",
                                    "save_attn_stream_up", "save_matmuls",
                                    None])
def test_remat_policy_keeps_loss_and_gradients(unrematted, policy):
    """A kept value is the value that would have been recomputed: every
    rung, and the default (the top rung at these shapes), gives the loss
    and gradients of no remat."""
    params, (loss0, grads0) = unrematted
    loss, grads = jax.value_and_grad(_remat_model(policy).loss)(
        params, _remat_batch())
    np.testing.assert_allclose(float(loss), float(loss0), atol=1e-6)
    for (path, g), g0 in zip(jax.tree_util.tree_leaves_with_path(grads),
                             jax.tree.leaves(grads0)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(g0),
                                   atol=1e-6, err_msg=str(path))


def test_unknown_remat_policy_is_refused():
    model = _remat_model("save_everything")
    with pytest.raises(ValueError, match="save_attn_qkv"):
        model.loss(model.init(jax.random.PRNGKey(0)),
                   _remat_batch())


def _walk(jaxpr, visit):
    for eqn in jaxpr.eqns:
        visit(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _walk(sub, visit)


@functools.lru_cache(maxsize=None)
def _layer_scans(policy):
    """What the two scans over the layers hold, forward then backward:
    the weight shapes of the dots of the form `activations @ weight` (a
    backward's own dots contract other dimensions, so those in its scan
    are the forward's, run again) and a count of every primitive."""
    model = _remat_model(policy)
    jaxpr = jax.make_jaxpr(jax.grad(model.loss))(
        model.init(jax.random.PRNGKey(0)), _remat_batch())
    scans = []
    _walk(jaxpr.jaxpr, lambda e: e.primitive.name == "scan"
          and scans.append(e))
    assert [e.params["reverse"] for e in scans] == [False, True]
    out = []
    for scan in scans:
        weights, prims = collections.Counter(), collections.Counter()

        def visit(eqn):
            prims[eqn.primitive.name] += 1
            if (eqn.primitive.name == "dot_general"
                    and eqn.params["dimension_numbers"]
                    == (((2,), (0,)), ((), ()))):
                weights[eqn.invars[1].aval.shape] += 1
        _walk(scan.params["jaxpr"].jaxpr, visit)
        out.append((weights, prims))
    return out


def test_save_attn_qkv_reruns_no_projection_rotation_or_transpose():
    """Under "save_attn_qkv" the backward's scan holds no q, k or v
    projection, neither rotation and none of the three transposes into
    the kernel's layout, where "full" holds them all: it fails if a name
    is dropped, or put on q or k before the rotary. What stays is what
    the rungs above keep: the output projection, gate and up."""
    (fwd, _), (again, prims) = _layer_scans("save_attn_qkv")
    (fwd_full, _), (again_full, prims_full) = _layer_scans("full")
    layer = {(48, 48): 2, (48, 24): 2, (48, 80): 2, (80, 48): 1}
    assert fwd == fwd_full == layer
    assert again_full == {(48, 48): 2, (48, 24): 2, (48, 80): 2}
    assert again == {(48, 48): 1, (48, 80): 2}
    # a rotation ends in a concatenate; q, k and v are each turned to
    # (b, h, s, hd)
    assert prims_full["concatenate"] - prims["concatenate"] == 2
    assert prims_full["transpose"] - prims["transpose"] == 3


@pytest.mark.parametrize("policy,run_again", [
    ("save_attn_stream", {(48, 80): 2}),
    ("save_attn_stream_up", {(48, 80): 1}),
    ("save_matmuls", {}),
    (None, {}),
])
def test_upper_rungs_rerun_one_matmul_fewer_each(policy, run_again):
    """Each rung above "save_attn_qkv" takes one more `activations @
    weight` out of the backward's scan: the output projection, `up`, then
    gate; on the top rung, and under the default at these shapes, the
    backward runs no matmul of the forward again, and no rotation or
    transpose either."""
    (fwd, _), (again, prims) = _layer_scans(policy)
    assert fwd == {(48, 48): 2, (48, 24): 2, (48, 80): 2, (80, 48): 1}
    assert again == run_again
    _, (_, prims_qkv) = _layer_scans("save_attn_qkv")
    assert prims["concatenate"] == prims_qkv["concatenate"]
    assert prims["transpose"] == prims_qkv["transpose"]


# ----------------------------------------- the rung nobody named
_MISTRAL_7B = dict(vocab_size=32000, d_model=4096, n_heads=32, n_kv_heads=8,
                   d_ff=14336, dtype="bfloat16", param_dtype="bfloat16")


@pytest.mark.parametrize("config,mesh,batch_tokens,per_device,rung", [
    # the train cell: 2 x 4096 tokens through 5 layers on one device
    (dict(_MISTRAL_7B, n_layers=5), None, 8192, 8192, "save_matmuls"),
    # a whole model at 4096 tokens a device: a rung between
    (llama3_8b(), None, 4096, 4096, "save_attn_stream"),
    # and at 8192: the floor, today's rung, whatever it keeps
    (llama3_8b(), None, 8192, 8192, "save_attn_qkv"),
    (dict(_MISTRAL_7B, n_layers=32), None, 8192, 8192, "save_attn_qkv"),
    # a batch over 4 devices: what one device takes at a quarter of it
    (llama3_8b(), ("fsdp", 4), 16384, 4096, "save_attn_stream"),
    (llama3_8b(), ("dp", 4), 8192, 2048, "save_attn_stream_up"),
    (llama3_8b(), ("sp", 4), 8192, 2048, "save_attn_stream_up"),
    # tp splits some of the kept values and not the tokens: counted high
    (llama3_8b(), ("tp", 4), 4096, 4096, "save_attn_stream"),
    # a MoE layer has no name above the stream
    (dict(_MISTRAL_7B, n_layers=2, moe_num_experts=8), None, 1024, 1024,
     "save_attn_stream"),
    # a named rung is taken as named, fit or not
    (dict(_MISTRAL_7B, n_layers=32, remat_policy="save_matmuls"), None,
     65536, 65536, "save_matmuls"),
    (dict(_MISTRAL_7B, n_layers=5, remat_policy="full"), None, 8192, 8192,
     "full"),
], ids=["train_cell", "llama3_8b_4k", "llama3_8b_8k", "mistral_7b_8k",
        "fsdp4", "dp4", "sp4", "tp4", "moe", "named_top", "named_full"])
def test_unnamed_remat_policy_takes_the_dearest_rung_that_fits(
        config, mesh, batch_tokens, per_device, rung):
    """`remat_plan` is a pure function of the config, the mesh and the
    tokens of a batch: no trace, no device asked."""
    if isinstance(config, dict):
        config = TransformerConfig(**config)
    if mesh is not None:
        axis, n = mesh
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), (axis,))
    got, kept = Transformer(config, mesh=mesh).remat_plan(batch_tokens)
    assert got == rung
    assert (got, kept) == Transformer(config).remat_plan(per_device)
    assert kept == remat_kept_bytes(config, rung, per_device)
    if config.remat_policy == "auto" and rung != "save_attn_qkv":
        assert kept <= REMAT_KEPT_BYTES_BUDGET


def test_kept_bytes_a_token_and_layer_are_config_pys_figures():
    config = TransformerConfig(**_MISTRAL_7B, n_layers=1)
    assert [remat_kept_bytes(config, rung, 1) for rung in REMAT_RUNGS] == [
        0, 8320, 20608, 28800, 57472, 86144]


# ----------------------------------------- the served classes' seam
def _tiny_served():
    from ray_tpu.models.gated_conv_moe import tiny_gated_conv_moe
    from ray_tpu.models.gqa_window_moe import tiny_gqa_window_moe
    from ray_tpu.models.hybrid_delta import tiny_hybrid_delta
    from ray_tpu.models.hybrid_kda_moe import tiny_hybrid_kda_moe
    from ray_tpu.models.hybrid_ssm_moe import tiny_hybrid_ssm_moe
    from ray_tpu.models.mla_moe import tiny_mla_moe
    from ray_tpu.models.parallel_hybrid import tiny_parallel_hybrid
    from ray_tpu.models.shortcut_mla_moe import tiny_shortcut_mla_moe
    from ray_tpu.models.sparse_mla_moe import tiny_sparse_mla_moe
    from ray_tpu.models.sparse_window_mla_moe import (
        tiny_sparse_window_mla_moe)
    return {"MLAMoE": tiny_mla_moe, "GQAWindowMoE": tiny_gqa_window_moe,
            "HybridDelta": tiny_hybrid_delta,
            "ShortcutMLAMoE": tiny_shortcut_mla_moe,
            "HybridSSMMoE": tiny_hybrid_ssm_moe,
            "HybridKDAMoE": tiny_hybrid_kda_moe,
            "ParallelHybrid": tiny_parallel_hybrid,
            "GatedConvMoE": tiny_gated_conv_moe,
            "SparseMLAMoE": tiny_sparse_mla_moe,
            "SparseWindowMLAMoE": tiny_sparse_window_mla_moe}


def _tree_sha256(tree) -> str:
    """One hash of a parameter tree: every leaf's path, shape, dtype and
    bytes, in the tree's own order."""
    import hashlib
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# `init(PRNGKey(0))` of each served class at its tiny configuration, as
# PR 48's classes gave it (recorded in PR 49 before `init` and its `fill`
# moved into `models/paged.py`): the same key gives the same arrays under
# the same names, so the benchmark's `weight_shapes` and every seeded test
# see the trees they saw
INIT_SHA256 = {
    "MLAMoE": "74eb686498725a64",
    "GQAWindowMoE": "4eb69e36a8d1a19f",
    "HybridDelta": "8b3f53971ffd9817",
    "ShortcutMLAMoE": "085f5ae8b852613d",
    "HybridSSMMoE": "90619eeef1dbbf44",
    # the seventh class, as PR 50 made it
    "HybridKDAMoE": "dc5c8e45cfc30e11",
    # the eighth, as PR 54 made it
    "ParallelHybrid": "e6602d2e0cf60bfe",
    # the ninth, as PR 58 made it (one table: the head is the embedding's)
    "GatedConvMoE": "ae1fa1c05a49a2dd",
    # the tenth, as PR 61 made it (`MLAMoE`'s leaves, the indexer's behind
    # them, the held experts')
    "SparseMLAMoE": "6c8d1c95cb0cd4d3",
}


@pytest.mark.parametrize("name", sorted(INIT_SHA256))
def test_served_class_init_gives_the_arrays_it_gave(name):
    from ray_tpu.models import build_model
    model = build_model(_tiny_served()[name]())
    assert type(model).__name__ == name
    assert _tree_sha256(model.init(jax.random.PRNGKey(0))) == INIT_SHA256[
        name]


def _tiny_models():
    """name in `MODELS` -> tiny config, every entry of the table."""
    from ray_tpu.models import MODELS
    by_class = {model.__name__: name for name, (_, model) in MODELS.items()}
    tiny_of = {by_class[cls]: make for cls, make in _tiny_served().items()}
    tiny_of["transformer"] = tiny
    assert sorted(tiny_of) == sorted(MODELS)
    return tiny_of


@pytest.mark.parametrize("name", ["transformer", "mla_moe", "gqa_window_moe",
                                  "hybrid_delta", "shortcut_mla_moe",
                                  "hybrid_ssm_moe", "hybrid_kda_moe",
                                  "parallel_hybrid", "gated_conv_moe",
                                  "sparse_mla_moe",
                                  "sparse_window_mla_moe"])
def test_every_class_answers_the_engines_fourteen_asks(name):
    """What `EngineCore` calls on a model, on every class of the table,
    with the types it uses them as (`models.paged.PagedDecoder`)."""
    from ray_tpu.models import MODELS, build_model, model_config
    from ray_tpu.models.paged import PagedDecoder
    cfg = _tiny_models()[name]()
    config_type, model_type = MODELS[name]
    model = build_model(cfg)
    assert type(cfg) is config_type and type(model) is model_type
    assert isinstance(model, PagedDecoder)
    fields = {**dataclasses.asdict(cfg), "type": name}
    assert model_config(fields) == cfg
    page, B, s = 8, 2, 16
    mp = cfg.max_seq_len // page
    fixed = model.fixed_pages(page)                             # 1
    assert isinstance(fixed, int) and fixed >= 0
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(            # 2
        B * mp, page, **({"fixed_pages": B * fixed} if fixed else {})))
    assert isinstance(cache, dict)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa
    logits, after = jax.eval_shape(                             # 3
        lambda p, t, n, pt, c: model.prefill(p, t, n, pt, c, page),
        params, i32(s), i32(), i32(mp), cache)
    assert (logits.shape, logits.dtype) == ((cfg.vocab_size,), jnp.float32)
    assert jax.tree_util.tree_structure(after) == (
        jax.tree_util.tree_structure(cache))
    logits, after = jax.eval_shape(                             # 4
        lambda p, c, t, pos, pts, a: model.decode_step(
            p, c, t, pos, pts, a, page),
        params, cache, i32(B), i32(B), i32(B, mp),
        jax.ShapeDtypeStruct((B,), jnp.bool_))
    assert (logits.shape, logits.dtype) == ((B, cfg.vocab_size),
                                            jnp.float32)
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), after) == (
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), cache))
    assert model.cache_page_bytes(page) > 0                     # 5
    if fixed:
        assert model.cache_page_bytes(page, fixed=True) > 0
    counts = model.fixed_step_counts(9, page)                   # 6
    assert bool(counts) == bool(fixed)
    assert all(isinstance(n, int) for n in counts.values())
    assert all(isinstance(n, int)                               # 7
               for n in model.prefill_counts(9, s).values())
    assert isinstance(model.decode_attention(page), str)        # 8
    assert 1 <= model.walk_block_pages(page, mp) <= mp          # 9
    stats = model.step_stats(cache)                             # 10
    assert all(a.shape == () for a in stats.values())
    assert set(stats) <= set(collections.ChainMap(after, *(
        v for v in after.values() if isinstance(v, dict))))
    real = model.init_cache(2, page, **(
        {"fixed_pages": fixed} if fixed else {}))
    assert isinstance(model.cache_stats(real), dict)            # 11
    assert model.pool_rows is None or model.pool_rows >= 1      # 12
    from ray_tpu.ops.dispatch import compute_platform
    for platform in (None, "tpu"):      # (where the kernels run: runs)
        with compute_platform(platform):
            run = model.page_run(page, mp)                      # 13
            table = model.table_pages(page, mp)                 # 14
            assert model.page_run(page, table) == run
        assert table == (mp if run == 1 or not fixed else
                         fixed + -(-(mp - fixed) // run) * run)
        assert (table - fixed) % run == 0 or not fixed and mp % run == 0
    # what the byte asks say the cache costs is what `init_cache` makes
    # (the counts apart): `num_pages` pages, and of the fixed class a
    # ring's pages, or the sequences' slots and one more, nobody's
    pools = [a for key, a in cache.items()
             if not isinstance(a, dict) and key != "moe_load"]
    held = B * fixed + (model.state_bytes() > 0 if fixed else 0)
    assert sum(math.prod(a.shape) * a.dtype.itemsize for a in pools) == (
        B * mp * model.cache_page_bytes(page)
        + (held * model.cache_page_bytes(page, fixed=True) if fixed else 0))


# -------------------------------------- the cache's addresses, by hand
def _np_decode_lanes(positions, tables, active, num_pages, page):
    out = []
    for pos, table, on in zip(positions, tables, active):
        entry = table[pos // page]
        out.append((entry if on and entry >= 0 else num_pages, pos % page,
                    pos + 1 if on else 0))
    return [list(col) for col in zip(*out)]


@pytest.mark.parametrize("positions,active", [
    ([0, 7, 8, 31], [True, True, True, True]),      # a page's edges
    ([5, 9, 17, 2], [True, False, True, False]),    # inactive lanes
    ([24, 16, 3, 15], [True, True, True, True]),    # unassigned entries
])
def test_decode_lanes_against_a_loop(positions, active):
    from ray_tpu.models.paged import decode_lanes, lane_page
    page, num_pages = 8, 12
    tables = np.array([[3, 1, 4, -1], [5, 9, -1, -1], [2, -1, 6, 0],
                       [7, 8, -1, -1]], np.int32)
    want = _np_decode_lanes(positions, tables, active, num_pages, page)
    pos, act = jnp.asarray(positions, jnp.int32), jnp.asarray(active)
    got = decode_lanes(pos, jnp.asarray(tables), act, num_pages, page)
    assert [np.asarray(a).tolist() for a in got] == want
    # the piece the two classes with their own order call
    assert np.asarray(lane_page(jnp.asarray(tables), pos // page, act,
                                num_pages)).tolist() == want[0]


@pytest.mark.parametrize("true_len", [1, 7, 8, 9, 16, 24, 29])
@pytest.mark.parametrize("ring", [0, 2, 3])
def test_prefill_page_ids_against_a_loop(true_len, ring):
    """Both spellings name the same pages (a prompt that ends on a page's
    last position fills that page and not the next), and the ring's ids
    are the newest page at each entry."""
    from ray_tpu.models.paged import prefill_page_ids, prefill_page_ids_held
    page, s, num_pages, ring_pages = 8, 29, 20, 6
    table = np.array([4, 2, 9, 7, 11, 13], np.int32)
    n = -(-s // page)
    want = [int(table[j]) if j * page < true_len else num_pages
            for j in range(n)]
    held = -(-true_len // page)
    want_ring = [int(table[j % ring]) if j < held and j >= held - ring
                 else ring_pages for j in range(n)] if ring else None
    args = (jnp.asarray(table), jnp.int32(true_len), s, num_pages, page)
    assert np.asarray(prefill_page_ids(*args)).tolist() == want
    ids, ring_ids = prefill_page_ids_held(*args, ring, ring_pages)
    assert np.asarray(ids).tolist() == want
    assert (ring_ids if ring_ids is None
            else np.asarray(ring_ids).tolist()) == want_ring
    if ring:        # every ring entry the prompt reaches is written once
        live = [i for i in want_ring if i != ring_pages]
        assert sorted(live) == sorted(set(live))
        assert len(live) == min(held, ring)


@pytest.mark.parametrize("first,active,slot,prefill", [
    (0, True, 0, 0), (3, True, 3, 3),           # a slot of the class
    (4, True, -1, 5),                           # past the pool: nobody's
    (9, True, -1, 5), (-1, True, -1, 5),        # and unassigned
    (2, False, -1, 2),                          # an inactive lane
])
def test_state_slot_arithmetic_against_its_cases(first, active, slot,
                                                 prefill):
    """`slots` = 4 slots of the class and one more, nobody's (index 4):
    a lane without a slot updates none (-1): its convolution reads
    nobody's rows, which exist, and writes none."""
    from ray_tpu.models.paged import decode_state_slots, prefill_state_slot
    from ray_tpu.ops.conv import conv_step, conv_tail_step, tail_shape
    slots = 4
    tables = jnp.asarray([[first, 7, 8]], jnp.int32)
    got = decode_state_slots(tables, jnp.asarray([active]), slots)
    assert int(got[0]) == slot
    assert int(prefill_state_slot(tables[0], slots)) == prefill
    pool = jnp.arange(2. * (slots + 1) * 3 * 2).reshape(
        2, slots + 1, *tail_shape(4, 2))
    x, w = jnp.asarray([[1., -1.]]), jnp.ones((4, 2)) / 8
    y, written = conv_tail_step(x, w, pool, 1, got)
    want_y, want_rows = conv_step(x, pool[1, slot].reshape(1, 3, 2), w)
    assert y.tolist() == want_y.tolist()
    assert bool((written == pool).all()) == (slot < 0)
    if slot >= 0:
        assert written[1, slot].reshape(1, 3, 2).tolist() == \
            want_rows.tolist()
