"""Kernel correctness: Pallas (interpreter) and collective ops vs references."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.models.gqa import FULL_BLOCKS
from ray_tpu.models.latent import PREFILL_BLOCKS
from ray_tpu.ops import (apply_rope, flash_attention, layer_norm,
                         mha_reference, ring_attention, rms_norm,
                         softmax_cross_entropy)
from ray_tpu.ops.attention import flash_attention_kernel
from ray_tpu.ops.losses import sharded_softmax_cross_entropy
from ray_tpu.ops.norms import rms_norm_reference
from ray_tpu.parallel import prepare_mesh


def test_rms_norm_matches_reference():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64,)) * 0.1
    got = rms_norm(x, w)
    want = rms_norm_reference(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_rms_norm_grad():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 32), jnp.float32)
    w = jnp.zeros(32)
    g1 = jax.grad(lambda x_, w_: jnp.sum(rms_norm(x_, w_) ** 2),
                  argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x_, w_: jnp.sum(rms_norm_reference(x_, w_) ** 2),
                  argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_layer_norm_basic():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
    out = layer_norm(x, jnp.ones(32), jnp.zeros(32))
    np.testing.assert_allclose(np.asarray(out).mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out).std(-1), 1.0, atol=1e-2)


def test_rope_rotation_preserves_norm_and_position_zero():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    out = apply_rope(x, pos)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(out), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # position 0 is the identity rotation
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(x[:, 0]),
                               atol=1e-6)


def test_rope_relative_property():
    # <rope(q,m), rope(k,n)> depends only on m - n
    d = 16
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, d))

    def dot_at(m, n):
        qm = apply_rope(jnp.broadcast_to(q, (1, 1, 1, d)),
                        jnp.array([[m]]))
        kn = apply_rope(jnp.broadcast_to(k, (1, 1, 1, d)),
                        jnp.array([[n]]))
        return float(jnp.sum(qm * kn))

    assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kvh", [4, 1])
def test_flash_kernel_matches_reference(causal, kvh):
    b, h, s, d = 2, 4, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kvh, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kvh, s, d), jnp.float32)
    got = flash_attention_kernel(q, k, v, causal=causal,
                                 block_q=128, block_k=128)
    want = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_backward_matches_reference():
    b, h, s, d = 1, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_kernel(q, k, v, causal=True,
                                              block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, rtol=1e-3)


def test_flash_gqa_backward():
    b, h, kvh, s, d = 1, 4, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kvh, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kvh, s, d), jnp.float32)
    g1 = jax.grad(lambda *a: jnp.sum(
        flash_attention_kernel(*a, block_q=32, block_k=32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(mha_reference(*a) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("causal,h,kvh,s,blocks,diag", [
    (True, 4, 4, 256, (64, 64), 32),     # 4 blocks a side, 2 x 2 squares
    (True, 4, 1, 256, (64, 64), 16),     # MQA, 4 x 4 squares a diagonal pair
    (True, 8, 2, 128, (64, 64), 32),     # group 4, 2 blocks a side
    (True, 8, 2, 64, (64, 64), 16),      # one block: the diagonal case alone
    (True, 2, 2, 200, (64, 64), 32),     # a tail of 8 in the fourth block
    (True, 4, 1, 100, (64, 64), 32),     # MQA and a tail
    (True, 4, 1, 250, (64, 64), 64),     # the diagonal pair walked whole
    (True, 2, 2, 128, (32, 64), 16),     # blocks unlike: one masked square
    (True, 2, 1, 128, (64, 32), 16),
    (True, 2, 2, 128, (64, 64), 48),     # a square that does not divide it
    (True, 2, 1, 40, (64, 64), 16),      # shorter than a block
    (False, 8, 2, 128, (64, 64), 32),    # not causal: no mask, no walk
    (False, 2, 2, 96, (64, 64), 32),     # not causal, a tail
])
def test_flash_backward_kernel_matches_reference(causal, h, kvh, s, blocks,
                                                 diag):
    """The one backward kernel (interpreter) against the gradients of
    `mha_reference` and against `_flash_bwd_xla`: a head's dq is summed in
    VMEM over every key block of its row, dk and dv over a column's query
    blocks, and of a pair the diagonal cuts only the squares on and under
    it are computed."""
    from ray_tpu.ops.attention import (_flash_bwd_pallas, _flash_bwd_xla,
                                       _flash_fwd)
    d, (block_q, block_k) = 32, blocks
    ks = jax.random.split(jax.random.PRNGKey(s + h + diag), 4)
    q, do = (jax.random.normal(kk, (2, h, s, d), jnp.float32)
             for kk in ks[:2])
    k, v = (jax.random.normal(kk, (2, kvh, s, d), jnp.float32)
            for kk in ks[2:])
    scale = d ** -0.5
    o, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, True)
    got = _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale, block_q,
                            block_k, True, diag_block=diag)
    _, vjp = jax.vjp(lambda *a: mha_reference(*a, causal=causal), q, k, v)
    blockwise = _flash_bwd_xla(q, k, v, o, lse, do, causal, scale, block_k)
    for name, a, want, xla in zip(("dq", "dk", "dv"), got, vjp(do),
                                  blockwise):
        assert a.shape == want.shape and a.dtype == want.dtype, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                                   atol=1e-3, rtol=1e-3, err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(xla),
                                   atol=1e-3, rtol=1e-3, err_msg=name)


def test_flash_saveable_grads_and_remat_policy():
    """The remat-saveable path (named out/lse residuals) must produce the
    same gradients as the reference, standalone and under jax.checkpoint
    with a policy that keeps them (`remat_policy="save_attn"`)."""
    from ray_tpu.ops.attention import (ATTN_RESIDUAL_NAMES,
                                       flash_attention_saveable)
    b, h, s, d = 1, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)

    g_ref = jax.grad(lambda *a: jnp.sum(mha_reference(*a, causal=True) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    g_sv = jax.grad(lambda *a: jnp.sum(flash_attention_saveable(
        *a, causal=True, block_q=64, block_k=64) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    rematted = jax.checkpoint(
        lambda *a: flash_attention_saveable(
            *a, causal=True, block_q=64, block_k=64),
        policy=jax.checkpoint_policies.save_only_these_names(
            *ATTN_RESIDUAL_NAMES))
    g_rm = jax.grad(lambda *a: jnp.sum(rematted(*a) ** 2),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b_, c in zip(g_ref, g_sv, g_rm):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    b, h, s, d = 1, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    mesh = prepare_mesh(sp=8)
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    got = jax.jit(lambda q_, k_, v_: ring_attention_sharded(
        q_, k_, v_, mesh, causal=causal))(q, k, v)
    want = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


def test_ring_attention_gqa():
    b, h, kvh, s, d = 1, 4, 2, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kvh, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kvh, s, d), jnp.float32)
    mesh = prepare_mesh(sp=4)
    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False)
    got = jax.jit(fn)(q, k, v)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


def test_flash_non_multiple_seq_fwd_bwd():
    b, h, s, d = 1, 2, 96, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    got = flash_attention_kernel(q, k, v, causal=False,
                                 block_q=64, block_k=64)
    want = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda *a: jnp.sum(flash_attention_kernel(
        *a, block_q=64, block_k=64) ** 2), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(mha_reference(*a) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, rtol=1e-3)


# the latent classes' prefills (`models/latent.py`): keys wider than values,
# at the blocks they give the forward and, for a bucket under them, the
# whole bucket a block
LATENT_WIDTHS = [(192, 128), (256, 256)]
LATENT_BLOCKS = [PREFILL_BLOCKS, (512, 512), (256, 256)]


@pytest.mark.parametrize("d,dv", LATENT_WIDTHS)
@pytest.mark.parametrize("blocks", LATENT_BLOCKS,
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("fit", ["shorter", "equal", "tail"])
def test_flash_forward_at_the_latent_prefills_blocks(fit, blocks, d, dv):
    """A sequence shorter than a block (the call cuts the blocks to it),
    as long as the larger one, and no multiple of either (the tail key
    block's mask, the tail query block's rows)."""
    block_q, block_k = blocks
    s = {"shorter": min(blocks) // 2, "equal": max(blocks),
         "tail": max(blocks) + min(blocks) // 2 + 40}[fit]
    ks = jax.random.split(jax.random.PRNGKey(s + d), 3)
    q = jax.random.normal(ks[0], (1, 2, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1, s, dv), jnp.float32)
    got = flash_attention_kernel(q, k, v, causal=True, block_q=block_q,
                                 block_k=block_k)
    assert got.shape == (1, 2, s, dv)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(mha_reference(q, k, v)),
                               atol=2e-5, rtol=2e-5)


# the dense class's prefill (`models/decode.py`): two query heads a kv head
# of 128 at `gqa.FULL_BLOCKS`, on the grids the chip runs: one block a head
# up to 1,024 tokens, 2 x 2 and 4 x 4 with the blocks above the diagonal
# skipped from there. Off a TPU `flash_attention` is the einsum, so the
# class's own equivalence tests never reach the kernel.
@pytest.mark.parametrize("bucket", [512, 1024, 2048, 4096])
def test_flash_forward_at_the_dense_prefills_blocks(bucket):
    ks = jax.random.split(jax.random.PRNGKey(bucket), 3)
    q = jax.random.normal(ks[0], (1, 4, bucket, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, bucket, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, bucket, 128), jnp.float32)
    got = flash_attention_kernel(q, k, v, causal=True,
                                 block_q=FULL_BLOCKS[0],
                                 block_k=FULL_BLOCKS[1])
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(mha_reference(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_flash_return_lse_differentiable():
    b, h, s, d = 1, 1, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    g = jax.grad(lambda q_: jnp.sum(
        flash_attention(q_, k, v, return_lse=True)[0] ** 2))(q)
    gr = jax.grad(lambda q_: jnp.sum(mha_reference(q_, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               atol=1e-3, rtol=1e-3)


def test_ring_sharded_gqa_with_tp():
    b, h, kvh, s, d = 1, 4, 2, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kvh, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kvh, s, d), jnp.float32)
    mesh = prepare_mesh(tp=4, sp=2)
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    got = jax.jit(lambda *a: ring_attention_sharded(*a, mesh))(q, k, v)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


def test_ring_sharded_custom_mesh_without_standard_axes():
    """ADVICE r1: specs must be built from axes the mesh actually has —
    a bare Mesh(devs, ("sp",)) used to raise on the hard-coded dp/fsdp/tp
    PartitionSpec."""
    import numpy as _np
    from jax.sharding import Mesh
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    b, h, s, d = 1, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(19), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    mesh = Mesh(_np.array(jax.devices()[:4]), ("sp",))
    got = jax.jit(lambda *a: ring_attention_sharded(*a, mesh))(q, k, v)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("h,kvh", [
    (8, 1),    # MQA: replicated-KV fast path
    (12, 3),   # kvh % tp != 0, kvh > 1: must take the repeat path —
               # replication would misalign contiguous q-head blocks to
               # kv heads (caught in r2 review)
])
def test_ring_sharded_gqa_nondivisible_tp(h, kvh):
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    b, s, d = 2, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kvh, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kvh, s, d), jnp.float32)
    mesh = prepare_mesh(tp=2, sp=2, dp=2)
    got = jax.jit(lambda *a: ring_attention_sharded(*a, mesh))(q, k, v)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.slow        # ~15s; the grad-matches-autodiff twin
                         # keeps cross-entropy in tier-1
def test_softmax_cross_entropy():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 32))
    labels = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 32)
    loss, per_tok = softmax_cross_entropy(logits, labels)
    want = -jax.nn.log_softmax(logits)[
        jnp.arange(4)[:, None], jnp.arange(8)[None], labels]
    np.testing.assert_allclose(np.asarray(per_tok), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert loss.shape == ()


def test_softmax_cross_entropy_grad_matches_autodiff():
    logits = jax.random.normal(jax.random.PRNGKey(2), (4, 8, 32)) * 3
    labels = jax.random.randint(jax.random.PRNGKey(3), (4, 8), 0, 32)
    g1 = jax.grad(lambda lg: softmax_cross_entropy(lg, labels)[0])(logits)
    g2 = jax.grad(lambda lg: -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(lg), labels[..., None], axis=-1)))(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=1e-6, rtol=1e-5)


def test_softmax_cross_entropy_mask():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 16))
    labels = jnp.zeros((2, 4), jnp.int32)
    mask = jnp.array([[1, 1, 0, 0], [1, 0, 0, 0]], jnp.float32)
    loss, per_tok = softmax_cross_entropy(logits, labels, mask=mask)
    want = (per_tok * mask).sum() / 3.0
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)


def test_sharded_cross_entropy_matches_dense():
    vocab, shard = 64, 8
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 16, vocab))
    labels = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, vocab)
    mesh = prepare_mesh(tp=8)
    fn = jax.shard_map(
        lambda lg, lb: sharded_softmax_cross_entropy(lg, lb, "tp", shard),
        mesh=mesh,
        in_specs=(P(None, None, "tp"), P(None, None)),
        out_specs=(P(), P(None, None)), check_vma=False)
    loss, per_tok = jax.jit(fn)(logits, labels)
    dense_loss, dense_per = softmax_cross_entropy(logits, labels)
    np.testing.assert_allclose(float(loss), float(dense_loss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(per_tok), np.asarray(dense_per),
                               atol=1e-5, rtol=1e-5)
