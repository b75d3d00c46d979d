"""The selective scan's ops (`ray_tpu/ops/ssd.py`): the chunk kernel and the
step kernel (through the Pallas interpreter) and their plain twins against
the recurrence written position by position, at lengths that are and are
not whole chunks, stopping at a true length inside a bucket, the state
handed from a prefill to decode steps, the slots a step leaves alone, and
the convolution with its bias; and both kernels at a group of heads wider
than a block of the step kernel. Tiny sizes, CPU, seeded.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import conv
from ray_tpu.ops import ssd
from ray_tpu.ops.dispatch import compute_platform

H, G, P, N, C = 4, 2, 8, 16, 8


def _case(s, seed=0, dtype=jnp.float32, H=H, P=P):
    """x (s, H x P), B, C (s, G x N), steps dt (s, H) as a softplus gives
    them and rates A (H,) that span an order of magnitude."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(s, H * P)), dtype)
    Bm = jnp.asarray(r.normal(size=(s, G * N)), dtype)
    Cm = jnp.asarray(r.normal(size=(s, G * N)), dtype)
    dt = jax.nn.softplus(jnp.asarray(r.normal(size=(s, H)) - 2.0,
                                     jnp.float32))
    A = jnp.asarray(np.exp(r.uniform(0.0, 2.8, size=(H,))), jnp.float32)
    return x, Bm, Cm, dt, A


@pytest.mark.parametrize("s,true_len", [(40, 40), (40, 37), (40, 17),
                                        (40, 5), (64, 64), (16, 9)])
def test_chunk_kernel_matches_the_recurrence_and_stops_at_true_len(
        s, true_len):
    x, Bm, Cm, dt, A = _case(s, seed=s + true_len)
    want_y, want_s = ssd.ssd_recurrence(
        x[:true_len], Bm[:true_len], Cm[:true_len], dt[:true_len], A, G)
    for fn in (ssd.ssd_prefill_kernel, ssd.ssd_prefill):
        y, state = fn(x, Bm, Cm, dt, A, true_len, G, C)
        np.testing.assert_allclose(y[:true_len], want_y, atol=5e-6)
        # the state is the one at true_len, not at the bucket's end
        np.testing.assert_allclose(state, want_s, atol=5e-6)
    # past the last chunk that holds the prompt the kernel writes zeros
    y, _ = ssd.ssd_prefill_kernel(x, Bm, Cm, dt, A, true_len, G, C)
    assert not np.asarray(y[-(-true_len // C) * C:]).any()


def test_chunk_kernel_takes_decays_that_underflow_within_a_chunk():
    """A head that forgets everything inside a chunk (exp(L) underflows):
    the differences are taken before the exponential, so nothing is a
    quotient of two zeros."""
    x, Bm, Cm, dt, A = _case(32, seed=5)
    A = A.at[0].set(400.0)
    dt = dt.at[:, 0].set(1.0)           # 8 positions: exp(-3200) = 0
    want_y, want_s = ssd.ssd_recurrence(x, Bm, Cm, dt, A, G)
    for fn in (ssd.ssd_prefill_kernel, ssd.ssd_prefill):
        y, state = fn(x, Bm, Cm, dt, A, 32, G, C)
        assert np.isfinite(np.asarray(y)).all()
        np.testing.assert_allclose(y, want_y, atol=5e-6)
        np.testing.assert_allclose(state, want_s, atol=5e-6)


def test_the_plain_chunked_form_is_differentiable_and_carries_a_state():
    x, Bm, Cm, dt, A = _case(32, seed=1)
    _, mid = ssd.ssd_chunked(x[:16], Bm[:16], Cm[:16], dt[:16], A, G,
                             chunk=C)
    y2, end = ssd.ssd_chunked(x[16:], Bm[16:], Cm[16:], dt[16:], A, G,
                              state=mid, chunk=C)
    want_y, want_s = ssd.ssd_recurrence(x, Bm, Cm, dt, A, G)
    np.testing.assert_allclose(y2, want_y[16:], atol=5e-6)
    np.testing.assert_allclose(end, want_s, atol=5e-6)
    grad = jax.grad(lambda x_: ssd.ssd_chunked(
        x_, Bm, Cm, dt, A, G, chunk=C)[0].sum())(x)
    assert np.isfinite(np.asarray(grad)).all() and np.asarray(grad).any()
    with pytest.raises(ValueError, match="whole chunks"):
        ssd.ssd_chunked(x[:30], Bm[:30], Cm[:30], dt[:30], A, G, chunk=C)


@pytest.mark.parametrize("true_len", [24, 19, 3])
def test_a_prefills_state_handed_to_the_step_is_the_scan_carried_on(
        true_len):
    """Prefill to `true_len` inside a bucket of 24, the state into a slot,
    then 6 decode steps of one lane: outputs and state equal the
    recurrence run over all `true_len + 6` positions."""
    x, Bm, Cm, dt, A = _case(true_len + 6, seed=true_len)
    pad = ((0, 24 - true_len), (0, 0))
    _, state = ssd.ssd_prefill_kernel(
        *(jnp.pad(a[:true_len], pad) for a in (x, Bm, Cm, dt)), A, true_len,
        G, C)
    want_y, want_s = ssd.ssd_recurrence(x, Bm, Cm, dt, A, G)
    for step in (ssd.ssd_step_kernel, ssd.ssd_step_reference):
        pool = jnp.zeros((1, 4, N, H * P), jnp.float32).at[0, 2].set(state)
        for t in range(true_len, true_len + 6):
            y, pool = step(x[t][None], Bm[t][None], Cm[t][None], dt[t][None],
                           A, pool, 0, jnp.asarray([2], jnp.int32), G)
            np.testing.assert_allclose(y[0], want_y[t], atol=5e-6)
        np.testing.assert_allclose(pool[0, 2], want_s, atol=5e-6)


def test_step_kernel_writes_active_slots_only():
    x, Bm, Cm, dt, A = _case(3, seed=2)
    pool = np.random.default_rng(3).normal(
        size=(2, 5, N, H * P)).astype(np.float32)
    slots = jnp.asarray([2, -1, 0], jnp.int32)
    for fn in (ssd.ssd_step_kernel, ssd.ssd_step_reference, ssd.ssd_step):
        y, new = fn(x, Bm, Cm, dt, A, jnp.asarray(pool), 1, slots, G)
        new = np.asarray(new)
        for lane, slot in ((0, 2), (2, 0)):
            want_y, want_s = ssd.ssd_recurrence(
                x[lane][None], Bm[lane][None], Cm[lane][None],
                dt[lane][None], A, G, state=jnp.asarray(pool[1, slot]))
            np.testing.assert_allclose(y[lane], want_y[0], atol=5e-6)
            np.testing.assert_allclose(new[1, slot], want_s, atol=5e-6)
        # the other layer, the slots of no lane and nobody's: bit for bit
        assert (new[0] == pool[0]).all()
        assert (new[1, [1, 3, 4]] == pool[1, [1, 3, 4]]).all()
    # a slot past the pool (the table's entry was not a fixed-class page)
    # is nobody's too
    _, new = ssd.ssd_step_kernel(x, Bm, Cm, dt, A, jnp.asarray(pool), 0,
                                 jnp.asarray([4, 9, -1], jnp.int32), G)
    assert (np.asarray(new) == pool).all()


def test_the_kernels_take_the_activations_in_bfloat16():
    x, Bm, Cm, dt, A = _case(24, seed=6, dtype=jnp.bfloat16)
    want_y, want_s = ssd.ssd_recurrence(x, Bm, Cm, dt, A, G)
    y, state = ssd.ssd_prefill_kernel(x, Bm, Cm, dt, A, 24, G, C)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    # the state is float32 whatever the inputs: exact products of bfloat16
    np.testing.assert_allclose(state, want_s, atol=2e-5)
    np.testing.assert_allclose(y.astype(jnp.float32), want_y, atol=0.06,
                               rtol=0.01)


def test_the_convolution_adds_its_bias_and_continues_from_its_tail():
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(12, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 6)), jnp.float32)
    b = jnp.asarray(r.normal(size=(6,)), jnp.float32)
    whole, _ = conv.causal_conv(x, w, bias=b)
    padded = jnp.pad(x, ((3, 0), (0, 0)))
    np.testing.assert_allclose(
        whole, jax.nn.silu(b + sum(w[i] * padded[i:i + 12]
                                   for i in range(4))), atol=1e-6)
    _, tail = conv.causal_conv(x, w, 9, b)        # a bucket of 12, 9 real
    y, new_tail = conv.conv_step(x[9][None], tail[None], w, b)
    np.testing.assert_allclose(y[0], whole[9], atol=1e-6)
    np.testing.assert_array_equal(new_tail[0], x[7:10])
    # without a bias both are what they were
    np.testing.assert_array_equal(conv.causal_conv(x, w)[0],
                                  conv.causal_conv(x, w, bias=None)[0])
    assert (np.asarray(conv.causal_conv(x, w)[0]) != np.asarray(whole)).any()


def test_kernels_tile_the_published_shapes_and_say_where_they_run():
    # 128 heads of 64 in 8 groups, a state of 128, chunks of 128
    assert ssd.chunk_tiles(64, 128, 16, 128, 8)
    assert ssd.block_columns(64, 1024) == 128       # two heads a block
    assert ssd.block_columns(8, 16) == 16
    # two groups of 1,024 columns a grid step: 1 MiB of state
    assert ssd.step_columns(8192, 1024, 128) == 2048
    assert ssd.step_tiles(8192, 1024, 128)
    assert not ssd.step_tiles(H * P, H * P // G, N)
    assert not ssd.chunk_tiles(P, N, H // G, C, G)
    assert not ssd.uses_step_kernel(8192, 1024, 128)    # this is a CPU
    with compute_platform("tpu"):
        assert ssd.uses_step_kernel(8192, 1024, 128)
        assert ssd.uses_chunk_kernel(64, 128, 16, 128, 8)
    with pytest.raises(ValueError, match="groups"):
        ssd.ssd_recurrence(*_case(4)[:3], jnp.ones((4, 3)), jnp.ones((3,)),
                           G)


# ------------------------------ a group of heads wider than a step's block
WIDE = dict(H=8, P=64)      # 2 groups of 256 columns; a block of 128


@pytest.fixture
def half_group_blocks(monkeypatch):
    """A step's block holds 128 columns of a state of 16: half a group of
    the `WIDE` case, two heads, as 1,024 of a group's 2,048 columns fit
    1 MiB at a state of 256."""
    monkeypatch.setattr(ssd, "STEP_BLOCK_BYTES", 128 * N * 4)
    assert ssd.step_columns(512, 256, N) == 128
    return 128


def test_step_kernel_finds_its_group_in_a_block_that_is_part_of_one(
        half_group_blocks):
    """Four blocks a lane, the first two in group 0 and the last two in
    group 1: every block's columns against the recurrence, so a block that
    spread another group's B and C over its columns would show; nobody's
    slot, an inactive lane's slot and the other layer are bit for bit what
    they were."""
    x, Bm, Cm, dt, A = _case(3, seed=7, **WIDE)
    pool = np.random.default_rng(8).normal(size=(2, 5, N, 512)).astype(
        np.float32)
    slots = jnp.asarray([3, -1, 1], jnp.int32)
    y, new = ssd.ssd_step_kernel(x, Bm, Cm, dt, A, jnp.asarray(pool), 0,
                                 slots, G)
    new = np.asarray(new)
    for lane, slot in ((0, 3), (2, 1)):
        want_y, want_s = ssd.ssd_recurrence(
            x[lane][None], Bm[lane][None], Cm[lane][None], dt[lane][None],
            A, G, state=jnp.asarray(pool[0, slot]))
        for block in range(4):          # the first and the last among them
            at = slice(block * half_group_blocks,
                       (block + 1) * half_group_blocks)
            np.testing.assert_allclose(y[lane, at], want_y[0, at],
                                       atol=5e-6)
            np.testing.assert_allclose(new[0, slot][:, at], want_s[:, at],
                                       atol=5e-6)
    assert not np.asarray(y[1]).any()
    assert (new[1] == pool[1]).all()
    assert (new[0, [0, 2, 4]] == pool[0, [0, 2, 4]]).all()
    # B and C of the two groups differ, so the groups' columns do
    swapped, _ = ssd.ssd_step_kernel(
        x, jnp.roll(Bm, N, axis=1), jnp.roll(Cm, N, axis=1), dt, A,
        jnp.asarray(pool), 0, slots, G)
    assert np.abs(np.asarray(swapped[0] - y[0])).min() > 0


@pytest.mark.parametrize("s,true_len", [(40, 40), (40, 21)])
def test_both_kernels_at_a_wide_group_carry_a_prompt_into_its_steps(
        half_group_blocks, s, true_len):
    """The chunk kernel over a group of four heads of 64 (two blocks of
    columns a group in its head loop) to `true_len`, its state into a
    slot, then the step kernel at half a group a block: the recurrence
    over all the positions."""
    x, Bm, Cm, dt, A = _case(s + 4, seed=s + true_len, **WIDE)
    want_y, want_s = ssd.ssd_recurrence(
        *(jnp.concatenate([a[:true_len], a[s:]])
          for a in (x, Bm, Cm, dt)), A, G)
    y, state = ssd.ssd_prefill_kernel(x[:s], Bm[:s], Cm[:s], dt[:s], A,
                                      true_len, G, C)
    np.testing.assert_allclose(y[:true_len], want_y[:true_len], atol=5e-6)
    pool = jnp.zeros((1, 3, N, 512), jnp.float32).at[0, 1].set(state)
    for t in range(4):
        yt, pool = ssd.ssd_step_kernel(
            x[s + t][None], Bm[s + t][None], Cm[s + t][None],
            dt[s + t][None], A, pool, 0, jnp.asarray([1], jnp.int32), G)
        np.testing.assert_allclose(yt[0], want_y[true_len + t], atol=5e-6)
    np.testing.assert_allclose(pool[0, 1], want_s, atol=5e-6)
    assert not np.asarray(pool[0, [0, 2]]).any()


def _whole_groups(width, group_cols, state):
    """`step_columns` as it stood while a block was whole groups."""
    groups = width // group_cols
    return max((n * group_cols for n in range(1, groups + 1)
                if groups % n == 0 and (n * group_cols) % ssd.LANES == 0
                and n * group_cols * state * 4 <= ssd.STEP_BLOCK_BYTES),
               default=0)


@pytest.mark.parametrize("width,group_cols,state,cols", [
    (8192, 1024, 128, 2048),    # two of eight groups a block, as before
    (8192, 1024, 64, 4096),
    (4096, 512, 128, 2048),
    (2048, 2048, 128, 2048),    # one group, whole
    (4096, 2048, 256, 1024),    # half a group: 8 heads of 128, 1 MiB
    (4096, 2048, 512, 512),
    (4096, 4096, 128, 2048),    # one group of two blocks
    (256, 128, 16, 256),
    (32, 16, 16, 0),            # no whole 128-lanes
])
def test_step_columns_keeps_whole_groups_and_cuts_a_group_that_is_too_wide(
        width, group_cols, state, cols):
    assert ssd.step_columns(width, group_cols, state) == cols
    assert cols == 0 or (width % cols == 0 and cols % ssd.LANES == 0
                         and cols * state * 4 <= ssd.STEP_BLOCK_BYTES)
    before = _whole_groups(width, group_cols, state)
    if before:          # where a block was whole groups it still is
        assert cols == before
    else:
        assert cols == 0 or group_cols % cols == 0
    assert ssd.step_tiles(width, group_cols, state) == bool(cols)
