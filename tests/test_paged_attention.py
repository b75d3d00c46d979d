"""The paged decode-attention kernel against the gather + einsum it
replaces, through the Pallas interpreter on a bf16 pool; the path
predicate; and that the engine's compiled decode and prefill programs
update the pool in place (no copy of the pool's shape, the pool aliased).
CPU, in-process. The same kernel compiled for a v5e is in
`test_kernel_names_aot.py`; on the chip, in `chip_smoke.py`.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models.config import tiny
from ray_tpu.models.transformer import Transformer
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.dispatch import compute_platform
from ray_tpu.serve.llm.engine import EngineCore

PAGE, HD, MAX_PAGES = 16, 128, 20
FULL = PAGE * MAX_PAGES


def _case(lengths, kvh=2, group=2, seed=0, layers=2, holes=()):
    """Random bf16 pools and queries; each lane's table lists pages drawn
    without order from a pool larger than all tables, -1 past the lane's
    pages and at `holes` (lane, table index)."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    pages = B * MAX_PAGES + 7
    shape = (layers, pages, PAGE, kvh * HD)
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, kvh * group, HD)), jnp.bfloat16)
    # page 0 is in no table: it is what a clamped -1 would name
    pt = (1 + rng.permutation(pages - 1))[:B * MAX_PAGES].reshape(
        B, MAX_PAGES).astype(np.int32)
    for b, n in enumerate(lengths):
        pt[b, -(-n // PAGE):] = -1
    for b, i in holes:
        pt[b, i] = -1
    return q, k, v, jnp.asarray(pt), jnp.asarray(lengths, jnp.int32)


def _close(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    # bf16 outputs of unit-variance values: a rounding step or two
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("lengths", [
    [1], [PAGE], [PAGE + 1], [FULL],
    [1, PAGE, PAGE + 1, FULL],                  # ragged lanes
    [8 * PAGE, 8 * PAGE + 1, 16 * PAGE, 3],     # the kernel block's edges
    [0, 45, 0, 129],                            # inactive lanes
    [0, 0],
], ids=lambda v: "-".join(map(str, v)))
def test_kernel_matches_einsum(lengths):
    q, k, v, pt, ln = _case(lengths)
    for layer in (0, 1):
        want = pa.paged_attention_reference(q, k, v, layer, pt, ln)
        got = pa.paged_decode_attention_kernel(q, k, v, layer, pt, ln)
        _close(got, want)
    idle = np.asarray(ln) == 0
    assert not np.asarray(got, np.float32)[idle].any()


@pytest.mark.parametrize("kvh,group", [(4, 1), (2, 2), (1, 4), (8, 2)])
def test_kernel_groups_query_heads(kvh, group):
    q, k, v, pt, ln = _case([37, 200, FULL], kvh=kvh, group=group, seed=1)
    _close(pa.paged_decode_attention_kernel(q, k, v, 1, pt, ln),
           pa.paged_attention_reference(q, k, v, 1, pt, ln))


def test_kernel_skips_unassigned_entries():
    """A -1 inside a lane's pages is not read and not seen, whatever the
    page the clamped index would have named holds."""
    q, k, v, pt, ln = _case([100, FULL, 40], seed=2,
                            holes=[(0, 2), (1, 0), (1, 9), (2, 2)])
    want = pa.paged_attention_reference(q, k, v, 0, pt, ln)
    got = pa.paged_decode_attention_kernel(q, k, v, 0, pt, ln)
    _close(got, want)
    # page 0 is what a clamped -1 names: poison it, nothing may change
    poisoned = k.at[:, 0].set(jnp.nan), v.at[:, 0].set(jnp.nan)
    assert 0 not in np.asarray(pt)
    again = pa.paged_decode_attention_kernel(q, *poisoned, 0, pt, ln)
    np.testing.assert_array_equal(np.asarray(again, np.float32),
                                  np.asarray(got, np.float32))


def test_kernel_reads_pages_in_table_order():
    """The same keys under another placement of the pages give the same
    output: the table, not the pool's order, says where a position is."""
    q, k, v, pt, ln = _case([150, 60], seed=3)
    perm = np.random.default_rng(4).permutation(k.shape[1])
    inv = np.argsort(perm).astype(np.int32)
    moved = jnp.where(pt >= 0, jnp.asarray(inv)[jnp.maximum(pt, 0)], -1)
    got = pa.paged_decode_attention_kernel(q, k, v, 1, pt, ln)
    again = pa.paged_decode_attention_kernel(
        q, k[:, perm], v[:, perm], 1, moved.astype(jnp.int32), ln)
    np.testing.assert_array_equal(np.asarray(again, np.float32),
                                  np.asarray(got, np.float32))


@pytest.mark.parametrize("hd,page,dtype,tiles", [
    (128, 16, jnp.bfloat16, True), (128, 32, jnp.bfloat16, True),
    (256, 16, jnp.bfloat16, True), (128, 8, jnp.float32, True),
    (128, 8, jnp.bfloat16, False),      # half a bf16 tile a page
    (16, 16, jnp.bfloat16, False),      # the tiny model's heads
    (64, 16, jnp.bfloat16, False),
])
def test_path_predicate(hd, page, dtype, tiles):
    assert pa.paged_decode_tiles(hd, page, dtype) is tiles
    # off a TPU the einsum runs whatever the shapes; for one, the shapes say
    with compute_platform("cpu"):
        assert not pa.uses_kernel(hd, page, dtype)
    with compute_platform("tpu"):
        assert pa.uses_kernel(hd, page, dtype) is tiles


def test_kernel_refuses_shapes_it_does_not_tile():
    q = jnp.zeros((1, 2, 16), jnp.bfloat16)
    pool = jnp.zeros((1, 4, 8, 32), jnp.bfloat16)
    with pytest.raises(ValueError, match="does not tile"):
        pa.paged_decode_attention_kernel(
            q, pool, pool, 0, jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32))


# ------------------------------------------------ the pool, in place
@pytest.fixture(scope="module")
def core():
    cfg = tiny()
    params = Transformer(cfg).init(jax.random.PRNGKey(0))
    return EngineCore(cfg, params, num_pages=32, page_size=8, max_batch=2)


def _compiled(core, which):
    B, P = core.max_batch, core.max_pages_per_seq
    if which == "step":
        lowered = core._decode_fn.lower(
            core.params, core._cache, jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B, P), jnp.int32),
            jnp.zeros((B,), bool))
    else:
        lowered = core._prefill_fn(16).lower(
            core.params, jnp.zeros((16,), jnp.int32), jnp.int32(3),
            jnp.zeros((P,), jnp.int32), core._cache)
    return lowered.compile()


@pytest.mark.parametrize("which", ["step", "pre"])
def test_compiled_program_updates_the_pool_in_place(core, which):
    compiled = _compiled(core, which)
    pool = core._cache["k"]
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= 2 * pool.nbytes)
    shape = ",".join(map(str, pool.shape))
    made = re.findall(
        rf"= \w+\[{shape}\]\S* (\w[\w\-]*)\(", compiled.as_text())
    assert made and "copy" not in made
    # nothing of the pool's shape but the scatters into it (a fusion
    # that holds one, on this backend)
    assert set(made) <= {"scatter", "fusion", "parameter",
                         "get-tuple-element", "bitcast"}, made


def test_a_caller_that_keeps_the_old_cache_holds_nothing(core):
    old = core._cache
    core.submit([1, 2, 3], max_tokens=2, rid="x")
    while core.has_work:
        core.step()
    assert old["k"].is_deleted() and old["v"].is_deleted()
    assert not core._cache["k"].is_deleted()


def test_kernel_on_a_mesh_splits_kv_heads_over_tp():
    """On more than one device the call is shard-mapped, kv heads over
    `tp` as `models.decode.cache_sharding` lays the pool."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.parallel.mesh import MeshSpec
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = MeshSpec(dp=1, tp=2).build(jax.devices()[:2])
    q, k, v, pt, ln = _case([70, 0, 200], kvh=4, group=2, seed=5)
    want = pa.paged_attention_reference(q, k, v, 1, pt, ln)
    on_mesh = NamedSharding(mesh, P(None, None, None, "tp"))
    k, v = jax.device_put(k, on_mesh), jax.device_put(v, on_mesh)
    got = jax.jit(lambda *a: pa.paged_decode_attention_kernel(
        *a, mesh=mesh))(q, k, v, jnp.int32(1), pt, ln)
    _close(got, want)
