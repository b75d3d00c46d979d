"""The Pallas kernels compiled ahead of time for a v5e, with no chip
attached: each must carry its own name as the name of its custom-call
instruction, which is what an `XLA Ops` event of a profiler trace is
called and what `benchmarks/metrics/kernel.flash_*_roofline.train.py`
and the ledger's `device_ops` find it by. Also a guard that the kernels
still compile for the chip at a real head size, and that the decode
step compiled for the chip holds the paged kernel, updates the pool in
place and reads its weights where they lie.

The topology is described inside a module-scoped fixture (one process at
a time may load libtpu: nothing here touches it at import time), and all
such compiles live in this one file.
"""
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import regions
from ray_tpu.ops import attention, norms, paged_attention
from ray_tpu.ops.dispatch import compute_platform


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compiled_text(topo, no_compile_cache):
    """One train-like program: a rematted layer of saveable flash
    attention (GQA, 8 heads over 4 of 128) and an rms_norm, forward and
    backward, so that every training kernel is in it once or more."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v, x, w):
        def layer(q, k, v, x):
            # each kernel in its caller's region, as the models call them:
            # a transform wraps the first scope under it, and a kernel
            # called bare would take the wrapping (`jvp_flash_fwd_`)
            with regions.region(regions.ATTN_CORE):
                a = attention.flash_attention_saveable(q, k, v, causal=True)
            with regions.region(regions.NORM):
                n = norms.rms_norm(x, w)
            return a.astype(jnp.float32).sum() * n
        y = jax.checkpoint(layer)(q, k, v, x)
        return y.astype(jnp.float32).sum()

    with compute_platform("tpu"):
        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))
        return step.trace(shape(1, 8, 512, 128), shape(1, 4, 512, 128),
                          shape(1, 4, 512, 128), shape(512, 1024),
                          shape(1024)).lower().compile().as_text()


def kernel_names(text: str):
    """Instruction names of the program's Mosaic custom calls, numbering
    dropped: `%flash_fwd.3 = ... custom_call_target="tpu_custom_call"`."""
    return [re.sub(r"[.\d]+$", "", m.group(1)) for m in re.finditer(
        r"^\s*%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text, re.M)]


@pytest.mark.parametrize("name", [
    attention.KERNEL_FWD, attention.KERNEL_BWD_DKDV, norms.KERNEL_RMS_FWD])
def test_kernel_is_named_where_the_trace_shows_it(compiled_text, name):
    assert name in kernel_names(compiled_text)


def test_no_kernel_is_named_after_its_enclosing_call(compiled_text):
    names = kernel_names(compiled_text)
    # one backward kernel: no `flash_bwd_dq` beside `flash_bwd_dkdv`
    assert set(names) == {"flash_fwd", "flash_bwd_dkdv", "rms_norm_fwd"}
    assert names.count(attention.KERNEL_BWD_DKDV) == 1
    # the benchmark's label for such an event is 'kernel:<name>'
    assert not {"closed_call", "checkpoint", "rematted_computation"} \
        & set(names)


def test_flash_kernels_first_result_is_rank_4(compiled_text):
    """`benchmarks/metrics/kernel.flash_roofline.train.py` finds the flash
    kernels as the Mosaic calls whose first result is a (batch, heads, seq,
    head_dim)-shaped bf16 or f32 array: the backward's is dK, before dV and
    the head's dQ block by block (rank 5)."""
    first = {name: shape for name, shape in re.findall(
        r"^\s*%([a-z_]+)[.\d]* = \(?(?:bf16|f32)\[([\d,]+)\][^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", compiled_text, re.M)}
    assert first[attention.KERNEL_FWD] == "1,8,512,128"
    assert first[attention.KERNEL_BWD_DKDV] == "1,8,512,128"


# ------------------------------------------------- the scanned trunk
@pytest.fixture(scope="module")
def trunk_text(topo, no_compile_cache):
    """`Transformer`'s scan over rematted layers, loss and gradient,
    compiled for one chip under a `remat_policy` (None: `remat=True` and
    no policy named); one compile a policy."""
    from ray_tpu.models import Transformer, TransformerConfig
    one_chip = SingleDeviceSharding(topo.devices[0])
    texts = {}

    def compiled(policy):
        if policy in texts:
            return texts[policy]
        over = {} if policy is None else {"remat_policy": policy}
        model = Transformer(TransformerConfig(
            vocab_size=512, d_model=512, n_layers=4, n_heads=4,
            n_kv_heads=2, d_ff=1024, max_seq_len=512, dtype="bfloat16",
            param_dtype="bfloat16", remat=True, **over))
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        batch = {"tokens": jax.ShapeDtypeStruct((2, 512), jnp.int32,
                                                sharding=one_chip)}
        with compute_platform("tpu"):
            texts[policy] = jax.jit(jax.value_and_grad(model.loss)).trace(
                params, batch).lower().compile().as_text()
        return texts[policy]
    return compiled


def matmuls(text: str) -> int:
    """The program's matmuls: on the chip a dot is a `convolution`."""
    return len(re.findall(r"= [^\n]* convolution\(", text))


@pytest.mark.parametrize("policy,flash_forwards,matmuls_spared", [
    (None, 1, 6), ("save_matmuls", 1, 6), ("save_attn_stream_up", 1, 5),
    ("save_attn_stream", 1, 4), ("save_attn_qkv", 1, 3),
    ("save_attn", 1, 0), ("full", 2, 0)])
def test_rematted_trunk_runs_the_flash_forward_once(
        trunk_text, policy, flash_forwards, matmuls_spared):
    """The forward's while body holds one `flash_fwd`, and the backward's
    holds another only where the policy keeps nothing ("full"). Against
    "full", whose backward runs the layer's six matmuls before `down`
    again, a rung that keeps q, k and v spares three, and each rung above
    one more: the output projection, `up`, gate; nothing else of the
    program holds a matmul that a policy moves. Under `remat=True` alone
    (`policy` None) the program is the top rung's at these shapes."""
    text = trunk_text(policy)
    names = kernel_names(text)
    assert names.count(attention.KERNEL_FWD) == flash_forwards
    assert names.count(attention.KERNEL_BWD_DKDV) == 1
    assert "flash_bwd_dq" not in names
    assert matmuls(trunk_text("full")) - matmuls(text) == matmuls_spared
    if policy is None:
        # every instruction, less what holds this file's lines: the call
        # stacks and the kernels' payloads
        def instructions(t):
            return re.sub(r",? (metadata|backend_config)=[^\n]*", "",
                          t[t.index("\n\n", t.index("\nStackFrames\n")):])
        assert instructions(text) == instructions(trunk_text("save_matmuls"))


# ------------------------------------------------------ the decode step
# a pool of the cells' 2048 pages: a small one the compiler would move
# into fast memory whole, which no deployment's fits
LANES, PAGE, PAGES = 8, 16, 2048
# the dense serving cells' widths; six of their 24 layers compile in 2 s
LAYERS, D_MODEL, HEADS, KV_HEADS, D_FF = 6, 2048, 16, 8, 8192


def _compile_decode_step(devices, tp: int):
    """The decode step as `EngineCore` jits it (the cache donated, on a
    mesh handed back as it lay): six layers at the serving cells' widths
    (2048, 16 heads over 8 kv heads of 128, d_ff 8192, 16-token bf16
    pages, 8 lanes), on one chip or over `tp` of them. Returns
    (compiled, the pool's shape on a device)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.models import Transformer, TransformerConfig, decode
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.parallel.sharding import param_shardings
    cfg = TransformerConfig(
        vocab_size=512, d_model=D_MODEL, n_layers=LAYERS, n_heads=HEADS,
        n_kv_heads=KV_HEADS, d_ff=D_FF, max_seq_len=512, remat=False,
        dtype="bfloat16", param_dtype="bfloat16")
    pool = (cfg.n_layers, PAGES, PAGE, cfg.kv_heads * cfg.head_dim)
    abstract = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    if tp == 1:
        model, out = Transformer(cfg), None
        whole = pooled = SingleDeviceSharding(devices[0])
        placed = jax.tree.map(lambda a: whole, abstract)
    else:
        mesh = MeshSpec(dp=1, tp=tp).build(list(devices)[:tp])
        model = Transformer(cfg, mesh=mesh)
        whole = NamedSharding(mesh, P())
        pooled = decode.cache_sharding(cfg, mesh)
        placed = param_shardings(mesh, model.param_logical_axes())
        out = (None, {"k": pooled, "v": pooled})

    def shape(dtype, *dims, sharding=whole):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    params = jax.tree.map(
        lambda a, s: shape(a.dtype, *a.shape, sharding=s), abstract, placed)
    cache = {name: shape(jnp.bfloat16, *pool, sharding=pooled)
             for name in "kv"}

    def _step(params, cache, tokens, positions, pts, active):
        return decode.decode_step(model, params, cache, tokens, positions,
                                  pts, active, PAGE)

    with compute_platform("tpu"):
        assert decode.decode_attention(cfg, PAGE) == "paged_decode_attn"
        compiled = jax.jit(
            _step, donate_argnums=(1,), out_shardings=out).trace(
            params, cache, shape(jnp.int32, LANES),
            shape(jnp.int32, LANES),
            shape(jnp.int32, LANES, cfg.max_seq_len // PAGE),
            shape(jnp.bool_, LANES)).lower().compile()
    return compiled, pool[:3] + (pool[3] // tp,)


@pytest.fixture(scope="module", params=[1, 4], ids=["one-chip", "tp4"])
def decode_step(request, topo, no_compile_cache):
    return _compile_decode_step(topo.devices, request.param)


def results(text: str):
    """(name, opcode or custom-call target, shapes of its result with
    unit dimensions dropped) of each instruction of the program's entry
    computation; a tuple's result is every array in it."""
    entry = text[text.index("\nENTRY "):]
    for name, result, op, rest in re.findall(
            r"^\s+(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$",
            entry, re.M):
        target = re.search(r'custom_call_target="(\w+)"', rest)
        shapes = [
            tuple(int(d) for d in dims.split(",") if d not in ("", "1"))
            for dims in re.findall(r"\w+\[([\d,]*)\]", result)]
        yield name, target.group(1) if target else op, shapes


def test_decode_step_holds_the_paged_kernel(decode_step):
    compiled, _ = decode_step
    names = kernel_names(compiled.as_text())
    # one call a layer (on a mesh: of each device's own kv heads), named
    # for the trace's `kernel:paged_decode_attn`
    assert names.count(paged_attention.KERNEL_PAGED_DECODE) == LAYERS


def test_decode_step_for_the_chip_updates_the_pool_in_place(decode_step):
    compiled, pool = decode_step
    nbytes = 2 * 2 * pool[0] * pool[1] * pool[2] * pool[3]   # k and v
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
    shape = ",".join(map(str, pool))
    made = re.findall(rf"= \w+\[{shape}\]\S* ([\w\-]+)\(",
                      compiled.as_text())
    # no copy of the pool, no slice of it made for the kernel, no gather
    # of its shards: the scatters of the writes (each layer's k and v),
    # in fusions or bare
    assert made and set(made) <= {"parameter", "scatter", "fusion",
                                  "bitcast", "get-tuple-element"}, made


def test_decode_step_reads_wq_and_wk_where_they_lie(decode_step):
    """No instruction writes a layer's wq or wk out anew. With the view
    into heads folded into the dot (`(h @ wq).reshape(b, 1, heads, hd)`)
    the compiler wants each as `(heads, hd, d_model)`: a
    `slice_bitcast_fusion` transposes every layer's, `slice-start` /
    `slice-done` and `ConcatBitcast` bring the copies into fast memory
    and a `copy` turns them once more, a quarter of the step's bytes
    (PERF.md, PR 34). `decode_step` keeps the products flat past a
    barrier, and the dots read the stacked weights in place."""
    compiled, pool = decode_step
    tp = KV_HEADS * 128 // pool[3]
    a_layers = set()
    for heads in (HEADS // tp, KV_HEADS // tp):     # flat or by heads
        a_layers |= {(D_MODEL, heads * 128), (heads * 128, D_MODEL),
                     (D_MODEL, heads, 128), (heads, 128, D_MODEL)}
    relaid = [
        (name, op) for name, op, shapes in results(compiled.as_text())
        if a_layers & set(shapes) and (
            op in ("copy", "ConcatBitcast") or op.startswith("slice")
            or name.startswith("slice_bitcast_fusion"))]
    assert not relaid, relaid
    if tp == 1:     # nor room kept for such copies (62 MB with them)
        assert (compiled.memory_analysis().temp_size_in_bytes
                < 2 * D_MODEL * HEADS * 128)


def test_dense_prefill_for_the_chip_walks_blocks_of_1024(
        topo, no_compile_cache):
    """The dense class's 2048-token prefill at the serving cells' widths
    (16 heads over 8 kv heads of 128): its flash forward at
    `gqa.FULL_BLOCKS`, 2 x 2 blocks a head, is a kernel the chip's
    compiler takes, once in the scanned layer, and the pools are written
    in place."""
    from ray_tpu.models import Transformer, TransformerConfig
    model = Transformer(TransformerConfig(
        vocab_size=512, d_model=D_MODEL, n_layers=2, n_heads=HEADS,
        n_kv_heads=KV_HEADS, d_ff=D_FF, max_seq_len=2048, remat=False,
        dtype="bfloat16", param_dtype="bfloat16"))
    compiled, cache = _compile_served(
        topo.devices, model, "prefill",
        lambda: model.init_cache(PAGES, PAGE), "paged_decode_attn")
    assert kernel_names(compiled.as_text()).count(attention.KERNEL_FWD) == 1
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= _held_bytes(cache))


# ------------------------------------- the second architecture's step
def _compile_served(devices, model, which: str, make_cache, kernels: str,
                    run: int = 1):
    """The decode step (32 lanes) or the prefill of a whole
    `max_seq_len` of a model with its own programs, as `EngineCore` jits
    them, for one chip, 16-token bf16 pages, the tables as wide as the
    class says (`table_pages`: its fixed entries and whole runs of `run`,
    the class's answer at these shapes). Returns (compiled, cache)."""
    one = SingleDeviceSharding(devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def ints(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(make_cache))
    lanes, seq = 32, model.config.max_seq_len
    with compute_platform("tpu"):
        assert model.decode_attention(PAGE) == kernels
        assert model.page_run(PAGE, seq // PAGE) == run
        fixed = model.fixed_pages(PAGE)
        table = model.table_pages(PAGE, seq // PAGE)
        assert table == (seq // PAGE if run == 1 or not fixed else
                         fixed + -(-(seq // PAGE - fixed) // run) * run)
        assert model.page_run(PAGE, table) == run
        if which == "step":
            def _step(params, cache, tokens, positions, pts, active):
                return model.decode_step(params, cache, tokens, positions,
                                         pts, active, PAGE)
            traced = jax.jit(_step, donate_argnums=(1,)).trace(
                params, cache, ints(lanes), ints(lanes),
                ints(lanes, table), jax.ShapeDtypeStruct(
                    (lanes,), jnp.bool_, sharding=one))
        else:
            def _pre(params, tokens, true_len, page_table, cache):
                return model.prefill(params, tokens, true_len, page_table,
                                     cache, PAGE)
            traced = jax.jit(_pre, donate_argnums=(4,)).trace(
                params, ints(seq), ints(), ints(table), cache)
        return traced.lower().compile(), cache


def _held_bytes(cache) -> int:
    """Bytes of a cache that a program must update in place: every leaf
    but `"moe_step"`'s (`paged.ExpertCounts`: the last step's counts, five
    int32 scalars that each program makes anew and aliases to nothing;
    until PR 51 the padding of the tail pool's 33 slots to 48 in the
    compiler's layout had stood in for their 20 bytes in these sums)."""
    counts = jax.tree.leaves(cache.get("moe_step", {}))
    assert all(a.shape == () and a.dtype == jnp.int32 for a in counts)
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(cache)) - 4 * len(counts)


def _compile_mla_moe(devices, which: str):
    """`MLAMoE`'s decode step or 2048-token prefill (the batch32 cell's
    largest bucket: 8192 pairs through `moe_gmm`): the dense layer and one
    expert layer at the published widths of GLM-4.7-Flash (20 heads,
    latent 512 + 64, 64 experts of 2048 x 1536)."""
    from ray_tpu.models.mla_moe import MLAMoE, MLAMoEConfig
    model = MLAMoE(MLAMoEConfig(vocab_size=1024, n_layers=2,
                                max_seq_len=2048))
    with compute_platform("tpu"):       # the step's walk: 4 pages a copy
        assert model.page_run(PAGE, 2048 // PAGE) == 4
    compiled, cache = _compile_served(
        devices, model, which, lambda: model.init_cache(PAGES, PAGE),
        "mla_paged_decode_attn", run=4)
    return compiled, cache["kv"].shape


@pytest.fixture(scope="module", params=["step", "prefill"])
def mla_moe_program(request, topo, no_compile_cache):
    return (request.param,) + _compile_mla_moe(topo.devices, request.param)


def test_mla_moe_programs_hold_their_kernels_by_name(mla_moe_program):
    from ray_tpu.ops import grouped_matmul
    which, compiled, _ = mla_moe_program
    names = kernel_names(compiled.as_text())
    # gate, up and down of the one expert layer: each a whole matrix a
    # block (12.6 MB of two in flight), which the compiler gave room
    assert names.count(grouped_matmul.KERNEL_GMM) == 3
    if which == "step":     # one latent kernel a layer, no flash kernel
        assert names.count(paged_attention.KERNEL_MLA_PAGED_DECODE) == 2
        assert attention.KERNEL_FWD not in names
    else:                   # the expanded form at d = 256, a layer
        assert names.count(attention.KERNEL_FWD) == 2
        assert paged_attention.KERNEL_MLA_PAGED_DECODE not in names


def test_mla_moe_programs_update_the_latent_pool_in_place(mla_moe_program):
    _, compiled, pool = mla_moe_program
    assert pool == (2, PAGES, PAGE, 640)
    nbytes = 2 * pool[0] * pool[1] * pool[2] * pool[3]
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
    shape = ",".join(map(str, pool))
    made = re.findall(rf"= \w+\[{shape}\]\S* ([\w\-]+)\(",
                      compiled.as_text())
    assert made and set(made) <= {"parameter", "scatter", "fusion",
                                  "bitcast", "get-tuple-element"}, made


# -------------------------------------- the third architecture's step
def _compile_gqa_window_moe(devices, which: str):
    """`GQAWindowMoE`'s decode step or 8192-token prefill (the mixed8k
    cell's largest bucket: 65,536 pairs through `moe_gmm`): a sliding and
    a full layer, both sparse, at the published widths of Laguna-XS.2 (64
    and 48 heads over 8 kv heads of 128, 256 experts of 2048 x 512),
    rings of 33 pages a lane."""
    from ray_tpu.models.gqa_window_moe import (FULL, SLIDING, SPARSE,
                                               GQAWindowMoE,
                                               GQAWindowMoEConfig)
    model = GQAWindowMoE(GQAWindowMoEConfig(
        vocab_size=1024, layer_types=(SLIDING, FULL),
        n_heads_per_layer=(64, 48), mlp_layer_types=(SPARSE, SPARSE)))
    return _compile_served(
        devices, model, which, lambda: model.init_cache(
            PAGES, PAGE, fixed_pages=32 * model.fixed_pages(PAGE)),
        "paged_decode_attn+paged_window_decode_attn")[0]


@pytest.mark.parametrize("which", ["step", "prefill"])
def test_gqa_window_moe_programs_hold_their_kernels_by_name(
        which, topo, no_compile_cache):
    from ray_tpu.ops import grouped_matmul
    names = kernel_names(
        _compile_gqa_window_moe(topo.devices, which).as_text())
    # gate, up and down of both layers' experts, a whole matrix a block
    assert names.count(grouped_matmul.KERNEL_GMM) == 6
    if which == "step":
        assert names.count(paged_attention.KERNEL_PAGED_DECODE) == 1
        assert names.count(paged_attention.KERNEL_PAGED_WINDOW_DECODE) == 1
    else:
        assert names.count(attention.KERNEL_FWD) == 1
        assert names.count(attention.KERNEL_WINDOW_FWD) == 1


# ------------------------------------- the fourth architecture's step
def _compile_hybrid_delta(devices, which: str, slots: int = 32):
    """`HybridDelta`'s decode step or 3072-token prefill (the answers3k
    cell's context limit): a linear and a full layer at the published
    widths of Olmo-Hybrid-7B (30 heads of 96 / 192 and a state of 96 x
    5760 a lane; 30 query heads over 30 kv heads of 128: a group of one
    in the page walk), `slots` state slots and nobody's."""
    from ray_tpu.models.hybrid_delta import (FULL, LINEAR, HybridDelta,
                                             HybridDeltaConfig)
    model = HybridDelta(HybridDeltaConfig(
        vocab_size=1024, layer_types=(LINEAR, FULL)))
    return _compile_served(
        devices, model, which, lambda: model.init_cache(
            PAGES, PAGE, fixed_pages=slots * model.fixed_pages(PAGE)),
        "paged_decode_attn+gated_delta_step")


@pytest.mark.parametrize("which", ["step", "prefill"])
def test_hybrid_delta_programs_hold_their_kernels_and_alias_the_state(
        which, topo, no_compile_cache):
    from ray_tpu.ops import gated_delta
    compiled, cache = _compile_hybrid_delta(topo.devices, which)
    names = kernel_names(compiled.as_text())
    if which == "step":
        assert names.count(gated_delta.KERNEL_STEP) == 1
        assert names.count(paged_attention.KERNEL_PAGED_DECODE) == 1
        assert gated_delta.KERNEL_CHUNK not in names
    else:
        assert names.count(gated_delta.KERNEL_CHUNK) == 1
        assert names.count(attention.KERNEL_FWD) == 1
        assert gated_delta.KERNEL_STEP not in names
    # every pool is updated in place: the state (33 slots of 96 x 5760
    # float32), the tail, the keys and the values
    assert cache["state"].shape == (1, 33, 96, 5760)
    nbytes = sum(a.size * a.dtype.itemsize for a in cache.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes


# -------------------------------------- the fifth architecture's step
def _compile_shortcut_mla_moe(devices, which: str):
    """`ShortcutMLAMoE`'s decode step or 2048-token prefill (the reason4k
    cell's largest bucket): one double layer at the published widths of
    LongCat-Flash-Chat (64 heads, latent 512 + 64, keys 192 wide and values
    128, norms 6,144 wide), 16 of 512 experts of 6144 x 2048 held, the
    router 768 wide."""
    from ray_tpu.models.shortcut_mla_moe import (ShortcutMLAMoE,
                                                 ShortcutMLAMoEConfig)
    model = ShortcutMLAMoE(ShortcutMLAMoEConfig(
        vocab_size=1024, n_layers=1, experts_held=(0, 16),
        max_seq_len=2048))
    with compute_platform("tpu"):       # the step's walks: 4 pages a copy
        assert model.page_run(PAGE, 2048 // PAGE) == 4
    compiled, cache = _compile_served(
        devices, model, which, lambda: model.init_cache(PAGES, PAGE),
        "mla_paged_decode_attn", run=4)
    return compiled, cache["kv"].shape


@pytest.mark.parametrize("which", ["step", "prefill"])
def test_shortcut_mla_moe_programs_hold_their_kernels_by_name(
        which, topo, no_compile_cache):
    from ray_tpu.ops import grouped_matmul
    compiled, pool = _compile_shortcut_mla_moe(topo.devices, which)
    names = kernel_names(compiled.as_text())
    # gate, up and down of the held experts; two attentions a layer; a
    # norm before each attention and feed-forward, and the last
    assert names.count(grouped_matmul.KERNEL_GMM) == 3
    assert names.count(norms.KERNEL_RMS_FWD) == 5
    if which == "step":
        assert names.count(paged_attention.KERNEL_MLA_PAGED_DECODE) == 2
        assert attention.KERNEL_FWD not in names
    else:       # the expanded form, keys 192 wide and values 128
        assert names.count(attention.KERNEL_FWD) == 2
        assert paged_attention.KERNEL_MLA_PAGED_DECODE not in names
    # a layer owns two rows of the pool, both updated in place
    assert pool == (2, PAGES, PAGE, 640)
    nbytes = 2 * pool[0] * pool[1] * pool[2] * pool[3]
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes


# ------------------------------ the class with a learned sparse attention
@pytest.mark.parametrize("which", ["step", "prefill"])
def test_sparse_mla_moe_programs_hold_their_kernels_by_name(
        which, topo, no_compile_cache):
    """`SparseMLAMoE`'s decode step or 4096-token prefill (past
    `index_topk`: the sparse forms): the dense layer and one expert layer
    at the published widths of GLM-5 (64 heads, latent 512 + 64, 32 index
    heads of 128, 16 of 256 experts of 6144 x 2048). What the interpreter
    cannot refuse (tiling, fast memory) the chip's compiler does here."""
    from ray_tpu.models.sparse_mla_moe import (SparseMLAMoE,
                                               SparseMLAMoEConfig)
    from ray_tpu.ops import grouped_matmul, sparse_attention
    model = SparseMLAMoE(SparseMLAMoEConfig(
        vocab_size=1024, n_layers=2, first_k_dense_replace=1,
        experts_held=(0, 16), max_seq_len=4096))
    compiled, cache = _compile_served(
        topo.devices, model, which, lambda: model.init_cache(PAGES, PAGE),
        sparse_attention.KERNEL_PAGED_ATTEND, run=8)
    names = kernel_names(compiled.as_text())
    assert names.count(grouped_matmul.KERNEL_GMM) == 3
    assert paged_attention.KERNEL_MLA_PAGED_DECODE not in names
    assert attention.KERNEL_FWD not in names
    if which == "step":     # a walk over index pages, one over latent rows
        with compute_platform("tpu"):       # each a run of 8 pages a copy
            assert model.page_run(PAGE, 4096 // PAGE) == 8
        assert names.count(sparse_attention.KERNEL_PAGED_INDEX) == 2
        assert names.count(sparse_attention.KERNEL_PAGED_ATTEND) == 2
        assert sparse_attention.KERNEL_FLASH_FWD not in names
    else:                   # index scores a block of queries, then flash
        assert names.count(sparse_attention.KERNEL_INDEX_SCORES) == 2
        assert names.count(sparse_attention.KERNEL_FLASH_FWD) == 2
    # both pools under one page id, both updated in place
    assert cache["kv"].shape == (2, PAGES, PAGE, 640)
    assert cache["idx"].shape == (2, PAGES, PAGE, 128)
    nbytes = sum(2 * a.size for a in (cache["kv"], cache["idx"]))
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes


# ------------------------- the class of two latent geometries and a ring
@pytest.mark.parametrize("which", ["step", "prefill"])
def test_sparse_window_mla_moe_programs_hold_their_kernels_by_name(
        which, topo, no_compile_cache):
    """`SparseWindowMLAMoE`'s decode step or 4096-token prefill: a full
    expert layer and a sliding one at the published widths of
    dots3-note-prev (128 heads over a latent of 512 + 64 under 64 index
    heads of 128; 64 heads over a latent of 1024 + 64 in a ring of 513: rows
    of 1,152 with a value part of 1,024, 34 pages of 16; 32 of 256 experts
    of 5120 x 1536). The ring's kernel has its own name; the index-score
    tile at 64 heads and the masked flash forward at keys of 192 are shapes
    only this class asks for."""
    from ray_tpu.models.sparse_window_mla_moe import (
        FULL, SLIDING, SparseWindowMLAMoE, SparseWindowMLAMoEConfig)
    from ray_tpu.ops import grouped_matmul, sparse_attention
    model = SparseWindowMLAMoE(SparseWindowMLAMoEConfig(
        vocab_size=1024, n_layers=2, layer_types=(FULL, SLIDING),
        first_k_dense_replace=0, experts_held=(0, 32), max_seq_len=4096))
    ring = 32 * model.fixed_pages(PAGE)
    assert model.fixed_pages(PAGE) == 34
    compiled, cache = _compile_served(
        topo.devices, model, which,
        lambda: model.init_cache(PAGES, PAGE, fixed_pages=ring),
        sparse_attention.KERNEL_PAGED_ATTEND + "+"
        + paged_attention.KERNEL_MLA_PAGED_WINDOW_DECODE, run=8)
    names = kernel_names(compiled.as_text())
    assert names.count(grouped_matmul.KERNEL_GMM) == 6
    assert paged_attention.KERNEL_MLA_PAGED_DECODE not in names
    assert paged_attention.KERNEL_PAGED_WINDOW_DECODE not in names
    if which == "step":     # the ring's 34 entries, then runs of 8 (PR 66)
        with compute_platform("tpu"):
            assert model.page_run(PAGE, 4096 // PAGE) == 8
            assert model.table_pages(PAGE, 4096 // PAGE) == 34 + 224
            assert model.window_attention.page_run(PAGE, 256, 34) == 1
        assert names.count(sparse_attention.KERNEL_PAGED_INDEX) == 1
        assert names.count(sparse_attention.KERNEL_PAGED_ATTEND) == 1
        assert names.count(
            paged_attention.KERNEL_MLA_PAGED_WINDOW_DECODE) == 1
        assert attention.KERNEL_WINDOW_FWD not in names
    else:
        assert names.count(sparse_attention.KERNEL_INDEX_SCORES) == 1
        assert names.count(sparse_attention.KERNEL_FLASH_FWD) == 1
        assert names.count(attention.KERNEL_WINDOW_FWD) == 1
        assert paged_attention.KERNEL_MLA_PAGED_WINDOW_DECODE not in names
    # the ring beside the two pools under one page id, all updated in place
    assert cache["kv"].shape == (1, PAGES, PAGE, 640)
    assert cache["idx"].shape == (1, PAGES, PAGE, 128)
    assert cache["kv_w"].shape == (1, ring, PAGE, 1152)
    nbytes = sum(2 * cache[name].size for name in ("kv", "idx", "kv_w"))
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes


# -------------------------------------- the sixth architecture's step
def _compile_hybrid_ssm_moe(devices, which: str, slots: int = 32):
    """`HybridSSMMoE`'s decode step or 4096-token prefill: one layer of
    each kind at the published widths of Nemotron-3-Super (128 state-space
    heads of 64 in 8 groups and a state of 128 x 8192 a lane; 32 query
    heads over 2 kv heads of 128: a group of sixteen in the page walk; 128
    of 512 experts of 1024 x 2688 held behind a latent), `slots` state slots
    and nobody's."""
    from ray_tpu.models.hybrid_ssm_moe import (HybridSSMMoE,
                                               HybridSSMMoEConfig)
    model = HybridSSMMoE(HybridSSMMoEConfig(
        vocab_size=1024, layer_types="M*E", experts_held=(0, 128),
        max_seq_len=4096))
    return _compile_served(
        devices, model, which, lambda: model.init_cache(
            PAGES, PAGE, fixed_pages=slots * model.fixed_pages(PAGE)),
        "paged_decode_attn+ssd_step", run=8)     # 8 KB a pool's page


@pytest.mark.parametrize("which", ["step", "prefill"])
def test_hybrid_ssm_moe_programs_hold_their_kernels_and_alias_the_state(
        which, topo, no_compile_cache):
    from ray_tpu.ops import grouped_matmul, ssd
    compiled, cache = _compile_hybrid_ssm_moe(topo.devices, which)
    names = kernel_names(compiled.as_text())
    # up and down of the held experts: two matrices, no gate
    assert names.count(grouped_matmul.KERNEL_GMM) == 2
    if which == "step":
        assert names.count(ssd.KERNEL_STEP) == 1
        assert names.count(paged_attention.KERNEL_PAGED_DECODE) == 1
        assert ssd.KERNEL_CHUNK not in names
    else:
        assert names.count(ssd.KERNEL_CHUNK) == 1
        assert names.count(attention.KERNEL_FWD) == 1
        assert ssd.KERNEL_STEP not in names
    # every pool is updated in place: the state (33 slots of 128 x 8192
    # float32), the tail, the keys and the values
    assert cache["state"].shape == (1, 33, 128, 8192)
    assert compiled.memory_analysis().alias_size_in_bytes >= _held_bytes(
        cache)


# ------------------------------------ the seventh architecture's step
def _compile_hybrid_kda_moe(devices, which: str, slots: int = 32):
    """`HybridKDAMoE`'s decode step or 4096-token prefill: a KDA layer
    and a latent layer over experts at the published widths of
    Ling-3.0-flash (32 heads of 128 / 128 and a state of 128 x 4096 a lane;
    a latent row of 512 + 64; 128 of 512 experts of 2560 x 768 held, chosen
    among 4 of 8 groups), `slots` state slots and nobody's."""
    from ray_tpu.models.hybrid_kda_moe import (LATENT, LINEAR, SPARSE,
                                               HybridKDAMoE,
                                               HybridKDAMoEConfig)
    model = HybridKDAMoE(HybridKDAMoEConfig(
        vocab_size=1024, layer_types=(LINEAR, LATENT),
        mlp_layer_types=(SPARSE, SPARSE), experts_held=(0, 128),
        max_seq_len=4096))
    return _compile_served(
        devices, model, which, lambda: model.init_cache(
            PAGES, PAGE, fixed_pages=slots * model.fixed_pages(PAGE)),
        "mla_paged_decode_attn+kda_step", run=4)     # 20 KB of latent rows


@pytest.mark.parametrize("which", ["step", "prefill"])
def test_hybrid_kda_moe_programs_hold_their_kernels_and_alias_the_state(
        which, topo, no_compile_cache):
    from ray_tpu.ops import gated_delta, grouped_matmul, kda
    compiled, cache = _compile_hybrid_kda_moe(topo.devices, which)
    names = kernel_names(compiled.as_text())
    # gate, up and down of the held experts, two expert layers
    assert names.count(grouped_matmul.KERNEL_GMM) == 6
    if which == "step":
        assert names.count(kda.KERNEL_STEP) == 1
        assert names.count(paged_attention.KERNEL_MLA_PAGED_DECODE) == 1
        assert kda.KERNEL_CHUNK not in names
    else:
        assert names.count(kda.KERNEL_CHUNK) == 1
        assert names.count(attention.KERNEL_FWD) == 1
        assert kda.KERNEL_STEP not in names
    # the vector decay's kernels are no kernel of the scalar decay's
    assert not {gated_delta.KERNEL_STEP, gated_delta.KERNEL_CHUNK} & set(
        names)
    # every pool is updated in place: the state (33 slots of 128 x 4096
    # float32), the tail and the latent rows
    assert cache["state"].shape == (1, 33, 128, 4096)
    assert cache["kv"].shape == (1, PAGES, PAGE, 640)
    assert compiled.memory_analysis().alias_size_in_bytes >= _held_bytes(
        cache)


# ------------------------------------- the eighth architecture's step
def _compile_parallel_hybrid(devices, which: str, slots: int = 32,
                             layers: int = 1):
    """`ParallelHybrid`'s decode step or 4096-token prefill: `layers`
    layers of both mixers at the published widths of Falcon-H1-34B (32
    state-space heads of 128 in 2 groups and a state of 256 x 4096 a lane:
    a group of 2,048 columns is 2 MiB, so a block of the step kernel is
    half a group; 20 query heads over 4 kv heads of 128: a group of five
    in the page walk; a SwiGLU of 21,504), `slots` state slots and
    nobody's."""
    from ray_tpu.models.parallel_hybrid import (ParallelHybrid,
                                                ParallelHybridConfig)
    model = ParallelHybrid(ParallelHybridConfig(
        vocab_size=1024, n_layers=layers, max_seq_len=4096))
    return _compile_served(
        devices, model, which, lambda: model.init_cache(
            PAGES, PAGE, fixed_pages=slots * model.fixed_pages(PAGE)),
        "paged_decode_attn+ssd_step", run=4)    # 16 KB a pool's page


@pytest.mark.parametrize("which", ["step", "prefill"])
def test_parallel_hybrid_programs_hold_both_mixers_kernels_in_every_layer(
        which, topo, no_compile_cache):
    from ray_tpu.ops import ssd
    compiled, cache = _compile_parallel_hybrid(topo.devices, which,
                                               layers=2)
    names = kernel_names(compiled.as_text())
    if which == "step":         # one of each a layer
        assert names.count(ssd.KERNEL_STEP) == 2
        assert names.count(paged_attention.KERNEL_PAGED_DECODE) == 2
        assert not {ssd.KERNEL_CHUNK, attention.KERNEL_FWD} & set(names)
    else:
        assert names.count(ssd.KERNEL_CHUNK) == 2
        assert names.count(attention.KERNEL_FWD) == 2
        assert not {ssd.KERNEL_STEP,
                    paged_attention.KERNEL_PAGED_DECODE} & set(names)
    # every pool is updated in place, all four under one layer index: the
    # states (33 slots of 256 x 4096 float32 a layer), the tails, the keys
    # and the values
    assert cache["state"].shape == (2, 33, 256, 4096)
    assert cache["k"].shape == cache["v"].shape == (2, PAGES, PAGE, 512)
    assert compiled.memory_analysis().alias_size_in_bytes >= _held_bytes(
        cache)


# ------------------------------------- the ninth architecture's step
def _compile_gated_conv_moe(devices, which: str, slots: int = 64,
                            layers: int = 2):
    """`GatedConvMoE`'s decode step or 4096-token prefill at the published
    widths of LFM2-8B-A1B (32 query heads over 8 kv heads of 64: a pool
    row of 512 numbers, two kv heads a 128-lane, eight query rows a lane in
    the page walk; a gated convolution of 3 taps over 2,048 channels; a
    dense layer, then 8 of the 32 experts of 1,792, 4 a token): `layers`
    layers, convolution and attention by turns, `slots` tail slots and
    nobody's."""
    from ray_tpu.models.gated_conv_moe import (GatedConvMoE,
                                               GatedConvMoEConfig)
    model = GatedConvMoE(GatedConvMoEConfig(
        vocab_size=1024, layer_types=("conv", "full_attention") * (
            layers // 2) + ("conv",) * (layers % 2), num_experts=8,
        num_dense_layers=1, max_seq_len=4096))
    return _compile_served(
        devices, model, which, lambda: model.init_cache(
            PAGES, PAGE, fixed_pages=slots * model.fixed_pages(PAGE)),
        "paged_decode_attn", run=4)             # 16 KB a pool's page


@pytest.mark.parametrize("which", ["step", "prefill"])
def test_gated_conv_moe_programs_hold_a_kernel_an_attention_layer(
        which, topo, no_compile_cache):
    """Heads of 64 reach both kernels: no gather stands in for the page
    walk (`decode_attention` said so before the trace), one call an
    attention layer."""
    from ray_tpu.ops import grouped_matmul
    compiled, cache = _compile_gated_conv_moe(topo.devices, which, layers=4)
    names = kernel_names(compiled.as_text())
    # gate, up and down of the experts, three expert layers
    assert names.count(grouped_matmul.KERNEL_GMM) == 9
    if which == "step":
        assert names.count(paged_attention.KERNEL_PAGED_DECODE) == 2
        assert attention.KERNEL_FWD not in names
    else:
        assert names.count(attention.KERNEL_FWD) == 2
        assert paged_attention.KERNEL_PAGED_DECODE not in names
    # every pool is updated in place: the keys and values of two layers
    # (rows of 512) and two layers' tails, 65 slots of 2 x 16 x 128
    assert cache["k"].shape == cache["v"].shape == (2, PAGES, PAGE, 512)
    assert cache["tail"].shape == (2, 65, 2, 16, 128)
    assert "state" not in cache
    assert compiled.memory_analysis().alias_size_in_bytes >= _held_bytes(
        cache)


# ------------------------- the recurrent classes' convolution in a step
@pytest.mark.parametrize("compile_step,tail", [
    (_compile_hybrid_delta, (3, 96, 128)),      # 11,520 channels in 12,288
    (_compile_hybrid_ssm_moe, (3, 80, 128)),    # 10,240, under a bias
    (_compile_hybrid_kda_moe, (3, 96, 128)),    # 12,288
    (_compile_parallel_hybrid, (3, 48, 128)),   # 5,120 in 6,144, a bias
    (_compile_gated_conv_moe, (2, 16, 128)),    # 2,048, linear, no state
], ids=["HybridDelta", "HybridSSMMoE", "HybridKDAMoE", "ParallelHybrid",
        "GatedConvMoE"])
def test_a_step_scatters_the_tail_pool_once_in_place_as_it_lies(
        compile_step, tail, topo, no_compile_cache):
    """The step of each class that keeps a convolution's tail writes the
    lanes' rows with one fused `scatter` over the pool in the layout it is
    written in (`tail_shape`'s whole tiles: the slots ahead of a slot's
    rows), in place: no loop of `dynamic-update-slice` a lane (the flat
    pool's, whose slots the compiler laid behind a slot's numbers: 2.6 us a
    lane and layer, PERF.md PR 51), no copy of the pool. 2048 slots: a
    pool of 126-151 MB, since one small enough the compiler holds in fast
    memory over the whole step, a copy each way (the 33 slots of one layer
    above, and Nemotron's five layers' 10 MB in its cell, at the parent
    too)."""
    compiled, cache = compile_step(topo.devices, "step", slots=2048)
    text = compiled.as_text()
    assert cache["tail"].shape == (1, 2049) + tail
    dims = ",".join(map(str, cache["tail"].shape[1:]))
    # (layout, operation) of every instruction that gives a pool's shape,
    # with the one layer ahead or without
    made = re.findall(
        rf"= bf16\[(?:1,)?{dims}\]\{{([\d,]+)[^\n]*? ([\w\-]+)\(", text)
    assert {layout for layout, _ in made} == {"4,3,2,1,0", "3,2,1,0"}
    ops = [op for _, op in made]
    assert ops.count("scatter") == 1
    assert set(ops) == {"parameter", "bitcast", "fusion", "scatter"}
    assert compiled.memory_analysis().alias_size_in_bytes >= _held_bytes(
        cache)
