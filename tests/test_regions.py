"""The rule of `ray_tpu/models/regions.py`, class by class: in the lowered
text of every program a serving or training cell times (the decode step and
the prefill as the engine jits them, lowered for a TPU so that the Pallas
kernels are in them; `loss` and its gradient; the engine's `_next` and
`_place`), every matmul, kernel, gather, scatter and reduction lies under a
region, its own `op_name` path's or the path's of a call that leads to it,
and each class shows the regions its layers should. Tiny sizes, lowering
only: nothing is compiled or run."""
import re

import pytest

import jax
import jax.numpy as jnp

import ray_tpu.models as M
from ray_tpu.models import config as C
from ray_tpu.models import regions as R
from ray_tpu.ops.dispatch import compute_platform

TINY = {"transformer": C.tiny,
        "mla_moe": M.mla_moe.tiny_mla_moe,
        "gqa_window_moe": M.gqa_window_moe.tiny_gqa_window_moe,
        "hybrid_delta": M.hybrid_delta.tiny_hybrid_delta,
        "shortcut_mla_moe": M.shortcut_mla_moe.tiny_shortcut_mla_moe,
        "hybrid_ssm_moe": M.hybrid_ssm_moe.tiny_hybrid_ssm_moe,
        "hybrid_kda_moe": M.hybrid_kda_moe.tiny_hybrid_kda_moe,
        "parallel_hybrid": M.parallel_hybrid.tiny_parallel_hybrid,
        "gated_conv_moe": M.gated_conv_moe.tiny_gated_conv_moe,
        # (a choice of 16: the 32-token prompt and the tables pass it)
        "sparse_mla_moe": lambda: M.sparse_mla_moe.tiny_sparse_mla_moe(
            index_topk=16),
        # (and a window of 21: the prompt and the tables pass that too; the
        # ring's counts, `latent.RING_COUNTS`, are sums under `r.cache`)
        "sparse_window_mla_moe": lambda: (
            M.sparse_window_mla_moe.tiny_sparse_window_mla_moe(
                index_topk=16))}
assert set(TINY) == set(M.MODELS)

ALWAYS = {R.EMBED, R.NORM, R.ATTN_IN, R.ATTN_CORE, R.ATTN_OUT, R.FFN, R.HEAD}
MIXER = {R.MIXER_IN, R.MIXER_CORE, R.MIXER_OUT}
EXPERTS = {R.MOE_ROUTE, R.MOE_EXPERTS}
SHOWS = {"transformer": ALWAYS, "mla_moe": ALWAYS | EXPERTS,
         "gqa_window_moe": ALWAYS | EXPERTS, "hybrid_delta": ALWAYS | MIXER,
         "shortcut_mla_moe": ALWAYS | EXPERTS,
         "hybrid_ssm_moe": ALWAYS | MIXER | EXPERTS,
         "hybrid_kda_moe": ALWAYS | MIXER | EXPERTS,
         "parallel_hybrid": ALWAYS | MIXER,
         "gated_conv_moe": ALWAYS | MIXER | EXPERTS,
         "sparse_mla_moe": ALWAYS | EXPERTS | {R.ATTN_INDEX},
         "sparse_window_mla_moe": ALWAYS | EXPERTS | {R.ATTN_INDEX}}

PAGE, LANES, PROMPT, TABLE = 16, 4, 32, 8
# the instructions the rule is about ("custom-call": a Pallas kernel, and
# what a backend makes of a `ragged_dot`)
HEAVY = {"dot", "convolution", "ragged-dot", "custom-call", "gather",
         "scatter", "reduce", "reduce-window", "sort"}
_REGION = re.compile(r"\br\.[a-z_]+")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) (?:\(.*)?\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][a-z\-]*)\(")
_CALLED = re.compile(
    r"(?:to_apply|calls|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def hlo_text(lowered) -> str:
    from jax._src.lib import xla_client
    options = xla_client._xla.HloPrintOptions.short_parsable()
    options.print_metadata = True
    return lowered.compiler_ir("hlo").as_hlo_module().to_string(options)


def walk(text: str):
    """(instructions under no region, regions seen): an instruction is
    under a region if its own `op_name` holds one or the `op_name` of a
    call on the way to it from the entry does, as XLA joins the two when
    it inlines the call."""
    computations, entry, current = {}, None, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = computations.setdefault(m.group(1), [])
            if line.startswith("ENTRY"):
                entry = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or current is None:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        called = [c.strip().lstrip("%") for a, b in _CALLED.findall(line)
                  for c in (a or b).split(",") if c.strip()]
        current.append((m.group(1), m.group(2),
                        name.group(1) if name else "", called))
    bare, seen, done = [], set(), set()

    def visit(computation: str, scoped: bool):
        if (computation, scoped) in done:
            return
        done.add((computation, scoped))
        for name, opcode, op_name, called in computations.get(
                computation, ()):
            found = _REGION.findall(op_name)
            seen.update(found)
            inside = scoped or bool(found)
            if opcode in HEAVY and not inside:
                bare.append(f"{opcode} {name} in {computation}: "
                            f"op_name {op_name!r}")
            for callee in called:
                # a reduction's or a sort's own scalar computation says
                # nothing; everything else inherits
                if opcode not in ("reduce", "reduce-window", "sort",
                                  "scatter", "all-reduce"):
                    visit(callee, inside)
    visit(entry, False)
    return bare, seen


def shapes(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


@pytest.fixture(scope="module", params=sorted(TINY))
def built(request):
    model = M.build_model(TINY[request.param]())
    params = shapes(jax.eval_shape(model.init, jax.random.key(0)))
    extra = {"fixed_pages": 8} if model.fixed_pages(PAGE) else {}
    cache = shapes(jax.eval_shape(
        lambda: model.init_cache(32, PAGE, **extra)))
    return request.param, model, params, cache


def lower(built, program: str):
    _, model, params, cache = built
    i32 = jnp.int32

    def sds(*shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def _step(params, cache, tokens, positions, tables, active):
        return model.decode_step(params, cache, tokens, positions, tables,
                                 active, PAGE)

    def _pre(params, tokens, true_len, table, cache):
        return model.prefill(params, tokens, true_len, table, cache, PAGE)

    def loss(params, tokens):
        return model.loss(params, {"tokens": tokens})

    with compute_platform("tpu"):   # the table as wide as the class says
        table = model.table_pages(PAGE, TABLE)
    if program == "_step":
        traced = jax.jit(_step, donate_argnums=(1,)).trace
        args = (params, cache, sds(LANES), sds(LANES), sds(LANES, table),
                sds(LANES, dtype=jnp.bool_))
    elif program == "_pre":
        traced = jax.jit(_pre, donate_argnums=(4,)).trace
        args = (params, sds(PROMPT), sds(), sds(table), cache)
    else:
        traced = jax.jit(loss if program == "loss"
                         else jax.grad(loss)).trace
        args = (params, sds(2, PROMPT))
    if program in ("_step", "_pre"):        # with the kernels in them
        with compute_platform("tpu"):
            return traced(*args).lower(lowering_platforms=("tpu",))
    return traced(*args).lower()


@pytest.mark.parametrize("program", ["_step", "_pre", "loss", "grad"])
def test_every_heavy_operation_lies_in_a_region(built, program):
    text = hlo_text(lower(built, program))
    bare, seen = walk(text)
    assert not bare, "\n".join(bare[:20])
    kernels = text.count('custom_call_target="tpu_custom_call"')
    assert (kernels > 0) == (program in ("_step", "_pre"))
    if program != "grad":
        missing = SHOWS[built[0]] - seen
        assert not missing, f"{built[0]}.{program} shows no {missing}"
    if program in ("_step", "_pre"):
        assert R.CACHE in seen


def test_a_bare_matmul_is_found():
    """The walk itself: a program with one matmul outside every region and
    one inside a called function under one."""
    def f(x, w):
        with R.region(R.FFN):
            y = jax.nn.softmax(jnp.take(x, jnp.arange(4), axis=0) @ w)
        return y @ w

    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    bare, seen = walk(hlo_text(jax.jit(f).lower(x, w)))
    assert len(bare) == 1 and bare[0].startswith("dot"), bare
    assert seen == {R.FFN}


def test_names_are_distinct_and_prefixed():
    assert len(set(R.ALL)) == len(R.ALL)
    assert all(re.fullmatch(r"r\.[a-z_]+", name) for name in R.ALL)


def test_the_engines_own_programs_are_sampling():
    """`_next` and `_place` as `EngineCore` jits them."""
    from ray_tpu.serve.llm.engine import EngineCore
    config = C.tiny()
    core = EngineCore(config, M.build_model(config).init(jax.random.key(0)),
                      max_batch=2, num_pages=8, page_size=PAGE)
    logits = jax.ShapeDtypeStruct((2, 256), jnp.float32)
    for lowered in (
            core._next_fn.lower(logits, {}),
            core._place_fn.lower(jax.ShapeDtypeStruct((2,), jnp.int32),
                                 jax.ShapeDtypeStruct((), jnp.int32),
                                 jax.ShapeDtypeStruct((256,), jnp.float32))):
        bare, seen = walk(hlo_text(lowered))
        assert not bare and seen == {R.SAMPLE}, (bare, seen)
