"""Do two checkouts build the same serving programs?

    python3 tools/lowered_text.py [--tpu] <checkout> <configuration> [...]

prints, for each named file under `<checkout>/benchmarks/configs`, a sha256
and the line count of the lowered text of its class's `decode_step` and
1024-token `prefill` at the cell's shapes (lanes, pages, context). CPU
lowering is enough: the text names every operation and shape, and no weight
is made. Run it on the parent and on the change and compare: where the
hashes agree the programs are the same operations. (A program that holds a
Pallas kernel is also keyed by its source lines, which this does not see:
PERF.md section 6, PR 39.)

Lowered for the CPU a flash or paged kernel is the einsum in its place, so
the text does not hold a kernel's blocks. `--tpu` as the first argument
traces for a TPU instead (no chip: `compute_platform("tpu")`) and hashes
the traced program, the Pallas calls with their grids and bodies in it and
the source lines dropped (a call carries its call path's files and lines,
which differ between two checkouts whatever they hold); each program's
line also gives the grids of its calls over four axes (the flash
forward's: batch, heads, query blocks, key blocks).
"""
import functools
import hashlib
import json
import os
import re
import sys


def lowered(root: str, name: str, tpu: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import modelcfg
    from benchmarks.harness.weights import leaves
    from ray_tpu.models import build_model
    from ray_tpu.ops.dispatch import compute_platform
    with open(os.path.join(root, "benchmarks/configs", name + ".json")) as f:
        cfg = json.load(f)
    module, dep = modelcfg.load_model(cfg), cfg["deployment"]
    model = build_model(module.program_config(
        cfg, max_seq_len=dep["context_limit"]))
    flat, treedef = leaves(module.weight_shapes(module.sizes(cfg)))
    S = jax.ShapeDtypeStruct
    params = jax.tree_util.tree_unflatten(
        treedef, [S(shape, jnp.bfloat16) for shape, _ in flat])
    page, lanes = dep["page_size"], dep["max_batch"]
    fixed = getattr(model, "fixed_pages", lambda page: 0)(page) * lanes
    cache = jax.eval_shape(lambda: model.init_cache(
        dep["num_pages"], page, **({"fixed_pages": fixed} if fixed else {})))
    table = dep["context_limit"] // page
    programs = {
        "decode_step": (jax.jit(
            functools.partial(model.decode_step, page_size=page),
            donate_argnums=1), (
                params, cache, S((lanes,), jnp.int32), S((lanes,), jnp.int32),
                S((lanes, table), jnp.int32), S((lanes,), jnp.bool_))),
        "prefill_1024": (jax.jit(
            functools.partial(model.prefill, page_size=page),
            donate_argnums=4), (
                params, S((1024,), jnp.int32), S((), jnp.int32),
                S((table,), jnp.int32), cache))}
    out = {}
    for which, (fn, args) in programs.items():
        with compute_platform("tpu" if tpu else None):
            traced = fn.trace(*args)
        # a kernel's call carries its call path's files and lines
        text = (re.sub(r" at [^\s\]]+:\d+", "", str(traced.jaxpr)) if tpu
                else traced.lower().as_text())
        out[which] = {"sha256": hashlib.sha256(text.encode()).hexdigest()[:16],
                      "lines": text.count("\n")}
        if tpu:
            out[which]["grids"] = re.findall(
                r"grid=(\(\d+, \d+, \d+, \d+\))", text)
    return out


def main():
    argv = sys.argv[1:]
    tpu = argv[:1] == ["--tpu"]
    root, *names = argv[1:] if tpu else argv
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    os.chdir(root)
    print(json.dumps({name: lowered(root, name, tpu) for name in names},
                     indent=1))


if __name__ == "__main__":
    main()
