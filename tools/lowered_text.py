"""Do two checkouts build the same serving programs?

    python3 tools/lowered_text.py <checkout> <configuration> [...]

prints, for each named file under `<checkout>/benchmarks/configs`, a sha256
and the line count of the lowered text of its class's `decode_step` and
1024-token `prefill` at the cell's shapes (lanes, pages, context). CPU
lowering is enough: the text names every operation and shape, and no weight
is made. Run it on the parent and on the change and compare: where the
hashes agree the programs are the same operations. (A program that holds a
Pallas kernel is also keyed by its source lines, which this does not see:
PERF.md section 6, PR 39.)
"""
import functools
import hashlib
import json
import os
import sys


def lowered(root: str, name: str) -> dict:
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import modelcfg
    from benchmarks.harness.weights import leaves
    from ray_tpu.models import build_model
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
    texts = {
        "decode_step": jax.jit(
            functools.partial(model.decode_step, page_size=page),
            donate_argnums=1).lower(
                params, cache, S((lanes,), jnp.int32), S((lanes,), jnp.int32),
                S((lanes, table), jnp.int32), S((lanes,), jnp.bool_)),
        "prefill_1024": jax.jit(
            functools.partial(model.prefill, page_size=page),
            donate_argnums=4).lower(
                params, S((1024,), jnp.int32), S((), jnp.int32),
                S((table,), jnp.int32), cache)}
    return {which: {"sha256": hashlib.sha256(
        low.as_text().encode()).hexdigest()[:16],
        "lines": low.as_text().count("\n")} for which, low in texts.items()}


def main():
    root = os.path.abspath(sys.argv[1])
    sys.path.insert(0, root)
    os.chdir(root)
    print(json.dumps({name: lowered(root, name) for name in sys.argv[2:]},
                     indent=1))


if __name__ == "__main__":
    main()
